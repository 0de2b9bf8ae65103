"""The comparison that decides ``correct`` in the live-DSST serving cell.

Beside the serving cells' stream checks (``bench/compare.py``), the
reference (``bench/reference/topology.py``) recomputes every epoch the
window ran from the inputs the program gave it — the accumulated fleet
factors, the ``[S]`` delta-norm vector, the lanes that may merge and the
deltas of the lanes chosen — starting from the benchmark's own weights:

* ``epoch_mismatches`` — epochs whose hot-lane choice or recycled count
  ``k`` differs from the reference's (exact);
* ``mask_mismatch_share`` — the program's final mask, and the mask of one
  more epoch run after the window, against the reference's (exact);
* ``nm_violations`` — (group, column) pairs of the final mask that do not
  keep exactly ``n`` (exact);
* ``base_gap`` — the final base weights' largest gap over the reference's
  largest weight;
* ``projected_delta_gap`` — that extra epoch's projected deltas of the
  compared lanes against the reference's projection of the same lanes;
* ``factor_sum_gap_median`` — one more grid step from the fleet's state
  after the window: the program's cross-chip sums of the DSST factors
  against the reference's float64 sum over every lane, the median over
  every (layer, unit) sum of both factors of the gap over the reference's
  sum. A spike that float32 rounding flips in one lane moves the sums of
  the few units it feeds by up to ~1e-3 of the largest (``factor_sum_gap``,
  the largest gap over the largest sum, printed, not held); a sum that
  leaves out a chip's lanes moves every unit's.

The sampled streams are replayed through the reference from admission,
with the reference's own base swapped in and the streams' deltas projected
(and zeroed where the lane merged) at each epoch's grid step, and held to
the serving cells' numbers: window logits, delta norms, state.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from bench import compare
from bench.harness import Check, eprint
from bench.reference import snn as ref
from bench.reference import topology as rtopo

last_readings = compare.last_readings


def epoch_chain(cfg, params0, records):
    """The reference's bases: ``[(w, mask)]`` before the first epoch and
    after each, each epoch's hot lanes, and the number of epochs whose
    choice or ``k`` differs from the program's."""
    svc = cfg["topology_service"]
    w = np.asarray(params0["hidden"]["w"], np.float32)
    mask = np.asarray(params0["hidden"]["mask"], bool)
    bases, hots, mismatches = [(w, mask)], [], 0
    for r in records:
        hot = rtopo.hot_lanes(r["norms"], r["eligible"], svc["merge_top"],
                              svc["merge_min_norm"])
        chosen = [int(h) for h, ok in zip(r["hot"], r["hot_ok"]) if ok]
        k_ref = [ref.dsst_k(cfg)] * cfg["n_layers"]
        mismatches += int(hot != chosen or list(r["k"]) != k_ref)
        lanes = [r["hot_deltas"][i] for i, ok in enumerate(r["hot_ok"])
                 if ok]
        w = rtopo.fold(w, mask, lanes, svc["merge_weight"])
        w, mask = rtopo.prune_regrow(cfg, w, mask, r["pre"])
        bases.append((w, mask))
        hots.append(hot)
    return bases, hots, mismatches


def _segment_fn(cfg):
    """A jitted run of R streams over one stretch at a fixed base, from
    given carries (the stretch of ``ref.serve_streams``)."""
    import jax
    import jax.numpy as jnp
    lr = cfg["lr"] * cfg["adapt"]["lr_scale"]

    def seg(w, mask, readout, carry, events, n_steps, chunk_end):
        def one(c, ev, n, ce):
            def body(c, inp):
                i, x, e = inp
                new, logits, end = ref._stream_timestep(
                    cfg, w, mask, readout, c, x, e, lr)
                live = i < n
                c = jax.tree_util.tree_map(
                    lambda a, b: jnp.where(live, a, b), new, c)
                return c, (logits, end & live)
            return jax.lax.scan(body, c, (jnp.arange(ev.shape[0]), ev, ce))
        return jax.vmap(one)(carry, events, n_steps, chunk_end)

    return jax.jit(seg)


def replay(cfg, bases, hots, boundaries, params0, traffic, entries):
    """Run the reference over each entry's stream, stretch by stretch:
    ``boundaries[e]`` is the grid step after which epoch ``e`` swapped in
    ``bases[e + 1]``. Returns per entry the serving comparison's form
    (``compare.serve_replay``)."""
    import jax
    import jax.numpy as jnp
    seg = _segment_fn(cfg)
    readout = jnp.asarray(params0["readout"], jnp.float32)
    R = len(entries)
    lens = [sum(n for _, n in e["pops"]) for e in entries]
    tmax = compare._pad_len(max(lens))
    ev = np.zeros((R, tmax, cfg["n_in"]), np.float32)
    ce = np.zeros((R, tmax), bool)
    cut = np.zeros((R, len(boundaries) + 2), np.int64)
    for i, e in enumerate(entries):
        plan = traffic.plans[e["sid"]]
        ev[i, :lens[i]] = traffic.events(plan, lens[i])
        pos = 0
        for _, n in e["pops"]:
            if n:
                pos += n
                ce[i, pos - 1] = True
        for j, g in enumerate(boundaries):
            cut[i, j + 1] = sum(n for call, n in e["pops"] if call < g)
        cut[i, -1] = lens[i]
    carry = jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a, (R,) + a.shape),
        ref.fresh_stream(cfg))
    logits = [[] for _ in range(R)]
    keep = 1.0 - cfg["topology_service"]["merge_weight"]
    for s in range(len(boundaries) + 1):
        w, mask = bases[s]
        if s:       # epoch s-1: merged lanes reset, every delta projected
            d = np.array(carry.delta)
            for i, e in enumerate(entries):
                if e["slot"] in hots[s - 1]:
                    d[i] = 0.0 if keep <= 0.0 else d[i] * np.float32(keep)
            carry = carry._replace(delta=jnp.asarray(
                rtopo.project(d, bases[s - 1][1], mask)))
        n = cut[:, s + 1] - cut[:, s]
        width = compare._pad_len(max(1, int(n.max())))
        sev = np.zeros((R, width, cfg["n_in"]), np.float32)
        sce = np.zeros((R, width), bool)
        for i in range(R):
            a, b = cut[i, s], cut[i, s + 1]
            sev[i, :b - a], sce[i, :b - a] = ev[i, a:b], ce[i, a:b]
        carry, (lg, ends) = seg(
            jnp.asarray(w), jnp.asarray(mask).astype(jnp.float32), readout,
            carry, jnp.asarray(sev), jnp.asarray(n, jnp.int32),
            jnp.asarray(sce))
        lg, ends = np.asarray(lg), np.asarray(ends)
        for i in range(R):
            logits[i].append(lg[i][ends[i]])
    c = {k: np.asarray(v) for k, v in carry._asdict().items()}
    out = []
    for i in range(R):
        d = c["delta"][i]
        out.append({
            "logits": np.concatenate(logits[i]) if logits[i]
            else np.zeros((0, cfg["n_out"])),
            "delta_norms": np.sqrt((d.reshape(d.shape[0], -1) ** 2).sum(-1)),
            "state": {k: c[k][i] for k in ("v", "tr", "tr_pc", "tr_cc",
                                           "x_tr", "ss_mean", "t_win")}})
    return out


def _lane_carries(extra, mask):
    """The fleet's carries after the window, lanes ``b0:b1`` at a time,
    deltas densified over the reference's own mask."""
    import jax.numpy as jnp
    st, dl = extra["state"], extra["deltas"]

    def lanes(b0, b1):
        return ref.StreamCarry(
            v=jnp.asarray(st["v"][b0:b1]), tr=jnp.asarray(st["tr"][b0:b1]),
            tr_pc=jnp.asarray(st["tr_pc"][b0:b1]),
            tr_cc=jnp.asarray(st["tr_cc"][b0:b1]),
            x_tr=jnp.asarray(st["x_tr"][b0:b1]),
            ss_mean=jnp.asarray(st["ss_mean"][b0:b1]),
            t_win=jnp.asarray(st["t_win"][b0:b1], jnp.int32),
            delta=jnp.asarray(rtopo.densify(dl[b0:b1], mask)))
    return lanes


def factor_gaps(sums, want) -> Dict[str, float]:
    """``sums`` against ``want`` (each ``(pre [L, ·], post [L, N])``): the
    median over every sum of its gap over the reference's, and the larger
    of the two factors' largest gap over its largest sum."""
    rel, worst = [], []
    for got, ref_sum in zip(sums, want):
        got = np.asarray(got, np.float64)[:, :ref_sum.shape[1]]
        gap = np.abs(got - ref_sum)
        rel.append((gap / np.maximum(np.abs(ref_sum), 1e-30)).ravel())
        worst.append(float(gap.max() / max(np.abs(ref_sum).max(), 1e-30)))
    return {"factor_sum_gap_median": float(np.median(np.concatenate(rel))),
            "factor_sum_gap": max(worst)}


def fleet_factor_sums(cfg, w, mask, extra):
    """The reference's factor sums over every lane of the extra step."""
    return rtopo.factor_sums(cfg, w, mask, _lane_carries(extra, mask),
                             extra["events"], extra["valid"])


def projection_readings(cfg, w, mask, extra) -> Dict[str, float]:
    """The extra epoch after the window (no lane merges) against the
    reference's: its new mask, and the compared lanes' projection."""
    pe = extra["epoch"]
    _, new_mask = rtopo.prune_regrow(cfg, w, mask, pe["pre"])
    before = rtopo.densify(pe["before"], mask)
    want = rtopo.compact(rtopo.project(before, mask, new_mask), new_mask)
    got = np.asarray(pe["after"], np.float32).reshape(want.shape)
    return {"extra_mask_mismatch_share":
            float(np.mean(np.asarray(pe["mask"]) != new_mask)),
            "projected_delta_gap": float(np.abs(got - want).max())}


def live_checks(cfg, params0, traffic, program, records, final, extra,
                control: str = "") -> List[Check]:
    """Every number of the live cell against its limit; with ``control``
    the reference at that precision takes the program's place."""
    bases, hots, mismatches = epoch_chain(cfg, params0, records)
    w_ref, m_ref = bases[-1]
    boundaries = [r["grid_step"] for r in records]
    ref_out = replay(cfg, bases, hots, boundaries, params0, traffic, program)
    got = [compare.program_entry(e) for e in program]
    rd = compare.serve_readings(got, ref_out)
    proj = projection_readings(cfg, w_ref, m_ref, extra)
    m_got = np.asarray(final["mask"], bool)
    w_got = np.asarray(final["w"], np.float32)
    rd.update({
        "epochs": float(len(records)),
        "epoch_mismatches": float(mismatches),
        "mask_mismatch_share": max(float(np.mean(m_got != m_ref)),
                                   proj["extra_mask_mismatch_share"]),
        "nm_violations": float(compare.nm_violations(cfg, m_got)),
        "base_gap": float(np.abs(w_got - w_ref).max()
                          / max(np.abs(w_ref).max(), 1e-30)),
        "projected_delta_gap": proj["projected_delta_gap"],
    })
    ref_sums = fleet_factor_sums(cfg, w_ref, m_ref, extra)
    rd.update(factor_gaps((extra["pre_sum"], extra["post_sum"]), ref_sums))
    eprint("readings(program): " + compare._fmt(rd))
    last_readings.clear()
    last_readings["program"] = rd
    if control:
        ccfg = {**cfg, "precision": control}
        ctl = replay(ccfg, bases, hots, boundaries, params0, traffic,
                     program)
        for c, g in zip(ctl, got):
            if g["state"] is None:
                c["state"] = None
        crd = compare.serve_readings(ctl, ref_out)
        crd.update({k: 0.0 for k in ("epoch_mismatches",
                                     "mask_mismatch_share", "base_gap",
                                     "projected_delta_gap")})
        crd["epochs"] = rd["epochs"]
        crd["nm_violations"] = float(compare.nm_violations(cfg, m_ref))
        crd.update(factor_gaps(
            fleet_factor_sums(ccfg, w_ref, m_ref, extra), ref_sums))
        rd = last_readings["control"] = crd
        eprint(f"readings(control {control}): " + compare._fmt(rd))
    return [Check(k, rd[k], float(v)) for k, v in cfg["limits"].items()
            if k in rd]
