"""The comparison that decides ``correct``: what the timed path produced
against the plain reference, as numbers each held to a limit.

Serving compares, for a sample of sessions drawn from the seed, every
window prediction the session received, its OSSL delta and, where the
session still holds its lane, its neuron state. Training compares the
first three steps of the very step object the window drives. The limits
live in the configuration file under ``limits``; ``PERF.md`` gives the
readings each was set from.

A window's logits are a function of its spike trains alone (the readout
of the traces), so the program and the reference agree on them to the bit
unless a spike differs. Where float32 rounding leaves a membrane within
rounding of the threshold, one spike flips and that window's logits move
by 1e-5 to 0.05; such flips are rare and stay in their window. Serving
therefore holds two numbers: the median window's logit gap, and the share
of windows whose gap exceeds ``GAP_TOL`` — far above a float32 reordering
of the readout (~1e-7), far below one flipped spike — and the median
compared stream's gap in its OSSL delta norms.

With a control precision (``readings.py --control``) the reference computed at
that precision takes the program's place: the numbers held to the limits
are the control's, so a control run comes out not correct. The program's
readings are printed beside them.
"""
from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from bench.harness import Check

GAP_TOL = 1e-6           # a window's logits differ beyond f32 rounding

# The readings of the last comparison, for ``bench/readings.py``: the
# program's, and the control's where one ran.
last_readings: Dict[str, Dict[str, float]] = {}


def _pad_len(n: int, step: int = 500) -> int:
    """Replay length bucket, so that few reference programs compile."""
    return max(step, -(-n // step) * step)


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def serve_replay(cfg, params, traffic, entries,
                 block: int = 16) -> List[Dict[str, Any]]:
    """Run the reference, in ``cfg["precision"]``, over each entry's stream
    (as many timesteps as the program was fed, clipping the delta at the
    same chunk ends), in blocks of ``block`` streams. Returns per entry the
    window logits ``[W, n_out]``, per-layer delta norms and the final
    state."""
    from bench.reference import snn as ref
    out = []
    for b in range(0, len(entries), block):
        part = entries[b:b + block]
        lens = [sum(n for _, n in e["pops"]) for e in part]
        tmax = _pad_len(max(lens))
        ev = np.zeros((len(part), tmax, cfg["n_in"]), np.float32)
        ce = np.zeros((len(part), tmax), bool)
        for i, e in enumerate(part):
            plan = traffic.plans[e["sid"]]
            ev[i, :lens[i]] = traffic.events(plan, lens[i])
            pos = 0
            for _, n in e["pops"]:
                if n:
                    pos += n
                    ce[i, pos - 1] = True
        carry, logits, ends = ref.serve_streams(cfg, params, ev, lens, ce)
        carry = {k: np.asarray(v) for k, v in carry._asdict().items()}
        logits, ends = np.asarray(logits), np.asarray(ends)
        for i in range(len(part)):
            d = carry["delta"][i]
            out.append({
                "logits": logits[i][ends[i]],
                "delta_norms": np.sqrt((d.reshape(d.shape[0], -1) ** 2)
                                       .sum(-1)),
                "state": {k: carry[k][i] for k in
                          ("v", "tr", "tr_pc", "tr_cc", "x_tr", "ss_mean",
                           "t_win")},
            })
    return out


def program_entry(e) -> Dict[str, Any]:
    """The program's outputs for one session in the reference's form."""
    d = np.asarray(e["delta"], np.float64)
    st = e["state"]
    state = None
    if st is not None:
        state = {"v": st.layers.v, "tr": st.layers.tr,
                 "tr_pc": st.layers.tr_pc, "tr_cc": st.layers.tr_cc,
                 "x_tr": st.x_tr, "ss_mean": st.ss_mean,
                 "t_win": st.t_in_window}
    return {"logits": (np.stack(e["logits"]) if e["logits"]
                       else np.zeros((0, 0))),
            "delta_norms": np.sqrt((d.reshape(d.shape[0], -1) ** 2).sum(-1)),
            "state": state}


def serve_readings(got: List[Dict], ref: List[Dict]) -> Dict[str, float]:
    """Numbers of ``got`` (the program, or the control) against ``ref``."""
    gaps, missing = [], 0
    dn_gap, dn_ref = [], []
    x_gap, tr_gap, ss_gap, t_miss = 0.0, [], 0.0, 0
    for g, r in zip(got, ref):
        w = min(len(g["logits"]), len(r["logits"]))
        missing += abs(len(g["logits"]) - len(r["logits"]))
        if w:
            gaps.extend(np.abs(np.asarray(g["logits"][:w], np.float64)
                               - r["logits"][:w]).max(-1).tolist())
        dn_gap.extend(np.abs(g["delta_norms"] - r["delta_norms"]).tolist())
        dn_ref.extend(r["delta_norms"].tolist())
        if g["state"] is not None:
            gs, rs = g["state"], r["state"]
            x_gap = max(x_gap, float(np.abs(gs["x_tr"] - rs["x_tr"]).max()))
            tr_gap.append(float(np.abs(gs["tr"] - rs["tr"]).max()))
            ss_gap = max(ss_gap,
                         float(np.abs(gs["ss_mean"] - rs["ss_mean"]).max()))
            t_miss += int(np.asarray(gs["t_win"]) != np.asarray(rs["t_win"]))
    gaps = np.asarray(gaps) if gaps else np.full(1, np.inf)
    dn_ref = np.asarray(dn_ref)
    scale = np.maximum(dn_ref, np.median(dn_ref)) if len(dn_ref) else 1.0
    dn_rel = np.asarray(dn_gap) / np.where(scale > 0, scale, 1.0)
    return {
        "windows_compared": float(len(gaps)),
        "windows_missing": float(missing),
        "windows_off_share": float(np.mean(gaps > GAP_TOL)),
        "logit_gap_median": float(np.median(gaps)),
        "logit_gap_p90": float(np.quantile(gaps, 0.9)),
        "logit_gap_max": float(gaps.max()),
        "delta_norm_gap_median": float(np.median(dn_rel)) if len(dn_rel)
        else np.inf,
        "delta_norm_gap_max": float(dn_rel.max()) if len(dn_rel) else np.inf,
        "x_trace_gap_max": x_gap,
        "trace_gap_median": float(np.median(tr_gap)) if tr_gap else 0.0,
        "ss_mean_gap_max": ss_gap,
        "window_position_mismatches": float(t_miss),
    }


def serve_checks(cfg, params, traffic, program, seed,
                 control: str = "") -> List[Check]:
    """Checks of the program's sample against the reference; with
    ``control`` (a lower matmul precision) the control's readings are
    checked in the program's place."""
    from bench.harness import eprint
    ref = serve_replay(cfg, params, traffic, program)
    got = [program_entry(e) for e in program]
    rd = serve_readings(got, ref)
    eprint("readings(program): " + _fmt(rd))
    last_readings.clear()
    last_readings["program"] = rd
    if control:
        ctl = serve_replay({**cfg, "precision": control}, params, traffic,
                           program)
        for c, g in zip(ctl, got):      # state only where the program's is
            if g["state"] is None:
                c["state"] = None
        rd = last_readings["control"] = serve_readings(ctl, ref)
        eprint(f"readings(control {control}): " + _fmt(rd))
    return [Check(k, rd[k], float(v)) for k, v in cfg["limits"].items()
            if k in rd]


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------

def _leaves(p) -> Dict[str, np.ndarray]:
    """Per-layer float leaves of the weights: hidden w and readout."""
    w, r = np.asarray(p["hidden"]["w"]), np.asarray(p["readout"])
    out = {f"w{l}": w[l] for l in range(w.shape[0])}
    out.update({f"readout{l}": r[l] for l in range(r.shape[0])})
    return out


def nm_violations(cfg, mask) -> int:
    """(group, column) pairs that do not keep exactly n inputs."""
    from bench.reference.snn import nm_counts
    m, n = nm_counts(cfg)
    mask = np.asarray(mask)
    L, K, N = mask.shape
    return int((mask.reshape(L, K // m, m, N).sum(2) != n).sum())


def train_readings(cfg, p0, got: List[Dict], ref: List[Dict]):
    """``got``/``ref``: per step ``{"params", "local_loss"}``, steps 1-3.

    Each norm is compared by its worst leaf: the gap between the two
    norms over the larger of the reference's norm of that leaf and of the
    median leaf. Leaves whose reference first update is under a thousandth
    of the median leaf's (moved by round-off alone) are left out."""
    l0 = _leaves(p0)

    def norms(p):
        lp = _leaves(p)
        return {k: float(np.linalg.norm(lp[k] - l0[k])) for k in l0}

    r1 = norms(ref[0]["params"])
    med1 = float(np.median(list(r1.values())))
    keep = [k for k in l0 if r1[k] >= 1e-3 * med1]

    def norm_gap(a, b):
        ga, gb = norms(a), norms(b)
        med = float(np.median([gb[k] for k in keep]))
        return max(abs(ga[k] - gb[k]) / max(gb[k], med, 1e-30)
                   for k in keep)

    loss = max(abs(g["local_loss"] - r["local_loss"])
               / max(abs(r["local_loss"]), 1e-30) for g, r in zip(got, ref))
    m_got = np.asarray(got[-1]["params"]["hidden"]["mask"])
    m_ref = np.asarray(ref[-1]["params"]["hidden"]["mask"])
    m_old = np.asarray(p0["hidden"]["mask"])
    return {
        "loss_gap": float(loss),
        "first_update_gap": float(norm_gap(got[0]["params"],
                                           ref[0]["params"])),
        "change_after_three_gap": float(norm_gap(got[-1]["params"],
                                                 ref[-1]["params"])),
        "mask_mismatch_share": float(np.mean(m_got != m_ref)),
        "nm_violations": float(nm_violations(cfg, m_got)),
        "reference_mask_changed": float(np.mean(m_ref != m_old)),
        "leaves_left_out": float(len(l0) - len(keep)),
    }


def train_checks(cfg, p0, batches, sample_idx, got, control: str = ""):
    """Checks of the program's first three steps against the reference;
    with ``control`` the control's steps are checked in their place."""
    from bench.harness import eprint
    from bench.reference.snn import train_steps
    ref = train_steps(cfg, p0, batches, sample_idx)
    rd = train_readings(cfg, p0, got, ref)
    eprint("readings(program): " + _fmt(rd))
    last_readings.clear()
    last_readings["program"] = rd
    if control:
        ctl = train_steps({**cfg, "precision": control}, p0, batches,
                          sample_idx)
        rd = last_readings["control"] = train_readings(cfg, p0, ctl, ref)
        eprint(f"readings(control {control}): " + _fmt(rd))
    return [Check(k, rd[k], float(v)) for k, v in cfg["limits"].items()
            if k in rd]


def _fmt(rd: Dict[str, float]) -> str:
    return " ".join(f"{k}={v!r}" for k, v in rd.items())
