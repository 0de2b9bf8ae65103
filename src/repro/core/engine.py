"""One layer-stacked timestep engine shared by training and serving.

ElfCore's central architectural claim is that spike integration (SI) and the
weight update (WU) run *concurrently through the same datapath* for every
layer.  This module is that datapath, exactly once: :func:`_layer_timestep`
is the only per-timestep layer body in ``src/repro/core`` — both
``snn.run_sample`` (training: aligned batch, in-place base weights, one gate
decision per layer shared across the batch) and ``snn.run_chunk`` (serving:
slot axis, frozen base + per-stream deltas, per-slot gates, valid masking)
are thin wrappers over the scans built here.

Two structural decisions:

* **Layer stacking.**  Per-layer parameters and state live in pytrees with a
  leading ``[L, ...]`` layer axis (zero-padded on the fan-in dimension when
  layer fan-ins differ) and the depth loop is a ``lax.scan`` over that axis.
  Trace size and compile time no longer multiply with depth — the Fig. 7
  depth study and the ROADMAP's sharded-slot-grid work both need this.

* **One implementation per op.**  The inner ops (forward current, LIF
  step, WU outer product) are jnp, lowered by XLA for whatever device runs
  them; a Pallas kernel returns only when it wins a cell, and is then
  chosen from the N:M spec, never from a user option.
"""
from __future__ import annotations

from functools import partial
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..kernels.nm_spmm import ops as nm_ops, ref as nm_ref
from ..kernels.wu_outer import ref as wu_ref
from . import gating as gating_lib
from . import topology as topology_lib


# ---------------------------------------------------------------------------
# neuron math — the single source of truth (re-exported by core.snn)
# ---------------------------------------------------------------------------

def lif_step(v, tr, current, *, alpha, beta, theta):
    """One LIF timestep with soft reset + trace decay. Returns (v', tr', s)."""
    v = alpha * v + current
    s = (v >= theta).astype(v.dtype)
    v = v - s * theta
    tr = beta * tr + s
    return v, tr, s


def surrogate_grad(v, *, theta, width):
    """Triangular STE (the chip's STE LUT for the non-derivative spike fn)."""
    return jnp.maximum(0.0, 1.0 - jnp.abs(v - theta) / (theta * width))


def _cos(a, b, eps=1e-6):
    num = (a * b).sum(-1)
    den = jnp.linalg.norm(a, axis=-1) * jnp.linalg.norm(b, axis=-1) + eps
    return num / den


def _cos_grad(a, b, eps=1e-6):
    """d cos(a,b) / d a."""
    na = jnp.linalg.norm(a, axis=-1, keepdims=True) + eps
    nb = jnp.linalg.norm(b, axis=-1, keepdims=True) + eps
    c = ((a * b).sum(-1, keepdims=True)) / (na * nb)
    return b / (na * nb) - c * a / (na * na)


def ossl_modulator(tr, tr_pc, tr_cc, v, cfg):
    """Third factor of the three-factor rule, from purely local quantities.

    Local loss  L = -cos(tr, tr_pc) + cc_weight * cos(tr, tr_cc):
    *predict* (stay similar to) the earlier-TS trace of the same sample,
    *contrast* against the previous sample's final trace. The modulator is
    -dL/dtr shaped through the spike-function surrogate. PC and CC run
    concurrently (no class-transition flag) — ElfCore §II-C.
    """
    g = _cos_grad(tr, tr_pc) - cfg.cc_weight * _cos_grad(tr, tr_cc)
    return g * surrogate_grad(v, theta=cfg.theta, width=cfg.surrogate_width)


# ---------------------------------------------------------------------------
# stacked state / geometry
# ---------------------------------------------------------------------------

class LayerState(NamedTuple):
    """Three-trace neuron SRAM + membrane; leaves are stacked ``[L, R, N]``
    (``R`` = batch rows in training, slots in serving) inside the engine,
    or a per-layer ``[R, N]`` slice inside the layer scan."""
    v: jax.Array        # membrane
    tr: jax.Array       # current trace (WU slot)
    tr_pc: jax.Array    # earlier-TS snapshot (PC slot)
    tr_cc: jax.Array    # final trace of the previous sample (CC slot)


class Geometry(NamedTuple):
    fanins: Tuple[int, ...]
    k_max: int
    uniform: bool       # all layers share fan-in and spec


def geometry(cfg) -> Geometry:
    """Static layer-stack geometry: per-layer fan-ins, the zero-padded
    stack width ``k_max = max(fanins)``, and whether all layers share one
    fan-in (which unlocks the compact N:M layout)."""
    fanins = tuple(cfg.layer_fanins)
    k_max = max(fanins)
    uniform = len(set(fanins)) == 1
    return Geometry(fanins=fanins, k_max=k_max, uniform=uniform)


# one shared zero-padding helper with the rest of the topology layout code
_pad_rows = topology_lib._pad_rows


def _pad_cols(x, k):
    """Zero-pad the last axis of ``x`` to the stack width ``k``.

    A one-layer stack with ``n_hidden > n_in`` is the one case where a
    layer's output is wider than ``k``; its last layer's carry is never
    read, so it is cropped to keep the layer scan's carry shape fixed.
    """
    if x.shape[-1] == k:
        return x
    if x.shape[-1] > k:
        return x[..., :k]
    return jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, k - x.shape[-1]),))


def dense_masks(mask_stacked: jax.Array, cfg) -> jax.Array:
    """Stacked unit masks ``[L, KBmax, J]`` -> dense float ``[L, Kmax, N]``
    (zero rows where a layer's fan-in is below the stack width).

    The expansion itself lives with the rest of the topology lifecycle in
    ``core/topology.py``; this is the engine-facing alias.
    """
    return topology_lib.dense_masks(mask_stacked, cfg, dtype=jnp.float32)


def hidden_slice(params, l: int, cfg) -> Tuple[jax.Array, jax.Array]:
    """Layer ``l``'s (w ``[fan_in, N]``, unit_mask ``[KB, J]``) view of the
    stacked params — what tests and DSST inspect per layer."""
    fan_in = cfg.layer_fanins[l]
    spec = cfg.spec(fan_in)
    kb, jj = spec.unit_counts(fan_in, cfg.n_hidden)
    return (params["hidden"]["w"][l, :fan_in, :],
            params["hidden"]["mask"][l, :kb, :jj])


def stack_params(legacy, cfg):
    """PR-1 layout (lists of per-layer dicts) -> stacked layout.

    Checkpoint migration helper: old manifests keyed ``hidden/0/w`` etc.;
    restore into the legacy template, then stack.
    """
    geo = geometry(cfg)
    w = jnp.stack([_pad_rows(p["w"], geo.k_max) for p in legacy["hidden"]])
    mask = jnp.stack([_pad_rows(p["mask"], geo.k_max)
                      for p in legacy["hidden"]])
    return {"hidden": {"w": w, "mask": mask},
            "readout": jnp.stack(list(legacy["readout"]))}


def unstack_params(params, cfg):
    """Stacked layout -> PR-1 layout (for legacy consumers/tests)."""
    hidden = []
    for l in range(cfg.n_layers):
        w, m = hidden_slice(params, l, cfg)
        hidden.append({"w": w, "mask": m})
    return {"hidden": hidden,
            "readout": [params["readout"][l] for l in range(cfg.n_layers)]}


# ---------------------------------------------------------------------------
# weight layouts and the three inner ops
# ---------------------------------------------------------------------------

def compact_weights(w_stacked, mask_stacked, cfg):
    """Stacked dense weights + unit masks -> ``{"wc", "idx"}`` compact rep.

    The mask-free serving weight rep: values ``[L, J, T, bk, bo]`` and kept
    block ids ``[L, J, T]``. Requires uniform layer fan-in (the stacked
    ``idx`` shares one geometry across layers).
    """
    geo = geometry(cfg)
    if not geo.uniform:
        raise ValueError(
            "the compact N:M layout requires uniform layer fan-in "
            f"(got {geo.fanins}); use the dense rep / dense deltas instead")
    spec = cfg.spec(geo.fanins[0])
    wcs, idxs = [], []
    for l in range(cfg.n_layers):
        wc, idx = nm_ops.make_compact(
            w_stacked[l], mask_stacked[l], spec.block, spec.out_tile,
            n_kept=compact_kept(cfg))
        wcs.append(wc)
        idxs.append(idx)
    return {"wc": jnp.stack(wcs), "idx": jnp.stack(idxs)}


def compact_deltas(deltas, idx, cfg):
    """Dense slot-leading deltas ``[S, L, Kmax, N]`` -> compact
    ``[S, L, J, T, bk, bo]`` by gathering the kept blocks of ``idx``
    (``[L, J, T]``). Pure gather — bitwise for every kept coordinate."""
    spec = cfg.spec(cfg.layer_fanins[0])
    bk, bo = spec.block, spec.out_tile
    s, l_, k, n = deltas.shape
    db = deltas.reshape(s, l_, k // bk, bk, n // bo, bo)
    db = db.transpose(0, 1, 4, 2, 3, 5)            # [S, L, J, KB, bk, bo]
    return jnp.take_along_axis(db, idx[None, :, :, :, None, None], axis=3)


def densify_deltas(deltas_c, idx, cfg):
    """Compact slot-leading deltas ``[S, L, J, T, bk, bo]`` -> dense
    ``[S, L, Kmax, N]`` (zeros at pruned coordinates). Pure scatter into
    disjoint block rows — bitwise for every kept coordinate."""
    geo = geometry(cfg)
    s, l_, j, t, bk, bo = deltas_c.shape
    kb = geo.k_max // bk
    li = jnp.arange(l_)[:, None, None]
    ji = jnp.arange(j)[None, :, None]
    db = jnp.zeros((s, l_, j, kb, bk, bo), deltas_c.dtype)
    db = db.at[:, li, ji, idx].add(deltas_c)       # disjoint ids: exact set
    return db.transpose(0, 1, 3, 4, 2, 5).reshape(s, l_, geo.k_max, j * bo)


def compact_kept(cfg) -> int:
    """Static kept-block count per out tile (trace-safe, from the spec)."""
    spec = cfg.spec(cfg.layer_fanins[0])
    kb, _ = spec.unit_counts(cfg.layer_fanins[0], cfg.n_hidden)
    return (kb // spec.m) * spec.n


def fwd_current(pre, w_l, delta_l):
    """Forward synaptic current for one layer: ``pre @ w`` (+ slot deltas).

    Dispatch is on the weight rep's keys: the dense rep (``{"w",
    "mask_f"}``) is a plain matmul, the compact rep (``"wc"``) goes
    through ``nm_spmm``, and compact per-slot deltas (rank 5:
    ``[S, J, T, bk, bo]``) contract through ``nm_spmm_deltas`` on the same
    kept-block ids — no dense ``[K, N]`` tensor exists anywhere on the
    compact path. The base current runs under the named scope ``si_base``,
    the per-slot delta current under ``si_delta``.
    """
    compact = "wc" in w_l
    with jax.named_scope("si_base"):
        if compact:
            cur = nm_ref.nm_spmm(pre, w_l["wc"], w_l["idx"])
        else:
            cur = pre @ w_l["w"]
    if delta_l is None:
        return cur
    with jax.named_scope("si_delta"):
        if compact and delta_l.ndim == 5:
            return cur + nm_ref.nm_spmm_deltas(pre, delta_l, w_l["idx"])
        return cur + jnp.einsum("sk,skn->sn", pre, delta_l)


def train_wu(w_l, pre_trace, mod, scale):
    """Gated three-factor WU into the dense base weights (training path);
    the sparsity pattern is the rep's own dense float mask ``mask_f``."""
    dw = scale * (pre_trace.T @ mod)
    return {**w_l, "w": w_l["w"] + dw * w_l["mask_f"]}


# ---------------------------------------------------------------------------
# THE per-timestep layer body (exists exactly once)
# ---------------------------------------------------------------------------

class LayerSlice(NamedTuple):
    """Per-layer xs of the layer scan (leading ``[L]`` axis before slicing).

    There is no dense-mask field: the sparsity pattern lives inside the
    weight rep ``w`` (kept block ids for compact, ``mask_f`` for dense), so
    a compact serving trace never holds a dense mask at all.
    """
    w: Any                                # weight rep: {w, mask_f} | {wc, idx}
    readout: jax.Array                    # [N, n_out] bypass readout
    st: LayerState                        # leaves [R, N]
    ss_mean: jax.Array                    # [] (train) or [S] (serve)
    gate_opened: Optional[jax.Array]      # [] train telemetry; None serving
    gate_offered: Optional[jax.Array]
    delta: Optional[jax.Array]            # serving: [S, J, T, bk, bo] compact
    #   or [S, Kmax, N] dense; None in training
    fanin: jax.Array                      # [] f32 — true fan-in (pre padding)
    density: jax.Array                    # [] f32 — spec density


class LayerCarry(NamedTuple):
    """Flows down the layer stack within one timestep."""
    pre_spikes: jax.Array                 # [R, Kmax]
    pre_trace: jax.Array                  # [R, Kmax]
    logits: jax.Array                     # [R, n_out] bypass accumulator
    sop_fwd: jax.Array                    # [R]
    sop_wu: jax.Array                     # [R]
    sop_wu_off: jax.Array                 # [R]
    loss: jax.Array                       # [R]


class LayerOut(NamedTuple):
    st: LayerState
    w: Any
    delta: Optional[jax.Array]
    ss_mean: jax.Array
    gate_opened: Optional[jax.Array]
    gate_offered: Optional[jax.Array]
    open_: jax.Array                      # gate decision ([] or [S])
    pre_mag: Optional[jax.Array]          # [S, Kmax] |pre trace|, valid-masked
    #   (serving only; the DSST pre factor the topology service accumulates)
    post_mag: Optional[jax.Array]         # [S, N] |OSSL modulator|, valid-masked


def _layer_timestep(cfg, geo: Geometry, learn: bool, serving: bool,
                    factors: bool, t_pc: int, t_wu: int, t_row, valid,
                    carry: LayerCarry, xs: LayerSlice
                    ) -> Tuple[LayerCarry, LayerOut]:
    """SI + gated WU for ONE layer at ONE timestep — training and serving.

    Training is the ``delta=None`` / ``valid=None`` special case: the gate
    decision is shared across the batch (IA/SS reduced over rows), the
    update lands in the base weights with the batch-mean scale ``lr/R``, and
    ``t_row`` is the sample-global timestep broadcast to every row. Serving
    keeps every quantity per-slot and masks invalid slots to exact no-ops.

    ``factors`` (serving only) selects whether the per-slot DSST activity
    magnitudes (``pre_mag``/``post_mag``) are emitted at all. A non-evolving
    fleet passes False and the O(S·(K+N))-per-timestep factor arithmetic
    never enters the trace — it is compiled out, not just skipped.

    Each stage runs under a ``jax.named_scope`` (``si_base``/``si_delta``
    in :func:`fwd_current`, ``lif``, ``ossl``, ``gate``, ``wu_delta`` or
    ``wu_base``, ``telemetry``, ``readout``), so the compiled ops carry
    the model's names in their metadata and a device profile can be read
    per stage.
    """
    g = cfg.gating
    st, pre, pre_tr = xs.st, carry.pre_spikes, carry.pre_trace
    col = (lambda c: c[:, None]) if serving else (lambda c: c)

    current = fwd_current(pre, xs.w, xs.delta)
    with jax.named_scope("lif"):
        v, tr, s = lif_step(st.v, st.tr, current, alpha=cfg.alpha,
                            beta=cfg.beta, theta=cfg.theta)
        tr_pc = jnp.where(col(t_row == t_pc), tr, st.tr_pc)

    # ---- OSSL three-factor WU, gated, concurrent with SI ----
    with jax.named_scope("ossl"):
        mod = ossl_modulator(tr, tr_pc, st.tr_cc, v, cfg)
    with jax.named_scope("gate"):
        if serving:
            ia = pre.mean(-1) if geo.uniform else pre.sum(-1) / xs.fanin
            ss = _cos(tr, st.tr_cc)
        else:
            ia = pre.mean() if geo.uniform \
                else pre.sum() / (pre.shape[0] * xs.fanin)
            ss = _cos(tr, st.tr_cc).mean()
        open_, new_mean = gating_lib.gate_decide(xs.ss_mean, ia, ss, g)
        if serving:
            open_ = open_ & valid
            new_mean = jnp.where(valid, new_mean, xs.ss_mean)
        wu_on = open_ & (t_row >= t_wu) & jnp.asarray(learn)

    if serving:
        with jax.named_scope("wu_delta"):
            if xs.delta.ndim == 5:
                # compact per-slot WU: the outer product lands only in kept
                # blocks — sparse in compute AND storage (the paper's
                # activity-dependent sparse WU)
                spec = cfg.spec(geo.fanins[0])
                scale = jnp.where(wu_on, cfg.lr, 0.0)
                delta_new = xs.delta + wu_ref.wu_outer_slots(
                    pre_tr, mod, xs.w["idx"], scale, spec.block,
                    spec.out_tile)
            else:
                scale = jnp.where(wu_on, cfg.lr, 0.0)[:, None, None]
                dw = scale * pre_tr[:, :, None] * mod[:, None, :]
                delta_new = xs.delta + dw * xs.w["mask_f"][None]
        w_new, opened_new, offered_new = xs.w, None, None
        if factors:
            # DSST factors for the live topology service: per-slot activity
            # magnitudes, zero on invalid timesteps (slot axis survives — the
            # slot-separability contract extends to topology telemetry)
            valf = valid.astype(tr.dtype)[:, None]
            pre_mag = jnp.abs(pre_tr) * valf
            post_mag = jnp.abs(mod) * valf
        else:
            pre_mag = post_mag = None   # frozen fleet: factors compiled out
    else:
        with jax.named_scope("wu_base"):
            scale = jnp.where(wu_on, cfg.lr / pre.shape[0], 0.0)
            w_new = train_wu(xs.w, pre_tr, mod, scale)
        delta_new = None
        opened_new = xs.gate_opened + open_.astype(jnp.float32)
        offered_new = xs.gate_offered + 1.0
        pre_mag = post_mag = None   # training accumulates its own factors

    # ---- telemetry (energy model inputs), per row ----
    with jax.named_scope("telemetry"):
        late = (t_row >= t_wu) & valid if serving else (t_row >= t_wu)
        offered = xs.fanin * cfg.n_hidden * xs.density
        sop_fwd = carry.sop_fwd + pre.sum(-1) * cfg.n_hidden * xs.density
        sop_wu_off = carry.sop_wu_off + offered * late
        sop_wu = carry.sop_wu + offered * wu_on
        loss = carry.loss + \
            (-_cos(tr, tr_pc) + cfg.cc_weight * _cos(tr, st.tr_cc)) * late

    # invalid slots keep their exact previous state
    if serving:
        vv = valid[:, None]
        v = jnp.where(vv, v, st.v)
        tr = jnp.where(vv, tr, st.tr)
        tr_pc = jnp.where(vv, tr_pc, st.tr_pc)
        s = s * valid.astype(s.dtype)[:, None]

    with jax.named_scope("readout"):
        logits = carry.logits + tr @ xs.readout
    new_carry = LayerCarry(
        pre_spikes=_pad_cols(s, geo.k_max),
        pre_trace=_pad_cols(tr, geo.k_max),
        logits=logits, sop_fwd=sop_fwd, sop_wu=sop_wu,
        sop_wu_off=sop_wu_off, loss=loss)
    out = LayerOut(st=LayerState(v, tr, tr_pc, st.tr_cc), w=w_new,
                   delta=delta_new, ss_mean=new_mean,
                   gate_opened=opened_new, gate_offered=offered_new,
                   open_=open_, pre_mag=pre_mag, post_mag=post_mag)
    return new_carry, out


def _layer_arrays(cfg):
    geo = geometry(cfg)
    fan = jnp.asarray([float(f) for f in geo.fanins], jnp.float32)
    dens = jnp.asarray([cfg.spec(f).density for f in geo.fanins], jnp.float32)
    return fan, dens


def _windows(cfg) -> Tuple[int, int]:
    return (int(cfg.t_steps * cfg.pc_snapshot_frac),
            int(cfg.t_steps * cfg.wu_start_frac))


# ---------------------------------------------------------------------------
# time scans: training (aligned sample) and serving (chunked streams)
# ---------------------------------------------------------------------------

def scan_sample(wrep, readout, layers: LayerState, x_tr, gate,
                events, cfg, learn: bool):
    """T aligned timesteps over the layer stack (training datapath).

    Returns (wrep', layers', x_tr', gate', outs) with per-timestep outs.
    """
    geo = geometry(cfg)
    t_pc, t_wu = _windows(cfg)
    fan, dens = _layer_arrays(cfg)
    body = partial(_layer_timestep, cfg, geo, learn, False, False, t_pc,
                   t_wu)

    def ts(carry, inp):
        t, x = inp["t"], inp["x"]
        layers, x_tr, gate, wrep = carry
        x_tr = cfg.beta * x_tr + x
        lc0 = LayerCarry(
            pre_spikes=_pad_cols(x, geo.k_max),
            pre_trace=_pad_cols(x_tr, geo.k_max),
            logits=jnp.zeros((x.shape[0], readout.shape[-1])),
            sop_fwd=jnp.zeros(x.shape[0]), sop_wu=jnp.zeros(x.shape[0]),
            sop_wu_off=jnp.zeros(x.shape[0]), loss=jnp.zeros(x.shape[0]))
        xs = LayerSlice(w=wrep, readout=readout, st=layers,
                        ss_mean=gate.ss_mean, gate_opened=gate.opened,
                        gate_offered=gate.offered, delta=None,
                        fanin=fan, density=dens)
        lc, ys = jax.lax.scan(partial(body, t, None), lc0, xs)
        new_gate = gating_lib.GatingState(
            ss_mean=ys.ss_mean, opened=ys.gate_opened,
            offered=ys.gate_offered)
        out = dict(logits=lc.logits, sop_fwd=lc.sop_fwd.sum(),
                   sop_wu=lc.sop_wu.sum(), sop_wu_off=lc.sop_wu_off.sum(),
                   gate=ys.open_.astype(jnp.float32).sum() / cfg.n_layers,
                   loss=lc.loss.mean() / cfg.n_layers)
        return (ys.st, x_tr, new_gate, ys.w), out

    T = events.shape[0]
    carry0 = (layers, x_tr, gate, wrep)
    (layers, x_tr, gate, wrep), outs = jax.lax.scan(
        ts, carry0, {"t": jnp.arange(T), "x": events})
    return wrep, layers, x_tr, gate, outs


def scan_chunk(wrep, readout, deltas, layers: LayerState, x_tr,
               ss_mean, t_win, samp, events, valid, cfg, learn: bool,
               want_factors: bool = True):
    """Up to C timesteps of S independent streams (serving datapath).

    Engine layout: layer axis leading on ``layers``/``deltas``/``ss_mean``
    (``[L, S, ...]``); the public slot-leading layout is transposed at the
    ``run_chunk`` boundary. Returns (deltas', state pieces, outs).

    A compact ``wrep`` (``{"wc", "idx"}``) with compact deltas
    (``[L, S, J, T, bk, bo]``) is the serving default: the trace then holds
    no dense mask and no dense ``[·, K, N]`` delta leaf at all.

    With ``want_factors`` (static bool) the carry also accumulates per-slot
    DSST activity factors (``acc_pre [L, S, Kmax]``, ``acc_post [L, S, N]``)
    over the chunk — the raw material the serving topology service turns
    into live prune/regrow epochs. ``want_factors=False`` removes the two
    accumulators from the scan carry entirely (no factor leaf appears in
    the jaxpr — pinned by ``tests/test_serving_pipeline.py``): a fleet with
    a frozen topology pays zero in-scan cost for machinery it never reads,
    mirroring how the chip gates its learning datapath off when inactive.
    """
    geo = geometry(cfg)
    t_pc, t_wu = _windows(cfg)
    fan, dens = _layer_arrays(cfg)
    body = partial(_layer_timestep, cfg, geo, learn, True, want_factors,
                   t_pc, t_wu)

    def ts(carry, inp):
        layers, x_tr, ss_mean, t_w, samp, dls, *acc = carry
        x, val = inp["x"], inp["v"]
        valf = val.astype(x.dtype)[:, None]
        x = x * valf
        x_tr = jnp.where(val[:, None], cfg.beta * x_tr + x, x_tr)
        S = x.shape[0]
        lc0 = LayerCarry(
            pre_spikes=_pad_cols(x, geo.k_max),
            pre_trace=_pad_cols(x_tr, geo.k_max),
            logits=jnp.zeros((S, readout.shape[-1])),
            sop_fwd=jnp.zeros(S), sop_wu=jnp.zeros(S),
            sop_wu_off=jnp.zeros(S), loss=jnp.zeros(S))
        xs = LayerSlice(w=wrep, readout=readout, st=layers,
                        ss_mean=ss_mean, gate_opened=None, gate_offered=None,
                        delta=dls, fanin=fan, density=dens)
        lc, ys = jax.lax.scan(partial(body, t_w, val), lc0, xs)

        # ---- per-slot window roll: final trace becomes the CC negative ----
        with jax.named_scope("window_roll"):
            at_end = val & (t_w == cfg.t_steps - 1)
            endf = at_end[:, None]
            rolled = LayerState(
                v=jnp.where(endf, 0.0, ys.st.v),
                tr=jnp.where(endf, 0.0, ys.st.tr),
                tr_pc=jnp.where(endf, 0.0, ys.st.tr_pc),
                tr_cc=jnp.where(endf, ys.st.tr, ys.st.tr_cc))
            x_tr = jnp.where(endf, 0.0, x_tr)
            samp = samp + at_end.astype(jnp.int32)
            t_w = jnp.where(val, (t_w + 1) % cfg.t_steps, t_w)

        out = dict(logits=lc.logits, at_end=at_end, sop_fwd=lc.sop_fwd,
                   sop_wu=lc.sop_wu, sop_wu_off=lc.sop_wu_off,
                   opened=ys.open_.T.astype(jnp.float32),
                   offered=jnp.tile(val.astype(jnp.float32)[:, None],
                                    (1, cfg.n_layers)),
                   loss=lc.loss / cfg.n_layers,
                   steps=val.astype(jnp.float32))
        new_acc = (acc[0] + ys.pre_mag, acc[1] + ys.post_mag) if acc else ()
        return (rolled, x_tr, ys.ss_mean, t_w, samp, ys.delta,
                *new_acc), out

    S = events.shape[1]
    acc0 = ()
    if want_factors:
        acc0 = (jnp.zeros((cfg.n_layers, S, geo.k_max)),
                jnp.zeros((cfg.n_layers, S, cfg.n_hidden)))
    carry0 = (layers, x_tr, ss_mean, t_win, samp, deltas, *acc0)
    carry, outs = jax.lax.scan(ts, carry0, {"x": events, "v": valid})
    _assert_slot_separable(carry, outs, events.shape[0], events.shape[1], cfg,
                           want_factors)
    return carry, outs


def ordered_slot_sum(x: jax.Array) -> jax.Array:
    """Reduce the leading slot axis with a shape-fixed adjacent-pair tree.

    ``x``: any ``[S, ...]`` array; returns ``x.sum(0)`` computed level by
    level as ``x[0::2] + x[1::2]`` (an odd last row rides along to the next
    level). Every level is a plain elementwise add, so the floating-point
    association order is a function of ``S`` alone — NOT of the device
    count, sharding, or XLA's reduction strategy — and the tree sums
    contiguous blocks first: when a slot mesh gives each of ``D`` devices
    ``S/D`` contiguous slots and ``S/D`` is a power of two, each device's
    shard is an exact subtree. The serving chunk fn therefore reduces each
    shard on its own device and combines the ``[D, ...]`` partials with the
    tree's top levels (``ordered_slot_sum`` again), bit-identical to the
    1-device fleet, and no ``[S/D, ...]`` array crosses chips.
    """
    while x.shape[0] > 1:
        even = x.shape[0] // 2 * 2
        paired = x[0:even:2] + x[1:even:2]
        x = paired if even == x.shape[0] else \
            jnp.concatenate([paired, x[even:]], axis=0)
    return x[0]


def _assert_slot_separable(carry, outs, C: int, S: int, cfg,
                           want_factors: bool) -> None:
    """The chunk step's zero-collective contract: every per-stream quantity
    keeps its slot axis through the scan. A reduction over slots — which
    would silently break the slot-axis ``shard_map`` in serving/adapt.py —
    shows up at trace time as a dropped ``S`` dimension here. Thin wrapper
    over the shared analyzer (repro.analysis.jaxpr_contracts), imported
    lazily so the engine keeps no static analysis dependency."""
    from repro.analysis.jaxpr_contracts import \
        assert_chunk_carry_slot_separable
    assert_chunk_carry_slot_separable(carry, outs, C=C, S=S,
                                      n_layers=cfg.n_layers,
                                      want_factors=want_factors)
