"""ElfCore's spiking network — the paper-faithful reproduction (floor).

Implements the chip of Fig. 2 as a pure-JAX simulator:

* (512)-512-512-16 topology, hidden LIF layers (each = 4 N:M groups /
  "PEs"), **bypass connections** from every hidden layer to the output, so
  depth can be varied for the Fig. 7 depth study.
* **Neuron SRAM with three traces per neuron**: the current TS's trace (used
  by WU), a snapshot from an earlier TS of the same sample (used by
  predictive coding), and the trace at the final TS of the *previous* sample
  (used by contrastive coding).
* **OSSL**: per-layer three-factor updates with concurrent PC + CC — no
  labels, no backprop, all hidden layers update in parallel with the forward
  pass (WU-locking removed; §III's 67–72 % TS-length cut).
* **SL output layer**: delta-rule readout (the only place labels enter).
* **DSST**: connectivity prune/regrow every ``period`` samples from the
  factorized |pre|·|post| statistics written back during WU.
* **Activity-dependent WU gating**: IA vs a global threshold, SS vs an
  adaptive per-layer threshold (core/gating.py).
* SOP / WU / memory-access counters feed the energy model (core/energy.py).

The per-timestep datapath lives in **core/engine.py** — one layer-stacked
``layer_timestep`` scanned over a ``[L, ...]`` layer axis, shared by the
training path (:func:`run_sample`) and the serving path (:func:`run_chunk`):
one jnp implementation of each op, no backend option. This module owns the
network-level layouts and the per-sample bookkeeping around that engine:
parameter/state initialisation, the SL readout delta rule, DSST events, and
the CC-slot roll.

Parameter layout (stacked; one leaf per role, leading layer axis)::

    params = {
      "hidden": {"w":    f32[L, Kmax, n_hidden],   # masked base weights
                 "mask": bool[L, KBmax, J]},       # N:M unit masks
      "readout": f32[L, n_hidden, n_out],          # bypass readouts
    }

``engine.hidden_slice(params, l, cfg)`` gives the per-layer view;
``engine.stack_params`` migrates PR-1 (list-of-dicts) checkpoints.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from . import engine
from . import gating as gating_lib
from . import topology as topology_lib
from .dsst import DSSTAccumulator, DSSTConfig
from .engine import (LayerState, _cos, lif_step, ossl_modulator,  # noqa: F401
                     surrogate_grad)
from .sparsity import NMSpec, apply_mask, paper_spec_4groups, random_unit_mask


# ---------------------------------------------------------------------------
# config
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SNNConfig:
    n_in: int = 512
    n_hidden: int = 512
    n_layers: int = 2          # hidden layers (bypass keeps output wired)
    n_out: int = 16
    t_steps: int = 50          # timesteps per sample
    # neuron dynamics
    alpha: float = 0.9         # membrane decay
    beta: float = 0.85         # trace decay
    theta: float = 1.0         # firing threshold (soft reset)
    surrogate_width: float = 1.0
    # learning
    lr: float = 0.02           # hidden OSSL rate
    lr_out: float = 0.1        # SL readout rate
    cc_weight: float = 1.0     # contrastive term weight
    pc_snapshot_frac: float = 0.5   # TS (fraction of T) at which tr_pc is latched
    wu_start_frac: float = 0.6      # WU runs on late TSs (traces must be formed)
    # sparsity
    sparsity: float = 0.8
    dense: bool = False        # dense baseline (Fig. 5/7 comparisons)
    dsst: DSSTConfig = dataclasses.field(default_factory=lambda: DSSTConfig(period=40, prune_frac=0.25))
    dsst_enabled: bool = True  # False = static sparse training baseline
    # gating
    gating: gating_lib.GatingConfig = dataclasses.field(default_factory=gating_lib.GatingConfig)

    def spec(self, fan_in: int) -> NMSpec:
        if self.dense:
            return NMSpec(n=4, m=4)  # degenerate: keep everything, 4 "groups"
        return paper_spec_4groups(fan_in, self.sparsity)

    @property
    def layer_fanins(self):
        return [self.n_in] + [self.n_hidden] * (self.n_layers - 1)


# ---------------------------------------------------------------------------
# parameters and state
# ---------------------------------------------------------------------------

def init_params(rng: jax.Array, cfg: SNNConfig) -> Dict[str, Any]:
    """Random weights at target sparsity from step 0 (sparse-to-sparse).

    One key per (layer weight, layer mask, layer readout) — readout layers
    no longer share initial weights at any depth.
    """
    geo = engine.geometry(cfg)
    keys = jax.random.split(rng, 3 * cfg.n_layers)
    ws, masks = [], []
    for l, fan_in in enumerate(cfg.layer_fanins):
        spec = cfg.spec(fan_in)
        w = jax.random.normal(keys[2 * l], (fan_in, cfg.n_hidden)) * (1.5 / jnp.sqrt(fan_in * spec.density))
        mask = random_unit_mask(keys[2 * l + 1], spec, fan_in, cfg.n_hidden)
        ws.append(engine._pad_rows(apply_mask(w, mask, spec), geo.k_max))
        masks.append(engine._pad_rows(mask, geo.k_max))
    readout = jnp.stack([
        jax.random.normal(keys[2 * cfg.n_layers + l],
                          (cfg.n_hidden, cfg.n_out)) * 0.05
        for l in range(cfg.n_layers)])
    return {"hidden": {"w": jnp.stack(ws), "mask": jnp.stack(masks)},
            "readout": readout}


class NetState(NamedTuple):
    layers: LayerState         # leaves [L, B, N]
    x_tr: jax.Array            # [B, K] input (pre-synaptic) trace
    gate: gating_lib.GatingState
    acc: Tuple[DSSTAccumulator, ...]
    sample_idx: jax.Array      # scalar int32


def init_state(cfg: SNNConfig, batch: int) -> NetState:
    layers = LayerState(*(jnp.zeros((cfg.n_layers, batch, cfg.n_hidden))
                          for _ in range(4)))
    accs = []
    for fan_in in cfg.layer_fanins:
        spec = cfg.spec(fan_in)
        kb, j = spec.unit_counts(fan_in, cfg.n_hidden)
        accs.append(DSSTAccumulator.init(kb, j))
    return NetState(
        layers=layers,
        x_tr=jnp.zeros((batch, cfg.n_in)),
        gate=gating_lib.init_state(cfg.n_layers, cfg.gating),
        acc=tuple(accs),
        sample_idx=jnp.zeros((), jnp.int32),
    )


# ---------------------------------------------------------------------------
# one sample (T timesteps), SI + WU concurrent, one lax.scan over the engine
# ---------------------------------------------------------------------------

class SampleMetrics(NamedTuple):
    logits: jax.Array          # [B, n_out] (final-TS readout)
    sop_forward: jax.Array     # synaptic ops on the forward path
    sop_wu: jax.Array          # weight-update MACs actually performed
    sop_wu_offered: jax.Array  # WU MACs before gating (for skip-rate)
    gate_open_frac: jax.Array  # fraction of (layer, TS) gates that fired
    local_loss: jax.Array     # mean OSSL loss over late TSs (learning signal)


def run_sample(
    params: Dict[str, Any],
    state: NetState,
    events: jax.Array,          # [T, B, n_in] binary spikes
    label: Optional[jax.Array],  # [B] int or None (inference)
    cfg: SNNConfig,
    *,
    learn: bool = True,
) -> Tuple[Dict[str, Any], NetState, SampleMetrics]:
    T, B, _ = events.shape
    t_wu = int(cfg.t_steps * cfg.wu_start_frac)
    masks = params["hidden"]["mask"]
    wrep = {"w": params["hidden"]["w"],
            "mask_f": engine.dense_masks(masks, cfg)}

    wrep, layers, x_tr, gate_st, outs = engine.scan_sample(
        wrep, params["readout"], state.layers, state.x_tr,
        state.gate, events, cfg, learn)
    w_stacked = wrep["w"]

    logits = outs["logits"][-1]

    # ---- SL delta rule on the output layer (labels only used here) ----
    pr = params["readout"]
    if label is not None and learn:
        err = jax.nn.one_hot(label, cfg.n_out) - jax.nn.softmax(logits)   # [B, n_out]
        pr = pr + (cfg.lr_out / B) * jnp.einsum("lbn,bo->lno", layers.tr, err)

    # ---- DSST statistics write-back + (maybe) stacked connectivity epoch ----
    # Accumulator updates stay per layer (unit counts differ when fan-ins
    # do); the prune/regrow epoch itself is ONE call into
    # ``topology.topology_epoch`` — the identical code path the serving
    # topology service runs between grid steps, honoring the decay schedule
    # through the traced sample index (lax.switch over static k levels).
    # Both run under the named scope ``dsst``.
    new_params = {"hidden": {"w": w_stacked, "mask": masks}, "readout": pr}
    with jax.named_scope("dsst"):
        pre_traces = [x_tr] + [layers.tr[l] for l in range(cfg.n_layers - 1)]
        new_acc = []
        for l, fan_in in enumerate(cfg.layer_fanins):
            spec = cfg.spec(fan_in)
            kb, jj = spec.unit_counts(fan_in, cfg.n_hidden)
            pre_mag = jnp.abs(pre_traces[l]).mean(0)                  # [K]
            mod = ossl_modulator(layers.tr[l], layers.tr_pc[l],
                                 layers.tr_cc[l], layers.v[l], cfg)
            post_mag = jnp.abs(mod).mean(0)                           # [N]
            pre_units = pre_mag.reshape(kb, -1).sum(-1)
            new_acc.append(state.acc[l].update(pre_units, post_mag))
        new_acc = tuple(new_acc)
        if cfg.dsst_enabled and not cfg.dense and learn:
            pre_stacked = jnp.stack([engine._pad_rows(a.pre, masks.shape[1])
                                     for a in new_acc])           # [L, KBmax]
            post_stacked = jnp.stack([a.post for a in new_acc])       # [L, J]

            def do(args):
                p, accs = args
                p2, _ = topology_lib.topology_epoch(
                    p, pre_stacked, post_stacked, cfg, step=state.sample_idx)
                fresh = tuple(DSSTAccumulator.init(a.pre.shape[0],
                                                   a.post.shape[0])
                              for a in accs)
                return p2, fresh

            def skip(args):
                return args

            new_params, new_acc = jax.lax.cond(
                cfg.dsst.is_update_step(state.sample_idx), do, skip,
                (new_params, new_acc))

    # ---- roll the CC slot: final trace of this sample becomes the negative ----
    final_layers = LayerState(
        v=jnp.zeros_like(layers.v), tr=jnp.zeros_like(layers.tr),
        tr_pc=jnp.zeros_like(layers.tr_pc), tr_cc=layers.tr)
    new_state = NetState(layers=final_layers, x_tr=jnp.zeros_like(x_tr),
                         gate=gate_st, acc=new_acc,
                         sample_idx=state.sample_idx + 1)
    metrics = SampleMetrics(
        logits=logits,
        sop_forward=outs["sop_fwd"].sum(),
        sop_wu=outs["sop_wu"].sum(),
        sop_wu_offered=outs["sop_wu_off"].sum(),
        gate_open_frac=outs["gate"].mean(),
        local_loss=outs["loss"].sum() / max(1, T - t_wu),
    )
    return new_params, new_state, metrics


# ---------------------------------------------------------------------------
# chunked streaming step (serving path)
# ---------------------------------------------------------------------------
#
# ``run_sample`` integrates one aligned batch over a full sample and shares
# gating / WU statistics across the batch. Serving needs the opposite: many
# *independent* event streams multiplexed onto the slots of one jitted step,
# each resuming from carried state at an arbitrary position inside its own
# T-step window. ``run_chunk`` therefore drives the same engine in its
# per-slot mode:
#
# * gating IA/SS and the adaptive SS threshold are per-stream (``ss_mean``
#   is [S, L], not [L]);
# * weight updates go into per-stream deltas over a frozen shared base
#   (``w_eff[s] = w_base + delta[s]``), so one stream's adaptation never
#   leaks into another slot;
# * per-slot window counters (``t_in_window``) decide PC-snapshot latching,
#   the WU window, and the CC roll at window end — streams need not be
#   aligned;
# * a ``valid [C, S]`` mask makes ragged chunks and idle slots exact no-ops
#   (state bit-identical, zero telemetry).
#
# This separability is what makes slot multiplexing sound; asserted by the
# interleaved-vs-solo equivalence test in tests/test_serving_streams.py, and
# the engine-sharing by the train↔serve trajectory-equivalence test in
# tests/test_train_serve_equivalence.py.


class StreamState(NamedTuple):
    layers: LayerState               # leaves [S, L, N] (slot axis leads —
    #   lane surgery in serving/session.py slices the leading axis of every
    #   leaf; the engine transposes to its [L, S, N] layout at the
    #   run_chunk boundary)
    x_tr: jax.Array                  # [S, n_in]
    ss_mean: jax.Array               # [S, L] per-stream adaptive SS threshold
    t_in_window: jax.Array           # [S] int32, position inside the T-window
    sample_idx: jax.Array            # [S] int32, windows completed


def init_stream_state(cfg: SNNConfig, n_slots: int) -> StreamState:
    layers = LayerState(*(jnp.zeros((n_slots, cfg.n_layers, cfg.n_hidden))
                          for _ in range(4)))
    return StreamState(
        layers=layers,
        x_tr=jnp.zeros((n_slots, cfg.n_in)),
        ss_mean=jnp.full((n_slots, cfg.n_layers), cfg.gating.ss_init,
                         dtype=jnp.float32),   # explicit dtype: weak-typed
        # init would force one retrace when the first chunk strong-types it
        t_in_window=jnp.zeros((n_slots,), jnp.int32),
        sample_idx=jnp.zeros((n_slots,), jnp.int32),
    )


def init_stream_deltas(cfg: SNNConfig, n_slots: int,
                       compact: Optional[bool] = None) -> jax.Array:
    """Per-stream weight deltas over the frozen shared base (slot axis
    leads for lane surgery).

    Default (``compact=None``) is layout auto-selection: the compact N:M
    tensor ``[S, L, J, T, bk, bo]`` — storage scales with density, not
    ``K·N`` — whenever the layer geometry is uniform, else the dense
    ``[S, L, Kmax, n_hidden]`` fallback. Pass ``compact=False`` to force
    the dense baseline layout (the A/B reference path).
    """
    geo = engine.geometry(cfg)
    if compact is None:
        compact = geo.uniform
    if compact:
        if not geo.uniform:
            raise ValueError(
                "compact stream deltas require uniform layer fan-in "
                f"(got {geo.fanins}); pass compact=False")
        spec = cfg.spec(geo.fanins[0])
        jj = cfg.n_hidden // spec.out_tile
        return jnp.zeros((n_slots, cfg.n_layers, jj, engine.compact_kept(cfg),
                          spec.block, spec.out_tile))
    return jnp.zeros((n_slots, cfg.n_layers, geo.k_max, cfg.n_hidden))


def serving_params(params: Dict[str, Any], cfg: SNNConfig) -> Dict[str, Any]:
    """Dense training params -> the mask-free serving weight rep.

    ``{"wc" [L,J,T,bk,bo], "idx" [L,J,T], "readout" [L,N,n_out]}`` — what a
    compact-mode :func:`run_chunk` consumes. Built on the host (outside
    jit) at fleet construction and at topology epoch boundaries, so neither
    the dense weights nor the dense mask ever enter the serving jaxpr.
    """
    wrep = engine.compact_weights(params["hidden"]["w"],
                                  params["hidden"]["mask"], cfg)
    return {**wrep, "readout": params["readout"]}


class ChunkMetrics(NamedTuple):
    """Per-chunk serving metrics; every per-stream leaf keeps its slot axis.

    The two DSST factor fields are ``None`` when the chunk ran with
    ``want_factors=False`` (frozen-topology fleets — the accumulators are
    compiled out of the scan, see ``engine.scan_chunk``). Out of
    :func:`run_chunk` they are per-slot ``[S, L, ·]``; the serving layer
    (``serving/adapt.make_chunk_fn``) slot-reduces them on device with the
    order-fixed ``engine.ordered_slot_sum`` before they leave the jit, so
    callers of the jitted chunk fn see ``[L, Kmax]`` / ``[L, N]`` instead.
    """
    logits: jax.Array          # [C, S, n_out] per-timestep readout
    window_end: jax.Array      # [C, S] bool: logits here close a T-window
    sop_forward: jax.Array     # [S]
    sop_wu: jax.Array          # [S]
    sop_wu_offered: jax.Array  # [S]
    gate_opened: jax.Array     # [S, L]
    gate_offered: jax.Array    # [S, L]
    local_loss: jax.Array      # [S] summed OSSL loss over late TSs
    steps: jax.Array           # [S] valid timesteps processed
    pre_mag: Optional[jax.Array]   # [S, L, Kmax] summed |pre trace|
    #   (DSST factor; [L, Kmax] past the serving chunk fn; None when off)
    post_mag: Optional[jax.Array]  # [S, L, N] summed |OSSL modulator|
    #   (DSST factor; [L, N] past the serving chunk fn; None when off)


def _to_engine(tree):
    """Slot-leading public layout -> layer-leading engine layout."""
    return jax.tree_util.tree_map(lambda a: jnp.swapaxes(a, 0, 1), tree)


def run_chunk(
    params: Dict[str, Any],
    deltas: jax.Array,          # compact [S,L,J,T,bk,bo] | dense [S,L,Kmax,N]
    state: StreamState,
    events: jax.Array,          # [C, S, n_in] binary spikes
    valid: jax.Array,           # [C, S] bool — ragged chunks / idle slots
    cfg: SNNConfig,
    *,
    learn: bool = True,
    want_factors: bool = True,
) -> Tuple[jax.Array, StreamState, ChunkMetrics]:
    """Advance S independent streams by up to C timesteps each.

    Args:
      params:  frozen shared base — either the dense training layout
        (stacked ``hidden/{w,mask}`` + readout) or the mask-free serving
        rep from :func:`serving_params` (``{"wc", "idx", "readout"}``).
      deltas:  per-stream adaptation, slot-leading — compact
        ``[S, L, J, T, bk, bo]`` (the hot-path default) or dense
        ``[S, L, Kmax, n_hidden]`` (the A/B baseline); the layout is
        inferred from the rank.
      state:   carried :class:`StreamState` (slot-leading leaves).
      events:  ``[C, S, n_in]`` binary spikes.
      valid:   ``[C, S]`` bool — ragged chunks / idle slots are exact no-ops.
      learn:   gate the per-stream OSSL delta updates on/off.
      want_factors: static; False compiles the DSST ``pre_mag``/``post_mag``
        accumulators out of the chunk scan and returns them as ``None`` —
        the right mode for fleets whose topology never evolves.

    With compact deltas the whole chunk runs on the compact layout: the
    forward current goes through ``nm_spmm``, the per-stream WU scatters
    only into kept blocks, and no dense mask or ``[S, L, K, N]`` leaf
    appears in the jaxpr (asserted by ``tests/test_compact_serving.py``).

    Returns ``(deltas', state', metrics)``: same shapes/dtypes in and out,
    so the caller can jit once and stream forever.
    """
    compact = deltas.ndim == 6
    if "wc" in params:               # mask-free serving rep
        if not compact:
            raise ValueError("the mask-free serving params carry no dense "
                             "mask, so dense [S, L, K, N] deltas cannot be "
                             "applied; use compact deltas "
                             "(init_stream_deltas default)")
        wrep = {"wc": params["wc"], "idx": params["idx"]}
    else:
        masks = params["hidden"]["mask"]
        if compact:
            wrep = engine.compact_weights(params["hidden"]["w"], masks, cfg)
        else:
            wrep = {"w": params["hidden"]["w"],
                    "mask_f": engine.dense_masks(masks, cfg)}

    (layers, x_tr, ss_mean, t_win, samp, dls, *accs), outs = \
        engine.scan_chunk(
            wrep, params["readout"], _to_engine(deltas),
            _to_engine(state.layers), state.x_tr, state.ss_mean.T,
            state.t_in_window, state.sample_idx, events, valid, cfg, learn,
            want_factors)

    new_state = StreamState(layers=_to_engine(layers), x_tr=x_tr,
                            ss_mean=ss_mean.T, t_in_window=t_win,
                            sample_idx=samp)
    metrics = ChunkMetrics(
        logits=outs["logits"],
        window_end=outs["at_end"],
        sop_forward=outs["sop_fwd"].sum(0),
        sop_wu=outs["sop_wu"].sum(0),
        sop_wu_offered=outs["sop_wu_off"].sum(0),
        gate_opened=outs["opened"].sum(0),
        gate_offered=outs["offered"].sum(0),
        local_loss=outs["loss"].sum(0),
        steps=outs["steps"].sum(0),
        pre_mag=_to_engine(accs[0]) if accs else None,
        post_mag=_to_engine(accs[1]) if accs else None,
    )
    # slot-separability contract (backs the slot-axis shard_map in serving):
    # metric reductions run over time only — the S axis survives everywhere
    S = events.shape[1]
    assert metrics.logits.shape[1] == S, metrics.logits.shape
    assert metrics.window_end.shape == events.shape[:2], metrics.window_end.shape
    for leaf in (metrics.sop_forward, metrics.sop_wu, metrics.sop_wu_offered,
                 metrics.local_loss, metrics.steps):
        assert leaf.shape == (S,), leaf.shape
    assert metrics.gate_opened.shape == metrics.gate_offered.shape \
        == (S, cfg.n_layers), metrics.gate_opened.shape
    if want_factors:
        assert metrics.pre_mag.shape[:2] == (S, cfg.n_layers), \
            metrics.pre_mag.shape
        assert metrics.post_mag.shape == (S, cfg.n_layers, cfg.n_hidden), \
            metrics.post_mag.shape
    else:
        assert metrics.pre_mag is None and metrics.post_mag is None
    return _to_engine(dls), new_state, metrics


# jit entry points -----------------------------------------------------------

def make_train_fn(cfg: SNNConfig):
    @jax.jit
    def step(params, state, events, label):
        return run_sample(params, state, events, label, cfg, learn=True)
    return step


def make_eval_fn(cfg: SNNConfig):
    @jax.jit
    def step(params, state, events):
        _, state, m = run_sample(params, state, events, None, cfg, learn=False)
        return state, m
    return step


def accuracy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    return (jnp.argmax(logits, -1) == labels).mean()
