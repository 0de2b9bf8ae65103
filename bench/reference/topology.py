"""Plain reference of the serving fleet's live DSST epoch, and of the DSST
factors one grid step feeds it, for the live cell's correctness check.

Written from the published description (arXiv:2512.21153, ElfCore) and
the configuration file alone, on dense ``[L, K, N]`` weights and masks in
numpy / ``jax.numpy`` float32 (every matmul at the configuration's
precision through :func:`bench.reference.snn.mm`). It imports nothing of
the program under test.

One epoch, between two grid steps:

1. **Hot lanes** — among the lanes that may merge, the ``merge_top`` with
   the largest per-lane delta norm (the sum over layers of each layer's
   L2 norm) above ``merge_min_norm``; ties go to the lower lane.
2. **Fold** — each hot lane's delta is added, times ``merge_weight``, to
   the shared base, and the lane keeps ``1 - merge_weight`` of it.
3. **Prune and regrow** — per layer, per output column, per group of
   ``m`` consecutive inputs: keep the ``n - k`` active connections of
   largest |w|, regrow the ``k`` inactive ones of largest accumulated
   presynaptic factor (ties to the lower input); regrown weights start at
   0 (:func:`bench.reference.snn.prune_regrow`).
4. **Projection** — every lane's delta keeps its value where the
   connection survives and is 0 elsewhere; in the compact layout (a
   lane's kept values per column, in ascending input order) that is the
   old values gathered to the new kept positions.

Departures from the paper, all the program's as well: the chip accumulates
its DSST factors per sample of a training batch, the serving fleet per grid
step over every lane's valid timesteps, decayed by ``accum_decay`` per grid
step; the regrow score is the factored one (|pre| alone orders a group,
since the column factor is constant along it), not a dense gradient; the
fold of hot streams into the shared base is the serving system's own
fleet-learning step, which the paper's single-stream chip does not have.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import snn as ref


# ---------------------------------------------------------------------------
# compact <-> dense, from the mask alone
# ---------------------------------------------------------------------------

def kept_rows(mask: np.ndarray) -> np.ndarray:
    """``[L, N, T]`` kept input rows per output column, ascending."""
    mask = np.asarray(mask, bool)
    t = int(mask[0, :, 0].sum())
    order = np.argsort(~mask, axis=1, kind="stable")[:, :t, :]
    return order.transpose(0, 2, 1)


def densify(compact: np.ndarray, mask: np.ndarray) -> np.ndarray:
    """Compact lanes ``[..., L, N, T(, 1, 1)]`` (the element-granular
    layout: a column's kept values in ascending input order) -> dense
    ``[..., L, K, N]``, zero off the mask."""
    mask = np.asarray(mask, bool)
    L, K, N = mask.shape
    rows = kept_rows(mask)
    c = np.asarray(compact, np.float32)
    lead = c.shape[:c.ndim - (5 if c.shape[-2:] == (1, 1) else 3)]
    c = c.reshape(lead + rows.shape)
    out = np.zeros(lead + (L, K, N), np.float32)
    li = np.arange(L)[:, None, None]
    ni = np.arange(N)[None, :, None]
    out[..., li, rows, ni] = c
    return out


def compact(dense: np.ndarray, mask: np.ndarray) -> np.ndarray:
    """Dense ``[..., L, K, N]`` -> compact ``[..., L, N, T]``."""
    rows = kept_rows(mask)
    L = rows.shape[0]
    li = np.arange(L)[:, None, None]
    ni = np.arange(rows.shape[1])[None, :, None]
    return np.asarray(dense, np.float32)[..., li, rows, ni]


# ---------------------------------------------------------------------------
# one epoch
# ---------------------------------------------------------------------------

def hot_lanes(norms: np.ndarray, eligible: np.ndarray, top: int,
              min_norm: float) -> List[int]:
    """The lanes that merge, largest delta norm first."""
    norms = np.asarray(norms)
    cand = [int(s) for s in np.nonzero(np.asarray(eligible))[0]
            if norms[s] > min_norm]
    return sorted(cand, key=lambda s: (-norms[s], s))[:top]


def fold(w: np.ndarray, mask: np.ndarray, lanes: Sequence[np.ndarray],
         weight: float) -> np.ndarray:
    """The base after adding each hot lane's delta (compact), in order."""
    w = np.asarray(w, np.float32)
    for lane in lanes:
        w = w + np.float32(weight) * densify(lane, mask)
    return w


def prune_regrow(cfg: Dict, w: np.ndarray, mask: np.ndarray,
                 pre: np.ndarray):
    """Every layer's prune and regrow (``pre``: ``[L, K]`` accumulated
    presynaptic factors). Returns ``(w', mask')`` as numpy."""
    out = [ref.prune_regrow(cfg, jnp.asarray(w[l]), jnp.asarray(mask[l]),
                            jnp.asarray(pre[l]))
           for l in range(w.shape[0])]
    return (np.stack([np.asarray(o[0]) for o in out]),
            np.stack([np.asarray(o[1]) for o in out]))


def project(delta: np.ndarray, old_mask: np.ndarray,
            new_mask: np.ndarray) -> np.ndarray:
    """Dense deltas ``[..., L, K, N]`` across a mask change."""
    keep = np.asarray(old_mask, bool) & np.asarray(new_mask, bool)
    return np.where(keep, delta, np.float32(0.0))


# ---------------------------------------------------------------------------
# the DSST factors of one grid step, every lane
# ---------------------------------------------------------------------------

def _factor_timestep(cfg, w, mask, c: ref.StreamCarry, x, valid, lr):
    """One timestep of one stream (as ``snn._stream_timestep``, without the
    readout): returns ``(carry', |pre trace| [L, K], |modulator| [L, N])``,
    both zero on an invalid timestep, which leaves the carry untouched."""
    t_pc, t_wu = ref._windows(cfg)
    T = cfg["t_steps"]
    t = c.t_win
    x_tr = cfg["beta"] * c.x_tr + x
    pre, pre_tr = x, x_tr
    vs, trs, pcs, means, deltas, pm, qm = [], [], [], [], [], [], []
    for l in range(cfg["n_layers"]):
        cur = ref.mm(cfg, pre, w[l] + c.delta[l])
        v = cfg["alpha"] * c.v[l] + cur
        s = (v >= cfg["theta"]).astype(jnp.float32)
        v = v - s * cfg["theta"]
        tr = cfg["beta"] * c.tr[l] + s
        tr_pc = jnp.where(t == t_pc, tr, c.tr_pc[l])
        mod = ref.modulator(cfg, tr, tr_pc, c.tr_cc[l], v)
        open_, mean = ref.gate(cfg, c.ss_mean[l], pre.mean(),
                               ref._cos(tr, c.tr_cc[l]))
        on = open_ & (t >= t_wu)
        deltas.append(c.delta[l] + jnp.where(on, lr, 0.0) * (
            pre_tr[:, None] * mod[None, :]) * mask[l])
        pm.append(jnp.abs(pre_tr))
        qm.append(jnp.abs(mod))
        vs.append(v), trs.append(tr), pcs.append(tr_pc), means.append(mean)
        pre, pre_tr = s, tr
    v, tr, tr_pc = jnp.stack(vs), jnp.stack(trs), jnp.stack(pcs)
    end = t == T - 1
    z = jnp.zeros_like(v)
    new = ref.StreamCarry(
        v=jnp.where(end, z, v), tr=jnp.where(end, z, tr),
        tr_pc=jnp.where(end, z, tr_pc), tr_cc=jnp.where(end, tr, c.tr_cc),
        x_tr=jnp.where(end, jnp.zeros_like(x_tr), x_tr),
        ss_mean=jnp.stack(means), t_win=(t + 1) % T,
        delta=jnp.stack(deltas))
    new = jax.tree_util.tree_map(lambda a, b: jnp.where(valid, a, b), new, c)
    vf = valid.astype(jnp.float32)
    return new, jnp.stack(pm) * vf, jnp.stack(qm) * vf


def _factor_block_fn(cfg):
    lr = cfg["lr"] * cfg["adapt"]["lr_scale"]

    def block(w, mask, carry, events, valid):
        def one(c, ev, va):
            def body(c, inp):
                c, p, q = _factor_timestep(cfg, w, mask, c, inp[0], inp[1],
                                           lr)
                return c, (p, q)
            _, (p, q) = jax.lax.scan(body, c, (ev, va))
            return p.sum(0), q.sum(0)
        return jax.vmap(one)(carry, events, valid)

    return jax.jit(block)


def factor_sums(cfg: Dict, w: np.ndarray, mask: np.ndarray,
                lanes: List[ref.StreamCarry], events: np.ndarray,
                valid: np.ndarray, block: int = 128):
    """Every lane's DSST factors over one chunk, summed over the lanes in
    float64, ``block`` lanes at a time. ``lanes(b0, b1)`` gives the
    carries of lanes ``b0:b1`` (deltas dense); ``events [S, C, K]``,
    ``valid [S, C]``. Returns ``(pre [L, K], post [L, N])``."""
    fn = _factor_block_fn(cfg)
    wj = jnp.asarray(w, jnp.float32)
    mj = jnp.asarray(mask).astype(jnp.float32)
    S = events.shape[0]
    pre = post = 0.0
    for b0 in range(0, S, block):
        b1 = min(S, b0 + block)
        p, q = fn(wj, mj, lanes(b0, b1), jnp.asarray(events[b0:b1]),
                  jnp.asarray(valid[b0:b1]))
        pre = pre + np.asarray(p, np.float64).sum(0)
        post = post + np.asarray(q, np.float64).sum(0)
    return pre, post
