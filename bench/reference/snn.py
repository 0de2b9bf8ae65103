"""Plain float32 reference of the ElfCore network, for the benchmark's
correctness check.

Written from the published description (arXiv:2512.21153, ElfCore) and the
configuration file alone: dense masked weights, one neuron layer after the
other, no compact layouts, no kernels, no slot grid. It imports nothing of
the program under test, and it makes nothing that the program made: the
weights it starts from are the benchmark's own (``bench/weights.py``).

Two entry points:

* :func:`serve_streams` — independent streams, each timestep unbatched
  and vmapped over the streams compared: effective weights are the frozen
  base plus the stream's own OSSL delta, a LIF layer with three traces, the
  IA/SS gate with its per-stream adaptive threshold, the gated three-factor
  update into the delta on the kept coordinates, the bypass readout, the
  window roll, and the per-chunk delta clip.
* :func:`train_steps` — the training step: one aligned batch for ``T``
  timesteps with a batch-shared gate, the update into the base weights at
  the batch-mean rate, the SL readout delta rule, the DSST factor
  accumulators, and a prune/regrow epoch every ``period`` samples.

``cfg`` is the configuration as a plain dict (the JSON file's keys).
Every matmul goes through :func:`mm`, in the configuration's
``precision``.
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

COS_EPS = 1e-6


def nm_counts(cfg: Dict[str, Any]):
    """(m, n): N:M group size and kept count per group for a fan-in of
    ``n_in`` split into 4 groups (the chip's four PEs)."""
    m = cfg["n_in"] // 4
    n = max(1, int(round(m * (1.0 - cfg["sparsity"]))))
    return m, n


def dsst_k(cfg: Dict[str, Any]) -> int:
    """Connections recycled per group at a DSST event (no decay)."""
    _, n = nm_counts(cfg)
    d = cfg["dsst"]
    k = int(round(n * d["prune_frac"]))
    return max(0, min(k, n - 1))


def mm(cfg, a, b, spec: str = "...k,kn->...n"):
    """``a @ b`` (or the einsum ``spec``) in ``cfg["precision"]``, the
    same on any backend.

    ``highest`` takes exact float32 products. ``high`` is the three-pass
    bfloat16 algorithm that precision names, spelt out so that no backend
    may compute more exactly: each operand split into a bfloat16 head and
    a bfloat16 tail, the tail-by-tail term dropped."""
    p = cfg["precision"]
    if p == "highest":
        return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)

    def split(x):
        head = x.astype(jnp.bfloat16)
        return head, (x - head.astype(jnp.float32)).astype(jnp.bfloat16)

    def dot(x, y):
        return jnp.einsum(spec, x, y, preferred_element_type=jnp.float32)

    if p != "high":
        raise ValueError(f"unknown precision {p!r}")
    (ah, al), (bh, bl) = split(a), split(b)
    return dot(ah, bh) + (dot(ah, bl) + dot(al, bh))


def _windows(cfg):
    t = cfg["t_steps"]
    return int(t * cfg["pc_snapshot_frac"]), int(t * cfg["wu_start_frac"])


def _cos(a, b):
    return (a * b).sum(-1) / (
        jnp.sqrt((a * a).sum(-1)) * jnp.sqrt((b * b).sum(-1)) + COS_EPS)


def _cos_grad(a, b):
    """d cos(a, b) / d a, with the same epsilon as :func:`_cos`."""
    na = jnp.sqrt((a * a).sum(-1, keepdims=True)) + COS_EPS
    nb = jnp.sqrt((b * b).sum(-1, keepdims=True)) + COS_EPS
    c = (a * b).sum(-1, keepdims=True) / (na * nb)
    return b / (na * nb) - c * a / (na * na)


def modulator(cfg, tr, tr_pc, tr_cc, v):
    """Third factor: -dL/dtr of L = -cos(tr, tr_pc) + cc * cos(tr, tr_cc),
    shaped by the triangular surrogate of the spike function."""
    g = _cos_grad(tr, tr_pc) - cfg["cc_weight"] * _cos_grad(tr, tr_cc)
    sur = jnp.maximum(0.0, 1.0 - jnp.abs(v - cfg["theta"])
                      / (cfg["theta"] * cfg["surrogate_width"]))
    return g * sur


def gate(cfg, ss_mean, ia, ss):
    """IA above the global threshold and SS below the adaptive one; the
    running mean of |SS| always adapts."""
    g = cfg["gating"]
    if g["enabled"]:
        open_ = (ia > g["theta_ia"]) & (ss < g["ss_scale"] * ss_mean)
    else:
        open_ = jnp.ones_like(ia > 0)
    return open_, (1 - g["ss_rho"]) * ss_mean + g["ss_rho"] * jnp.abs(ss)


def dense_mask(unit_mask):
    """The configuration's N:M is element-granular: the unit mask is the
    dense ``[L, K, N]`` mask itself."""
    return jnp.asarray(unit_mask).astype(jnp.float32)


# ---------------------------------------------------------------------------
# serving: independent streams over a frozen base
# ---------------------------------------------------------------------------

class StreamCarry(NamedTuple):
    v: jax.Array        # [L, N]
    tr: jax.Array
    tr_pc: jax.Array
    tr_cc: jax.Array
    x_tr: jax.Array     # [K]
    ss_mean: jax.Array  # [L]
    t_win: jax.Array    # [] int32
    delta: jax.Array    # [L, K, N] dense, zero off the mask


def fresh_stream(cfg) -> StreamCarry:
    L, N, K = cfg["n_layers"], cfg["n_hidden"], cfg["n_in"]
    z = jnp.zeros((L, N), jnp.float32)
    return StreamCarry(z, z, z, z, jnp.zeros((K,), jnp.float32),
                       jnp.full((L,), cfg["gating"]["ss_init"], jnp.float32),
                       jnp.zeros((), jnp.int32),
                       jnp.zeros((L, K, N), jnp.float32))


def _stream_timestep(cfg, w, mask, readout, c: StreamCarry, x, chunk_end,
                     lr):
    """One timestep of one stream. Returns (carry', logits [n_out],
    window_end)."""
    t_pc, t_wu = _windows(cfg)
    T = cfg["t_steps"]
    t = c.t_win
    x_tr = cfg["beta"] * c.x_tr + x
    pre, pre_tr = x, x_tr
    logits = jnp.zeros((readout.shape[-1],), jnp.float32)
    vs, trs, pcs, means, deltas = [], [], [], [], []
    for l in range(cfg["n_layers"]):
        cur = mm(cfg, pre, w[l] + c.delta[l])
        v = cfg["alpha"] * c.v[l] + cur
        s = (v >= cfg["theta"]).astype(jnp.float32)
        v = v - s * cfg["theta"]
        tr = cfg["beta"] * c.tr[l] + s
        tr_pc = jnp.where(t == t_pc, tr, c.tr_pc[l])
        mod = modulator(cfg, tr, tr_pc, c.tr_cc[l], v)
        open_, mean = gate(cfg, c.ss_mean[l], pre.mean(), _cos(tr, c.tr_cc[l]))
        on = open_ & (t >= t_wu)
        d = c.delta[l] + jnp.where(on, lr, 0.0) * (
            pre_tr[:, None] * mod[None, :]) * mask[l]
        logits = logits + mm(cfg, tr, readout[l])
        vs.append(v), trs.append(tr), pcs.append(tr_pc), means.append(mean)
        deltas.append(d)
        pre, pre_tr = s, tr
    delta = jnp.stack(deltas)
    hygiene = delta * cfg["adapt"]["delta_decay"]
    clip = cfg["adapt"]["delta_clip"]
    if clip > 0:
        hygiene = jnp.clip(hygiene, -clip, clip)
    delta = jnp.where(chunk_end, hygiene, delta)
    v, tr, tr_pc = jnp.stack(vs), jnp.stack(trs), jnp.stack(pcs)
    end = t == T - 1
    z = jnp.zeros_like(v)
    new = StreamCarry(
        v=jnp.where(end, z, v), tr=jnp.where(end, z, tr),
        tr_pc=jnp.where(end, z, tr_pc), tr_cc=jnp.where(end, tr, c.tr_cc),
        x_tr=jnp.where(end, jnp.zeros_like(x_tr), x_tr),
        ss_mean=jnp.stack(means), t_win=(t + 1) % T, delta=delta)
    return new, logits, end


def serve_streams(cfg, params, events, n_steps, chunk_end):
    """Run independent streams from a fresh lane through their events.

    Args:
      params: ``{"hidden": {"w" [L,K,N], "mask" bool [L,K,N]},
        "readout" [L,N,n_out]}`` — the frozen base.
      events: ``[R, Tmax, n_in]`` spikes of R streams (padded past each
        stream's length).
      n_steps: ``[R]`` int, timesteps each stream was fed.
      chunk_end: ``[R, Tmax]`` bool, True at the last timestep of each grid
        step's chunk (where the served path clips the delta).

    Returns ``(carry, logits [R, Tmax, n_out], window_end [R, Tmax])``;
    timesteps past a stream's length leave it untouched and emit nothing.
    """
    w = jnp.asarray(params["hidden"]["w"], jnp.float32)
    mask = dense_mask(params["hidden"]["mask"])
    readout = jnp.asarray(params["readout"], jnp.float32)
    lr = cfg["lr"] * cfg["adapt"]["lr_scale"]

    def one(ev, n, ce):
        def body(c, inp):
            i, x, e = inp
            new, logits, end = _stream_timestep(cfg, w, mask, readout, c, x,
                                                e, lr)
            live = i < n
            c = jax.tree_util.tree_map(lambda a, b: jnp.where(live, a, b),
                                       new, c)
            return c, (logits, end & live)
        idx = jnp.arange(ev.shape[0])
        return jax.lax.scan(body, fresh_stream(cfg), (idx, ev, ce))

    carry, (logits, ends) = jax.jit(jax.vmap(one))(
        jnp.asarray(events, jnp.float32), jnp.asarray(n_steps, jnp.int32),
        jnp.asarray(chunk_end, bool))
    return carry, logits, ends


# ---------------------------------------------------------------------------
# training: aligned batches, base-weight update, SL readout, DSST
# ---------------------------------------------------------------------------

class TrainCarry(NamedTuple):
    w: jax.Array         # [L, K, N] masked weights
    mask: jax.Array      # [L, K, N] bool
    readout: jax.Array   # [L, N, n_out]
    tr_cc: jax.Array     # [L, B, N] final traces of the previous sample
    ss_mean: jax.Array   # [L]
    acc_pre: jax.Array   # [L, K]
    acc_post: jax.Array  # [L, N]
    sample_idx: jax.Array


def fresh_train(cfg, params, batch: int, sample_idx: int) -> TrainCarry:
    L, N, K = cfg["n_layers"], cfg["n_hidden"], cfg["n_in"]
    return TrainCarry(
        w=jnp.asarray(params["hidden"]["w"], jnp.float32),
        mask=jnp.asarray(params["hidden"]["mask"], bool),
        readout=jnp.asarray(params["readout"], jnp.float32),
        tr_cc=jnp.zeros((L, batch, N), jnp.float32),
        ss_mean=jnp.full((L,), cfg["gating"]["ss_init"], jnp.float32),
        acc_pre=jnp.zeros((L, K), jnp.float32),
        acc_post=jnp.zeros((L, N), jnp.float32),
        sample_idx=jnp.asarray(sample_idx, jnp.int32))


def prune_regrow(cfg, w, mask, pre):
    """One DSST event on one layer: in each group of ``m`` consecutive
    inputs of each output column, keep the ``n - k`` active connections of
    largest |w| and regrow the ``k`` inactive ones whose presynaptic
    activity ``pre`` is largest (ties to the lower input index). Regrown
    weights start at 0. Returns (w', mask')."""
    m, n = nm_counts(cfg)
    k = dsst_k(cfg)
    K, N = w.shape
    g = K // m
    wg = jnp.abs(w).reshape(g, m, N)
    mg = mask.reshape(g, m, N)
    pg = jnp.broadcast_to(pre.reshape(g, m, 1), (g, m, N))
    keep_order = jnp.argsort(jnp.where(mg, -wg, jnp.inf), axis=1, stable=True)
    grow_order = jnp.argsort(jnp.where(mg, jnp.inf, -pg), axis=1, stable=True)
    rows = jnp.concatenate([keep_order[:, :n - k], grow_order[:, :k]], axis=1)
    gi = jnp.arange(g)[:, None, None]
    ci = jnp.arange(N)[None, None, :]
    new = jnp.zeros((g, m, N), bool).at[gi, rows, ci].set(True).reshape(K, N)
    return jnp.where(mask & new, w, 0.0), new


def train_sample(cfg, c: TrainCarry, events, labels):
    """One training sample (``events [T, B, n_in]``, ``labels [B]``).
    Returns (carry', local_loss, logits [B, n_out])."""
    t_pc, t_wu = _windows(cfg)
    T, B, K = events.shape
    L = cfg["n_layers"]
    lr = cfg["lr"] / B
    maskf = c.mask.astype(jnp.float32)

    def ts(carry, inp):
        t, x = inp
        w, v, tr, tr_pc, x_tr, ss_mean = carry
        x_tr = cfg["beta"] * x_tr + x
        pre, pre_tr = x, x_tr
        logits = jnp.zeros((B, c.readout.shape[-1]), jnp.float32)
        loss = jnp.zeros((B,), jnp.float32)
        late = t >= t_wu
        ws, vs, trs, pcs, means = [], [], [], [], []
        for l in range(L):
            vl = cfg["alpha"] * v[l] + mm(cfg, pre, w[l])
            s = (vl >= cfg["theta"]).astype(jnp.float32)
            vl = vl - s * cfg["theta"]
            trl = cfg["beta"] * tr[l] + s
            pcl = jnp.where(t == t_pc, trl, tr_pc[l])
            mod = modulator(cfg, trl, pcl, c.tr_cc[l], vl)
            open_, mean = gate(cfg, ss_mean[l], pre.mean(),
                               _cos(trl, c.tr_cc[l]).mean())
            on = open_ & late
            ws.append(w[l] + jnp.where(on, lr, 0.0) * mm(cfg, pre_tr.T, mod)
                      * maskf[l])
            loss = loss + (-_cos(trl, pcl)
                           + cfg["cc_weight"] * _cos(trl, c.tr_cc[l])) * late
            logits = logits + mm(cfg, trl, c.readout[l])
            vs.append(vl), trs.append(trl), pcs.append(pcl), means.append(mean)
            pre, pre_tr = s, trl
        new = (jnp.stack(ws), jnp.stack(vs), jnp.stack(trs), jnp.stack(pcs),
               x_tr, jnp.stack(means))
        return new, (logits, loss.mean() / L)

    N = cfg["n_hidden"]
    z = jnp.zeros((L, B, N), jnp.float32)
    carry0 = (c.w, z, z, z, jnp.zeros((B, K), jnp.float32), c.ss_mean)
    (w, v, tr, tr_pc, x_tr, ss_mean), (logits_t, loss_t) = jax.lax.scan(
        ts, carry0, (jnp.arange(T), events))
    logits = logits_t[-1]
    local_loss = loss_t.sum() / max(1, T - t_wu)

    err = jax.nn.one_hot(labels, cfg["n_out"]) - jax.nn.softmax(logits)
    readout = c.readout + (cfg["lr_out"] / B) * mm(cfg, tr, err,
                                                    "lbn,bo->lno")
    pres = [x_tr] + [tr[l] for l in range(L - 1)]
    acc_pre = jnp.stack([0.9 * c.acc_pre[l] + jnp.abs(pres[l]).mean(0)
                         for l in range(L)])
    acc_post = jnp.stack([
        0.9 * c.acc_post[l]
        + jnp.abs(modulator(cfg, tr[l], tr_pc[l], c.tr_cc[l], v[l])).mean(0)
        for l in range(L)])
    mask = c.mask
    d = cfg["dsst"]
    i = c.sample_idx
    due = (i >= d["start_step"]) & (i < d["stop_step"]) & \
        (i % d["period"] == d["period"] - 1)
    if cfg["dsst_enabled"]:
        evolved = [prune_regrow(cfg, w[l], mask[l], acc_pre[l])
                   for l in range(L)]
        w = jnp.where(due, jnp.stack([e[0] for e in evolved]), w)
        mask = jnp.where(due, jnp.stack([e[1] for e in evolved]), mask)
        acc_pre = jnp.where(due, 0.0, acc_pre)
        acc_post = jnp.where(due, 0.0, acc_post)
    new = TrainCarry(w=w, mask=mask, readout=readout, tr_cc=tr,
                     ss_mean=ss_mean, acc_pre=acc_pre, acc_post=acc_post,
                     sample_idx=i + 1)
    return new, local_loss, logits


def train_steps(cfg, params, batches, sample_idx: int):
    """Run the training reference over ``batches`` [(events, labels), ...]
    from fresh state at ``sample_idx``. Returns a list with, per step,
    ``{"params", "local_loss", "logits"}`` as numpy arrays."""
    B = batches[0][0].shape[1]
    step = jax.jit(lambda c, ev, lab: train_sample(cfg, c, ev, lab))
    c = fresh_train(cfg, params, B, sample_idx)
    out = []
    for ev, lab in batches:
        c, loss, logits = step(c, jnp.asarray(ev, jnp.float32),
                               jnp.asarray(lab, jnp.int32))
        out.append({
            "params": {"hidden": {"w": np.asarray(c.w),
                                  "mask": np.asarray(c.mask)},
                       "readout": np.asarray(c.readout)},
            "local_loss": float(loss), "logits": np.asarray(logits)})
    return out
