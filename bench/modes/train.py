"""Training cells: the jitted ``snn.make_train_fn`` step over a pool of
batches fed from the host.

Set-up makes the weights from the seed and the batch pool, builds the one
step object with its state, and drives it through its first three steps
on the pool's first three batches — the same call and feed as the window.
Those three steps are the ones the reference follows, and the window goes
on from their result. The state starts ``start_sample_idx`` samples into
its DSST period (a job resumed mid-period), so the third step is a
prune/regrow event.

``train_samples_per_s`` is batch x steps completed in the window over the
window's seconds; the window ends when the last step's weights are ready.
The host keeps at most ``inflight`` steps queued ahead of the device, as
an input pipeline with that much prefetch would.

The pool holds each batch's spikes as the host stores event data: one bit
per input, packed into bytes (``np.packbits``), 1/32 of the float32
array, in rows of 128 bytes, whose tiled device layout is their row-major
one, so the transfer needs no relayout on the host. The feed sends the
packed batch and widens it to float32 ``[T, B, n_in]`` on the device.
"""
from __future__ import annotations

import time
from typing import Any, Dict

import numpy as np

from bench import harness
from bench.compare import train_checks
from bench.flops import per_timestep
from bench.generator import train_batches
from bench.modes.serve import _snn_config


def run(cell: harness.Cell, *, seed: int, seconds: float, trace: bool,
        clock: harness.CompileClock, t_start: float, devs,
        fault: str = "", control: str = "") -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    from repro.core import snn
    from repro.obs.trace import Tracer

    cfg, mix = cell.config, cell.traffic
    B, T = mix["batch"], cfg["t_steps"]
    snn_cfg = _snn_config(cfg)
    setup: Dict[str, float] = {"import": time.perf_counter() - t_start}

    t = time.perf_counter()
    from bench.weights import make_params
    p0 = jax.block_until_ready(make_params(cfg, seed))
    setup["weights"] = time.perf_counter() - t
    t = time.perf_counter()
    batches = train_batches(mix, cfg, seed)
    ref_batches = batches[:3]            # float32, for the reference
    pool = [(np.packbits(ev.astype(bool), axis=-1).reshape(-1, 128), lab)
            for ev, lab in batches]
    del batches
    setup["traffic"] = time.perf_counter() - t
    shape = (T, B, cfg["n_in"])
    widen = jax.jit(lambda b: jnp.unpackbits(b.reshape(-1))
                    .reshape(shape).astype(jnp.float32))

    def feed(i):
        packed, lab = pool[i % len(pool)]
        return widen(packed), lab

    tracer = Tracer(capacity=1 << 16, annotate=True) if trace else None
    span = tracer.span if tracer is not None else (
        lambda name, **kw: harness.NULL_SPAN)
    step = snn.make_train_fn(snn_cfg)
    if fault:
        from bench.faults import plant_train
        step = plant_train(step, fault)
    start_idx = mix["start_sample_idx"]
    state = snn.init_state(snn_cfg, B)._replace(
        sample_idx=jnp.asarray(start_idx, jnp.int32))

    t = time.perf_counter()
    comp0 = clock.seconds
    got, p = [], p0
    for i in range(3):                   # the steps the reference follows
        ev, lab = feed(i)
        p, state, m = step(p, state, ev, lab)
        got.append({"params": jax.device_get(p),
                    "local_loss": float(m.local_loss)})
    setup["first_steps"] = time.perf_counter() - t
    setup["compile_s"] = clock.seconds - comp0
    t = time.perf_counter()
    harness.settle()
    setup["settle"] = time.perf_counter() - t
    setup_s = time.perf_counter() - t_start

    # ---- the measured window ----------------------------------------------
    programs0 = clock.programs
    gc_clock = harness.GcClock()
    inflight = mix["inflight"]
    losses, n = [], 3
    prof = None
    if trace:
        from bench.trace_reduce import Profile
        prof = Profile(seconds, mix["trace_seconds"])
    t0 = time.perf_counter()
    while True:
        if prof is not None:
            prof.tick(time.perf_counter() - t0)
        with span("train.feed"):
            ev, lab = feed(n)
        with span("train.dispatch"):
            p, state, m = step(p, state, ev, lab)
        losses.append(m.local_loss)
        n += 1
        if len(losses) > inflight:
            with span("train.wait"):
                losses[-1 - inflight].block_until_ready()
        if time.perf_counter() - t0 >= seconds:
            break
    with span("train.wait"):
        jax.block_until_ready(p)
    t1 = time.perf_counter()
    compiles_in_window = clock.programs - programs0
    gc_clock.stop()
    if prof is not None:
        prof.stop()
    mem_peak = harness.peak_bytes(devs)
    steps = len(losses)
    loss_host = np.asarray(jax.device_get(losses))
    failed = int(np.sum(~np.isfinite(loss_host)))
    window_s = t1 - t0
    counts = {"window_s": window_s, "steps": steps, "samples": steps * B,
              "batch": B, "compiles_in_window": compiles_in_window,
              "gc_s": round(gc_clock.seconds, 6), "gc_full": gc_clock.full,
              "flops_per_timestep": per_timestep(cfg), "t_steps": T}
    harness.eprint(
        f"set-up: total_s={setup_s:.3f} " + " ".join(
            f"{k}={v:.3f}" for k, v in setup.items())
        + f" cache_hits={clock.cache_hits} programs={clock.programs}")
    harness.eprint("window: " + " ".join(f"{k}={v}" for k, v in
                                        counts.items()))
    metrics = {"setup_s": (setup_s, "s"),
               "train_samples_per_s": (steps * B / window_s, "samples/s")}

    # ---- correctness: the first three steps against the reference ----------
    spans = tracer.spans() if tracer is not None else []
    del p, state, m, losses
    p0_host = jax.device_get(p0)
    del p0
    t = time.perf_counter()
    checks = train_checks(cfg, p0_host, ref_batches, start_idx, got,
                          control=control)
    harness.eprint(f"reference: steps=3 seconds="
                   f"{time.perf_counter() - t:.3f}")
    return {"metrics": metrics, "attempted": steps, "failed": failed,
            "checks": checks, "peak_bytes": mem_peak, "counts": counts,
            "spans": spans, "window": (t0, t1), "profile": prof}
