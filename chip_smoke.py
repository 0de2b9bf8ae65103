"""Chip smoke test: the SNN main path at the paper's widths on a TPU.

    python chip_smoke.py              # one chip: serve, train, reference
    python chip_smoke.py --chips 4    # four chips: sharded fleet vs one chip

Drives the system through the entry points a user calls, at
``configs/elfcore_snn.CONFIG`` — the paper's 512-512-512-16 network, T=50,
80 % element-granular N:M, DSST period 40 — with random weights from
``--seed``:

* **serve** — ``StreamScheduler`` over ~1024 ``TaskStreamSource`` gesture
  streams (compact per-stream deltas, ~0.43 MB each), double-buffered
  staging, and a live ``TopologyService`` that runs prune/regrow epochs
  between grid steps. Checks one prediction per closed window, finite
  logits, the N:M invariant after the epochs, and exactly one compile.
* **train** — ``snn.run_sample`` (jitted, batch 32, ``learn=True``) for
  ``dsst.period + 1`` samples, so one prune/regrow event runs on the chip.
  Checks finite weights and the N:M invariant.
* **reference** — a handful of streams and one training sample run again
  on the host CPU; both sides under ``default_matmul_precision("highest")``
  (the TPU takes bf16 passes for f32 matmuls by default, and thresholded
  spikes amplify that). Fails past ``REF_LOGIT_ATOL``,
  ``REF_WEIGHT_ATOL`` or ``REF_MIN_AGREE``.
  The serve and train phases run at the default precision.

``--chips 4`` runs only the sharded path: the live-topology fleet on a
4-chip ``("slots",)`` mesh and the same fleet on one of those chips, in
this one process; the two must agree bit for bit.

Exits non-zero, printing no result line, unless JAX's first device is a
TPU. Every rate printed is from this smoke run, not a benchmark. The last
line of standard output is the result, e.g.
``{"ok": true, "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# serving grid: ~1024 concurrent gesture streams of a few T=50 windows
N_STREAMS, N_WINDOWS, CHUNK_LEN = 1024, 3, 25
EPOCH_EVERY = 3                   # grid steps between live DSST epochs
TRAIN_BATCH = 32
N_REF_STREAMS = 8
REF_LOGIT_ATOL = 1e-3             # max |logits_tpu - logits_cpu|
REF_WEIGHT_ATOL = 1e-5            # max |w_tpu - w_cpu| after one sample
REF_MIN_AGREE = 1.0               # fraction of equal argmax predictions
RATE_NOTE = "(smoke, not a benchmark)"


def _keep_cpu_backend() -> None:
    """The reference phase runs on the host CPU next to the TPU; when
    ``JAX_PLATFORMS`` pins a platform list, append ``cpu`` to it (the
    first entry stays the default device)."""
    plats = os.environ.get("JAX_PLATFORMS")
    if plats and "cpu" not in plats.split(","):
        os.environ["JAX_PLATFORMS"] = plats + ",cpu"


class CompileClock:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events (the whole process, every program)."""

    def __init__(self):
        import jax
        self.seconds, self.programs, self.cache_hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.programs += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return self.seconds, self.programs, self.cache_hits

    def since(self, snap) -> str:
        s, p, h = snap
        return (f"compile_s={self.seconds - s:.2f} "
                f"programs={self.programs - p} "
                f"cache_hits={self.cache_hits - h}")


def _host_params(cfg, seed):
    """Random weights made once on the host CPU (numpy leaves), so every
    device starts from the same bits."""
    import jax
    from repro.core.snn import init_params
    with jax.default_device(jax.devices("cpu")[0]):
        return jax.device_get(init_params(jax.random.PRNGKey(seed), cfg))


def _sources(cfg, n_streams, n_windows, seed):
    from repro.data.events import make_task
    from repro.serving import ArrivalConfig, TaskStreamSource
    task = make_task("gesture", n_in=cfg.n_in, t_steps=cfg.t_steps,
                     seed=seed)
    arrival = ArrivalConfig(min_chunk=CHUNK_LEN // 2, max_chunk=CHUNK_LEN,
                            mean_gap_s=1e-3, start_jitter_s=1e-3)
    return [TaskStreamSource(task, n_windows=n_windows, seed=seed + sid,
                             arrival=arrival) for sid in range(n_streams)]


def run_fleet(cfg, params, *, n_streams, n_windows, seed, mesh=None,
              epoch_every=0, pipeline_depth=1, tracer=None):
    """Serve ``n_streams`` gesture streams to completion through the
    scheduler. Returns (scheduler, sessions by sid, first-step s, wall s)."""
    from repro.serving import (StreamScheduler, StreamSession,
                               TopologyService, TopologyServiceConfig)
    svc = None
    if epoch_every:
        svc = TopologyService(cfg, TopologyServiceConfig(
            epoch_every=epoch_every, merge_top=2))
    sched = StreamScheduler(params, cfg, n_slots=n_streams,
                            chunk_len=CHUNK_LEN, mesh=mesh, topology=svc,
                            pipeline_depth=pipeline_depth, tracer=tracer)
    for sid, src in enumerate(_sources(cfg, n_streams, n_windows, seed)):
        sched.submit(StreamSession(sid=sid, source=src))
    t0 = time.perf_counter()
    sched.step()                      # compiles the grid step
    sched.flush()
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    done = {s.sid: s for s in sched.run_until_drained()}
    return sched, done, first, time.perf_counter() - t0


def _check_fleet(sched, done, cfg, n_streams, n_windows, epochs_min):
    import numpy as np
    from repro.core import topology
    assert len(done) == n_streams, (len(done), n_streams)
    for sid, sess in done.items():
        assert len(sess.predictions) == n_windows, \
            (sid, len(sess.predictions), n_windows)
        for p in sess.predictions:
            assert np.all(np.isfinite(p.logits)), (sid, p.window_idx)
    assert sched.n_compiles == 1, f"grid step compiled {sched.n_compiles}x"
    if epochs_min:
        assert sched.topology.epoch_idx >= epochs_min, \
            f"only {sched.topology.epoch_idx} topology epochs ran"
        assert topology.check(sched.params["hidden"]["mask"], cfg), \
            "N:M invariant broken after the live epochs"


def _span_totals(tracer) -> str:
    """Host-clock seconds per scheduler span name, summed over the run
    (spans nest: stage holds admit; retire holds device_wait and
    topology.epoch)."""
    tot = {}
    for sp in tracer.spans():
        tot[sp.name] = tot.get(sp.name, 0.0) + sp.dur_s
    return " ".join(f"{k}={v:.2f}" for k, v in sorted(tot.items()))


def serve_phase(cfg, params, clock, seed):
    from repro.obs.trace import Tracer
    snap = clock.snapshot()
    tracer = Tracer()
    sched, done, first, wall = run_fleet(
        cfg, params, n_streams=N_STREAMS, n_windows=N_WINDOWS, seed=seed,
        epoch_every=EPOCH_EVERY, tracer=tracer)
    _check_fleet(sched, done, cfg, N_STREAMS, N_WINDOWS, epochs_min=1)
    r = sched.telemetry.rollup()
    held = r["bytes_held"]
    svc = sched.topology
    print(f"serve: streams={N_STREAMS} windows/stream={N_WINDOWS} "
          f"slots={sched.n_slots} chunk_len={CHUNK_LEN} "
          f"grid_steps={sched.grid.stats['steps']} n_compiles="
          f"{sched.n_compiles} epochs={svc.epoch_idx} "
          f"pruned={sum(e.pruned for e in svc.events)} "
          f"bytes_held params={held['params']:.0f} "
          f"deltas={held['deltas']:.0f} {clock.since(snap)}")
    print(f"serve: first_step_s={first:.2f} drain_s={wall:.2f} "
          f"timesteps/s={r['timesteps_per_s']:.0f} "
          f"events/s={r['events_per_s']:.0f} {RATE_NOTE}")
    print(f"serve: span_s {_span_totals(tracer)} {RATE_NOTE}")


def _train_batches(cfg, n, seed):
    import numpy as np
    from repro.data.events import make_task
    task = make_task("gesture", n_in=cfg.n_in, t_steps=cfg.t_steps,
                     seed=seed)
    rng = np.random.default_rng(seed)
    return [task.sample(rng, TRAIN_BATCH) for _ in range(n)]


def _nm_holds(params, cfg) -> bool:
    """Exactly n kept units per N:M group, and no weight off the mask."""
    import numpy as np
    from repro.core import topology
    dense = np.asarray(topology.dense_masks(params["hidden"]["mask"], cfg))
    off = np.asarray(params["hidden"]["w"]) * (1.0 - dense)
    return topology.check(params["hidden"]["mask"], cfg) and \
        not np.any(off)


def train_phase(cfg, params, clock, seed):
    import jax
    import numpy as np
    from repro.core import snn
    n = cfg.dsst.period + 1
    batches = _train_batches(cfg, n, seed)
    step = snn.make_train_fn(cfg)
    st = snn.init_state(cfg, TRAIN_BATCH)
    p = params
    snap = clock.snapshot()
    t0 = time.perf_counter()
    p, st, m = step(p, st, *batches[0])
    jax.block_until_ready(p)
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    for ev, lab in batches[1:]:
        p, st, m = step(p, st, ev, lab)
    jax.block_until_ready(p)
    wall = time.perf_counter() - t0
    p = jax.device_get(p)
    assert int(st.sample_idx) == n, int(st.sample_idx)
    for leaf in jax.tree_util.tree_leaves(p):
        if np.issubdtype(np.asarray(leaf).dtype, np.floating):
            assert np.all(np.isfinite(leaf)), "non-finite weights"
    assert _nm_holds(p, cfg), "N:M invariant broken by training"
    changed = float(np.mean(p["hidden"]["mask"]
                            != params["hidden"]["mask"]))
    assert changed > 0, "no prune/regrow event ran"
    print(f"train: samples={n} batch={TRAIN_BATCH} mask_changed="
          f"{changed:.4f} local_loss={float(m.local_loss):.4f} "
          f"{clock.since(snap)}")
    print(f"train: first_sample_s={first:.2f} "
          f"samples/s={(n - 1) / wall:.1f} {RATE_NOTE}")


def reference_phase(cfg, params, seed):
    """A few streams and one training sample on the TPU and on the host
    CPU, both at full f32 matmul precision."""
    import jax
    import numpy as np
    from repro.core import snn
    tpu, cpu = jax.devices()[0], jax.devices("cpu")[0]
    (ev, lab), = _train_batches(cfg, 1, seed + 1)
    out = []
    for dev in (tpu, cpu):
        with jax.default_device(dev), \
                jax.default_matmul_precision("highest"):
            p = jax.device_put(params, dev)
            sched, done, _, _ = run_fleet(
                cfg, p, n_streams=N_REF_STREAMS, n_windows=2,
                seed=seed + 100, pipeline_depth=0)
            _check_fleet(sched, done, cfg, N_REF_STREAMS, 2, epochs_min=0)
            logits = np.stack([np.stack([w.logits for w in
                                         done[s].predictions])
                               for s in sorted(done)])
            p2, _, m = snn.make_train_fn(cfg)(
                p, snn.init_state(cfg, TRAIN_BATCH), ev, lab)
            out.append((logits, np.asarray(m.logits),
                        np.asarray(p2["hidden"]["w"])))
    (sl_t, tl_t, w_t), (sl_c, tl_c, w_c) = out
    d_serve = float(np.abs(sl_t - sl_c).max())
    d_train = float(np.abs(tl_t - tl_c).max())
    d_w = float(np.abs(w_t - w_c).max())
    agree = float(np.mean(sl_t.argmax(-1) == sl_c.argmax(-1)))
    print(f"reference: precision=highest streams={N_REF_STREAMS} "
          f"max|dlogits| serve={d_serve:.3e} train={d_train:.3e} "
          f"max|dw| train={d_w:.3e} prediction_agreement={agree:.3f} "
          f"(limits {REF_LOGIT_ATOL:g}, {REF_WEIGHT_ATOL:g}, "
          f"{REF_MIN_AGREE:g})")
    assert d_serve <= REF_LOGIT_ATOL and d_train <= REF_LOGIT_ATOL, \
        (d_serve, d_train)
    assert d_w <= REF_WEIGHT_ATOL, d_w
    assert agree >= REF_MIN_AGREE, agree


def sharded_phase(cfg, params, clock, seed, n_chips):
    """The live-topology fleet on an ``n_chips`` slot mesh vs the same
    fleet on the first of those chips: bitwise agreement."""
    import jax
    import numpy as np
    from repro.launch.mesh import make_serving_mesh
    runs = {}
    for name, mesh in (("mesh", make_serving_mesh(n_chips)), ("one", None)):
        snap = clock.snapshot()
        sched, done, first, wall = run_fleet(
            cfg, params, n_streams=N_STREAMS, n_windows=N_WINDOWS,
            seed=seed, mesh=mesh, epoch_every=EPOCH_EVERY)
        _check_fleet(sched, done, cfg, N_STREAMS, N_WINDOWS, epochs_min=1)
        print(f"sharded[{name}]: chips={n_chips if mesh else 1} "
              f"slots={sched.n_slots} grid_steps="
              f"{sched.grid.stats['steps']} n_compiles={sched.n_compiles} "
              f"epochs={sched.topology.epoch_idx} {clock.since(snap)}")
        print(f"sharded[{name}]: first_step_s={first:.2f} "
              f"drain_s={wall:.2f} {RATE_NOTE}")
        runs[name] = (sched, done)
    (sm, dm), (s1, d1) = runs["mesh"], runs["one"]
    events = [[(e.pruned, e.regrown, e.merged_slots) for e in s.topology.events]
              for s in (sm, s1)]

    def maxdiff(pairs):
        return max(float(np.abs(np.asarray(a, np.float64)
                                - np.asarray(b, np.float64)).max())
                   for a, b in pairs)

    d_params = maxdiff(zip(
        jax.tree_util.tree_leaves(jax.device_get(sm.params)),
        jax.tree_util.tree_leaves(jax.device_get(s1.params))))
    d_deltas = maxdiff([(sm.deltas, s1.deltas)])
    d_logits = maxdiff([(a.logits, b.logits) for sid in d1
                        for a, b in zip(dm[sid].predictions,
                                        d1[sid].predictions)])
    print(f"sharded: {n_chips}-chip vs 1-chip max|diff| params={d_params:g} "
          f"deltas={d_deltas:g} logits={d_logits:g} (over "
          f"{len(d1) * N_WINDOWS} windows) epochs_equal="
          f"{events[0] == events[1]}")
    assert events[0] == events[1], events
    assert d_params == d_deltas == d_logits == 0.0, "not bit-identical"
    print(f"sharded: {n_chips}-chip fleet == 1-chip fleet bit for bit")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded-fleet comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    _keep_cpu_backend()
    import jax
    from repro.configs.elfcore_snn import CONFIG
    from repro.runtime.compile_cache import use_persistent_cache

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX's first device is "
              f"{devs[0].platform!r}", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devs)}", file=sys.stderr)
        return 2
    cache = use_persistent_cache(ROOT)
    clock = CompileClock()
    print(f"device: platform={devs[0].platform} "
          f"kind={devs[0].device_kind} count={len(devs)} "
          f"jax={jax.__version__} compile_cache={cache}")
    params = _host_params(CONFIG, args.seed)
    t0 = time.perf_counter()
    if args.chips > 1:
        sharded_phase(CONFIG, params, clock, args.seed, args.chips)
    else:
        serve_phase(CONFIG, params, clock, args.seed)
        train_phase(CONFIG, params, clock, args.seed)
        reference_phase(CONFIG, params, args.seed)
    print(f"total: wall_s={time.perf_counter() - t0:.1f} "
          f"compile_s={clock.seconds:.2f} programs={clock.programs} "
          f"cache_hits={clock.cache_hits}")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
