"""Contract registry: the repo's real entrypoints bound to contract sets.

Each entry names one production entrypoint plus the invariants its callers
rely on; ``check_all()`` runs every set on a small-but-real configuration
(compact AND dense delta layouts, sharded and unsharded, factors on and
off). CI's ``static-analysis`` step runs this module
(``python -m repro.analysis.registry``) so a change that breaks a hot-path
contract — a collective sneaking into the shard-mapped step, a dense mask
leaking into the compact jaxpr, a factor accumulator surviving
``want_factors=False`` — fails the build with the contract's name, not as
an 8-device parity diff three tests later.

Entries are built lazily (registering costs nothing at import), each
returning ``(fn, args, contracts, kwargs)`` for
:func:`repro.analysis.jaxpr_contracts.check`.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence, Tuple

from repro.analysis import jaxpr_contracts as jc

_REG: Dict[str, Callable[[], tuple]] = {}

# small-but-real geometry shared by the SNN entries; S is distinct from the
# chunk length, layer count and n_out so slot_separable cannot pass
# vacuously (see its docstring)
_S, _C = 4, 5


def register(name: str):
    def deco(build: Callable[[], tuple]):
        _REG[name] = build
        return build
    return deco


def names() -> List[str]:
    return sorted(_REG)


def check_entry(name: str) -> jc.Report:
    fn, args, contracts, kwargs = _REG[name]()
    return jc.check(fn, args, contracts, kwargs=kwargs, name=name)


def check_all(only: Optional[Sequence[str]] = None) -> Dict[str, jc.Report]:
    return {n: check_entry(n) for n in names()
            if only is None or n in only}


def summary(reports: Optional[Dict[str, jc.Report]] = None) -> dict:
    """Compact roll-up for the benchmark artifact's ``contracts_checked``
    field: how many entrypoints/contracts ran and whether all held."""
    reports = check_all() if reports is None else reports
    return {
        "entrypoints": sorted(reports),
        "contracts": sum(len(r.contracts) for r in reports.values()),
        "violations": sum(len(r.violations) for r in reports.values()),
        "ok": all(r.ok for r in reports.values()),
    }


# --------------------------------------------------------------------------
# shared builders
# --------------------------------------------------------------------------

def _snn_cfg():
    from repro.core.snn import SNNConfig
    return SNNConfig(n_in=32, n_hidden=32, n_layers=2, n_out=8, t_steps=8)


def _snn_inputs(cfg, *, compact: bool, chunk_len: int = _C,
                n_slots: int = _S, packed: bool = False):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import snn

    params = snn.init_params(jax.random.PRNGKey(0), cfg)
    deltas = snn.init_stream_deltas(cfg, n_slots, compact=compact)
    state = snn.init_stream_state(cfg, n_slots)
    rng = np.random.default_rng(0)
    spikes = rng.random((chunk_len, n_slots, cfg.n_in)) < 0.25
    # packed: the uint8 bit rows the scheduler stages
    events = jnp.asarray(np.packbits(spikes, axis=-1) if packed
                         else spikes.astype(np.float32))
    valid = jnp.ones((chunk_len, n_slots), bool)
    amask = jnp.ones((n_slots,), bool)
    return params, deltas, state, events, valid, amask


def _chunk_entry(*, mesh=None, want_factors: bool, compact: bool,
                 chunk_len: int = _C, n_slots: int = _S,
                 packed: bool = False):
    from repro.core import snn
    from repro.serving.adapt import AdaptConfig, make_chunk_fn

    cfg = _snn_cfg()
    params, deltas, state, events, valid, amask = _snn_inputs(
        cfg, compact=compact, chunk_len=chunk_len, n_slots=n_slots,
        packed=packed)
    exec_params = snn.serving_params(params, cfg) if compact else params
    fn = make_chunk_fn(cfg, AdaptConfig(), mesh=mesh,
                       want_factors=want_factors)
    contracts = [
        jc.no_collectives(),
        jc.slot_separable(
            n_slots,
            exempt=(".pre_mag", ".post_mag") if want_factors else ()),
        jc.dtype_discipline(),
        jc.compile_count(),
    ]
    if compact:
        contracts += [jc.mask_free(cfg), jc.no_dense_deltas(cfg, n_slots)]
    if not want_factors:
        contracts += [jc.no_factor_carries(cfg, n_slots,
                                           chunk_len=chunk_len)]
    return fn, (exec_params, deltas, state, events, valid, amask), \
        contracts, None


# --------------------------------------------------------------------------
# entries
# --------------------------------------------------------------------------

@register("serving.chunk_fn[compact,factors]")
def _chunk_compact_factors():
    """The default serving hot path: mask-free exec params, compact deltas,
    DSST factors slot-reduced on device."""
    return _chunk_entry(want_factors=True, compact=True)


@register("serving.chunk_fn[compact,factors,packed]")
def _chunk_compact_factors_packed():
    """The program the scheduler runs: the default hot path fed packed
    uint8 spike rows, widened to float32 inside the step."""
    return _chunk_entry(want_factors=True, compact=True, packed=True)


@register("serving.chunk_fn[compact,frozen,packed]")
def _chunk_compact_frozen_packed():
    """The frozen-topology fleet's program as the scheduler runs it."""
    return _chunk_entry(want_factors=False, compact=True, packed=True)


@register("serving.chunk_fn[compact,frozen]")
def _chunk_compact_frozen():
    """Frozen-topology fleet: factors compiled out of the chunk scan."""
    return _chunk_entry(want_factors=False, compact=True)


@register("serving.chunk_fn[dense]")
def _chunk_dense():
    """The dense-fallback A/B layout (no mask-free claim, but the
    zero-collective / slot-separable / compile-once contracts still bind)."""
    return _chunk_entry(want_factors=True, compact=False)


@register("serving.chunk_fn[tier=interactive]")
def _chunk_tier_interactive():
    """The interactive QoS tier's geometry: a short chunk grid (small
    chunk_len bounds per-window latency). Same compact exec rep and
    contract set as the default hot path — the tiers differ only in
    trace-time shape, never in program structure."""
    return _chunk_entry(want_factors=True, compact=True,
                        chunk_len=3, n_slots=4)


@register("serving.chunk_fn[tier=bulk]")
def _chunk_tier_bulk():
    """The bulk QoS tier's geometry: a long chunk grid (large chunk_len
    amortizes dispatch overhead for throughput streams)."""
    return _chunk_entry(want_factors=True, compact=True,
                        chunk_len=12, n_slots=4)


@register("serving.chunk_fn[sharded]")
def _chunk_sharded():
    """The slot-axis shard_map path — THE zero-collectives claim, checked
    structurally instead of via 8-device parity alone. Runs on however
    many devices the host has (1 in the default test env); the contract
    walks the shard_map sub-jaxpr either way."""
    from repro.launch.mesh import make_serving_mesh
    return _chunk_entry(mesh=make_serving_mesh(), want_factors=True,
                        compact=True)


@register("serving.chunk_fn[sharded,packed]")
def _chunk_sharded_packed():
    """The slot-axis shard_map path fed packed spike rows: each device
    widens only its own slot shard, still with no collective."""
    from repro.launch.mesh import make_serving_mesh
    return _chunk_entry(mesh=make_serving_mesh(), want_factors=True,
                        compact=True, packed=True)


@register("snn.run_chunk[compact]")
def _run_chunk_compact():
    """The raw (unjitted) engine chunk step on the compact layout: the
    per-slot factor metrics keep their S axis here (slot reduction happens
    in the serving wrapper, not the engine)."""
    from repro.core import snn

    cfg = _snn_cfg()
    params, deltas, state, events, valid, _ = _snn_inputs(cfg, compact=True)
    sp = snn.serving_params(params, cfg)

    def run_chunk_compact(p, d, s, e, v):
        return snn.run_chunk(p, d, s, e, v, cfg)

    contracts = [jc.no_collectives(), jc.slot_separable(_S),
                 jc.mask_free(cfg), jc.no_dense_deltas(cfg, _S),
                 jc.dtype_discipline()]
    return run_chunk_compact, (sp, deltas, state, events, valid), \
        contracts, None


@register("snn.run_chunk[dense]")
def _run_chunk_dense():
    from repro.core import snn

    cfg = _snn_cfg()
    params, deltas, state, events, valid, _ = _snn_inputs(cfg, compact=False)

    def run_chunk_dense(p, d, s, e, v):
        return snn.run_chunk(p, d, s, e, v, cfg)

    contracts = [jc.no_collectives(), jc.slot_separable(_S),
                 jc.dtype_discipline()]
    return run_chunk_dense, (params, deltas, state, events, valid), \
        contracts, None


@register("launch.decode_step")
def _decode_step():
    """The continuous batcher's jitted one-token decode: slot (batch)
    separability is what makes slot multiplexing sound; the global cache
    ``pos`` scalar is the one sanctioned slot-reduced output."""
    import jax
    import jax.numpy as jnp
    import repro.configs as C
    from repro.models import transformer as T

    cfg = C.get_reduced("phi3_medium_14b")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    batch = _S
    cache = T.init_cache(cfg, batch, 32)
    tokens = jnp.zeros((batch,), jnp.int32)

    def decode_step(p, c, t):
        return T.decode_step(p, c, t, cfg)

    contracts = [jc.no_collectives(), jc.dtype_discipline(),
                 jc.slot_separable(batch, exempt=("pos",))]
    return decode_step, (params, cache, tokens), contracts, None


# --------------------------------------------------------------------------
# CLI (the CI static-analysis step)
# --------------------------------------------------------------------------

def main(argv: Optional[Sequence[str]] = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis.registry",
        description="run every registered entrypoint contract set")
    ap.add_argument("entries", nargs="*", help="entry names (default: all)")
    ap.add_argument("--list", action="store_true",
                    help="list registered entries and exit")
    args = ap.parse_args(argv)

    if args.list:
        for n in names():
            print(n)
        return 0

    reports = check_all(only=args.entries or None)
    bad = 0
    for name in sorted(reports):
        r = reports[name]
        status = "PASS" if r.ok else "FAIL"
        print(f"{status} {name} ({', '.join(r.contracts)})")
        for v in r.violations:
            bad += 1
            print(f"  {v}")
    s = summary(reports)
    print(f"{len(reports)} entrypoints, {s['contracts']} contracts, "
          f"{s['violations']} violation(s)")
    return 1 if bad else 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
