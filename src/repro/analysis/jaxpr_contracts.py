"""Static jaxpr contract analyzer: named invariants checked on traced code.

The repo's headline claims are protected by *structural* properties of the
compiled hot path, not by any particular run passing: the serving chunk
step must stay free of cross-device collectives (that is what makes the
slot-axis ``shard_map`` bit-identical to one device), the compact layout
must never materialize a dense ``[L, Kmax, N]`` mask or ``[S, L, Kmax, N]``
delta tensor (the 3.8x memory claim), ``want_factors=False`` must compile
the DSST factor accumulators out of the chunk scan entirely, and every
per-stream quantity must keep its slot axis end to end (slot separability).

Each of those used to live as a one-off assert somewhere — a hand-rolled
jaxpr walker in one test file, a trace-time shape assert in the engine, an
indirect 8-device parity check. This module makes them first-class:

* :func:`check` traces a callable once (``jax.make_jaxpr``), walks the
  resulting ``ClosedJaxpr`` — recursing into ``scan`` / ``while`` /
  ``cond`` / ``pjit`` / ``shard_map`` sub-jaxprs — and evaluates a list of
  named :class:`Contract` objects against it, returning a :class:`Report`.
* Contract factories (:func:`no_collectives`, :func:`slot_separable`,
  :func:`mask_free`, :func:`no_dense_deltas`, :func:`no_factor_carries`,
  :func:`dtype_discipline`, :func:`compile_count`) build the repo's
  standard contracts; ``repro.analysis.registry`` binds contract *sets* to
  the real entrypoints and is run by CI's static-analysis step.

Everything here is static — ``check`` never executes the target on real
data (the one exception is the explicitly *dynamic* :func:`compile_count`
contract, which drives the entrypoint to observe its trace counter).
The trace-time tree assert the engine calls from inside ``scan_chunk``
(:func:`assert_chunk_carry_slot_separable`) lives here too, so the engine
and the analyzer enforce one definition of slot separability.
"""
from __future__ import annotations

import dataclasses
from collections import Counter as _Counter
from typing import (Any, Callable, Dict, Iterator, List, Optional, Sequence,
                    Tuple)

import jax
from jax import tree_util as jtu

# Cross-device communication primitives. Any of these inside the serving
# chunk step would make the slot-axis shard_map results depend on the
# device count — the exact failure mode the zero-collectives contract
# forbids. Names are matched after stripping a trailing version digit
# (``psum2`` -> ``psum``) and the variant affixes jax adds under
# ``shard_map``'s varying-manual-axes typing (``psum_invariant``,
# ``all_gather_reduced``, ``unreduced_psum``, ``ragged_all_to_all``), so
# jax renames don't silently blind the check. ``pbroadcast`` and ``pvary``
# are deliberately absent: they are device-local replication-typing
# no-ops, so flagging them would false-positive on communication-free
# bodies.
COLLECTIVE_PRIMITIVES = frozenset({
    "psum", "pmax", "pmin", "pmean", "ppermute", "pshuffle",
    "all_gather", "all_to_all", "pgather", "reduce_scatter", "psum_scatter",
    "pdot", "psend", "precv",
})
_VARIANT_PREFIXES = ("unreduced_", "ragged_")
_VARIANT_SUFFIXES = ("_invariant", "_reduced")


# --------------------------------------------------------------------------
# jaxpr walking
# --------------------------------------------------------------------------

def _as_jaxpr(obj):
    """Normalize ClosedJaxpr | Jaxpr -> Jaxpr (None if neither)."""
    inner = getattr(obj, "jaxpr", None)
    if inner is not None and hasattr(inner, "eqns"):
        return inner
    if hasattr(obj, "eqns"):
        return obj
    return None


def _sub_jaxprs(eqn) -> Iterator[Any]:
    """Sub-jaxprs hanging off one equation's params (scan/while/cond/pjit/
    shard_map/custom_* — anything that stores a Jaxpr or ClosedJaxpr,
    scalar or in a tuple like ``cond``'s branches)."""
    for val in eqn.params.values():
        for item in (val if isinstance(val, (tuple, list)) else (val,)):
            sub = _as_jaxpr(item)
            if sub is not None:
                yield sub


def iter_jaxprs(jaxpr) -> Iterator[Any]:
    """The jaxpr and every (transitively) nested sub-jaxpr, each once."""
    top = _as_jaxpr(jaxpr)
    stack, seen = [top], set()
    while stack:
        jx = stack.pop()
        if id(jx) in seen:
            continue
        seen.add(id(jx))
        yield jx
        for eqn in jx.eqns:
            stack.extend(_sub_jaxprs(eqn))


def iter_eqns(jaxpr, _path: Tuple[str, ...] = ()) -> Iterator[Tuple[Any, Tuple[str, ...]]]:
    """Yield ``(eqn, path)`` for every equation at any nesting depth;
    ``path`` is the chain of enclosing primitive names (e.g.
    ``("pjit", "shard_map", "scan")``)."""
    top = _as_jaxpr(jaxpr)
    for eqn in top.eqns:
        yield eqn, _path
        inner_path = _path + (eqn.primitive.name,)
        for sub in _sub_jaxprs(eqn):
            yield from iter_eqns(sub, inner_path)


def all_avals(jaxpr) -> Iterator[Tuple[Any, str]]:
    """``(aval, role)`` for every abstract value anywhere in the jaxpr:
    constvars/invars of the jaxpr and each sub-jaxpr, plus every equation's
    in/out vars (literals included via ``.aval``)."""
    for jx in iter_jaxprs(jaxpr):
        for v in jx.constvars:
            yield v.aval, "const"
        for v in jx.invars:
            yield v.aval, "input"
        for eqn in jx.eqns:
            for v in eqn.invars:
                aval = getattr(v, "aval", None)
                if aval is not None:
                    yield aval, "eqn-in"
            for v in eqn.outvars:
                aval = getattr(v, "aval", None)
                if aval is not None:
                    yield aval, "eqn-out"


# --------------------------------------------------------------------------
# report plumbing
# --------------------------------------------------------------------------

class ContractViolationError(AssertionError):
    """Raised by :meth:`Report.raise_if_violations`. An ``AssertionError``
    subclass so callers that wrapped the old ad-hoc asserts keep working."""


@dataclasses.dataclass(frozen=True)
class Violation:
    contract: str
    message: str

    def __str__(self) -> str:
        return f"[{self.contract}] {self.message}"


@dataclasses.dataclass
class Report:
    """Outcome of :func:`check`: which contracts ran, what they found."""
    target: str
    contracts: Tuple[str, ...]
    violations: List[Violation]

    @property
    def ok(self) -> bool:
        return not self.violations

    def raise_if_violations(self) -> "Report":
        if self.violations:
            lines = "\n".join(f"  {v}" for v in self.violations)
            raise ContractViolationError(
                f"{self.target}: {len(self.violations)} contract "
                f"violation(s)\n{lines}")
        return self

    def __str__(self) -> str:
        status = ("OK" if self.ok
                  else f"{len(self.violations)} violation(s)")
        head = f"{self.target}: {status} ({', '.join(self.contracts)})"
        if self.ok:
            return head
        return head + "\n" + "\n".join(f"  {v}" for v in self.violations)


@dataclasses.dataclass(frozen=True)
class Contract:
    """A named check over a traced callable. ``run`` receives the
    :class:`_Ctx` (lazy jaxpr / output-shape access) and returns
    violations; an empty list means the contract holds."""
    name: str
    run: Callable[["_Ctx"], List[Violation]]


class _Ctx:
    """Lazily-traced view of ``(fn, args, kwargs)`` shared by the contracts
    of one ``check`` call: one ``make_jaxpr`` and one ``eval_shape``, no
    matter how many contracts inspect them."""

    def __init__(self, fn, args: tuple, kwargs: dict):
        self.fn, self.args, self.kwargs = fn, args, kwargs
        self._closed = None
        self._out_shape = None

    @property
    def closed_jaxpr(self):
        if self._closed is None:
            self._closed = jax.make_jaxpr(self.fn)(*self.args, **self.kwargs)
        return self._closed

    @property
    def jaxpr(self):
        return self.closed_jaxpr.jaxpr

    @property
    def out_shape(self):
        if self._out_shape is None:
            self._out_shape = jax.eval_shape(self.fn, *self.args,
                                             **self.kwargs)
        return self._out_shape


def check(fn, args: Sequence[Any], contracts: Sequence[Contract], *,
          kwargs: Optional[dict] = None, name: Optional[str] = None) -> Report:
    """Statically verify ``contracts`` against ``fn`` traced on ``args``.

    ``fn`` may be jitted or plain — ``jax.make_jaxpr`` recurses through
    ``pjit`` either way. Returns a :class:`Report`; call
    ``.raise_if_violations()`` to turn findings into a
    :class:`ContractViolationError` (tests) or inspect ``.violations``
    (CI's registry runner).
    """
    ctx = _Ctx(fn, tuple(args), dict(kwargs or {}))
    violations: List[Violation] = []
    for c in contracts:
        violations.extend(c.run(ctx))
    return Report(
        target=name or getattr(fn, "__name__", None) or repr(fn),
        contracts=tuple(c.name for c in contracts),
        violations=violations)


# --------------------------------------------------------------------------
# contract factories
# --------------------------------------------------------------------------

def _base_prim_name(name: str) -> str:
    name = name.rstrip("0123456789")
    for pre in _VARIANT_PREFIXES:
        if name.startswith(pre):
            name = name[len(pre):]
    for suf in _VARIANT_SUFFIXES:
        if name.endswith(suf):
            name = name[:-len(suf)]
    return name


def no_collectives(axis: Optional[str] = None) -> Contract:
    """No cross-device collective primitive anywhere in the jaxpr
    (recursively — in particular not inside a slot-axis ``shard_map``).
    With ``axis`` given, only collectives touching that named axis count;
    default flags any collective at any depth."""
    def run(ctx: _Ctx) -> List[Violation]:
        out = []
        for eqn, path in iter_eqns(ctx.jaxpr):
            nm = eqn.primitive.name
            if (nm not in COLLECTIVE_PRIMITIVES
                    and _base_prim_name(nm) not in COLLECTIVE_PRIMITIVES):
                continue
            axes = (eqn.params.get("axes") or eqn.params.get("axis_name")
                    or eqn.params.get("axis_index_groups") or ())
            if isinstance(axes, (str, int)):
                axes = (axes,)
            axes = tuple(axes)
            if axis is not None and axes and axis not in axes:
                continue
            where = " > ".join(path) if path else "<top level>"
            out.append(Violation(
                "no_collectives",
                f"collective `{nm}` over axes {axes} under {where} — the "
                f"slot-sharded step must be communication-free"))
        return out
    return Contract("no_collectives", run)


def slot_separable(n_slots: int, *, exempt: Sequence[str] = ()) -> Contract:
    """Every output leaf keeps an axis of extent ``n_slots`` within its
    first two dims — the static half of the slot-separability contract
    (the dynamic half is the engine's trace-time carry assert, which
    wraps :func:`assert_chunk_carry_slot_separable` below). A reduction
    or reshape that drops the slot axis shows up here as an output whose
    leading dims no longer carry ``n_slots``.

    ``exempt``: keystr substrings for deliberately slot-reduced outputs
    (e.g. the serving chunk fn's ordered-slot-summed ``pre_mag`` /
    ``post_mag`` DSST factors, or a decode cache's global ``pos`` scalar).
    Pick ``n_slots`` distinct from the other leading extents (chunk len,
    layer count) or the check degrades to vacuously true.
    """
    def run(ctx: _Ctx) -> List[Violation]:
        out = []
        leaves, _ = jtu.tree_flatten_with_path(ctx.out_shape)
        for path, leaf in leaves:
            key = jtu.keystr(path) or "<result>"
            if any(e in key for e in exempt):
                continue
            shape = tuple(getattr(leaf, "shape", ()))
            if n_slots not in shape[:2]:
                out.append(Violation(
                    "slot_separable",
                    f"output {key} shape {shape} lost the slot axis "
                    f"(extent {n_slots} not within the first two dims)"))
        return out
    return Contract("slot_separable", run)


_DTYPE_SHORT = {"float32": "f32", "float64": "f64", "float16": "f16",
                "bfloat16": "bf16", "int32": "i32", "int64": "i64",
                "bool": "pred"}


def no_dense_leaves(shapes: Sequence[Sequence[int]], *,
                    dtypes: Sequence[str] = ("float32",),
                    contract_name: str = "no_dense_leaves") -> Contract:
    """No aval of any forbidden ``(shape, dtype)`` anywhere in the jaxpr —
    not a constvar, not an input, not an intermediate. Belt and braces: the
    traversal is cross-checked against the printed jaxpr text, so a const
    hiding in a sub-jaxpr a future jax version stops exposing still trips
    the string scan."""
    forbidden = {tuple(int(d) for d in s) for s in shapes}
    want_dtypes = tuple(dtypes)

    def run(ctx: _Ctx) -> List[Violation]:
        out, seen = [], set()
        for aval, role in all_avals(ctx.jaxpr):
            shape = tuple(getattr(aval, "shape", ()))
            dt = str(getattr(aval, "dtype", ""))
            if shape in forbidden and dt in want_dtypes:
                key = (role, dt, shape)
                if key not in seen:
                    seen.add(key)
                    out.append(Violation(
                        contract_name,
                        f"{role} aval {dt}{list(shape)} — dense layout "
                        f"leaked into the compact hot path"))
        flagged = {k[2] for k in seen}
        txt = str(ctx.closed_jaxpr)
        for shape in forbidden - flagged:
            for dt in want_dtypes:
                pat = f"{_DTYPE_SHORT.get(dt, dt)}[{','.join(map(str, shape))}]"
                if pat in txt:
                    out.append(Violation(
                        contract_name,
                        f"printed jaxpr contains `{pat}` (missed by the "
                        f"traversal — report this walker gap)"))
        return out
    return Contract(contract_name, run)


def mask_free(cfg) -> Contract:
    """Compact serving never materializes the dense connection mask
    ``[L, Kmax, N]`` (cfg needs ``n_layers`` / ``n_hidden`` /
    ``layer_fanins`` — ``core.snn.SNNConfig`` shaped, but duck-typed)."""
    k_max = max(cfg.layer_fanins)
    return no_dense_leaves([(cfg.n_layers, k_max, cfg.n_hidden)],
                           contract_name="mask_free")


def no_dense_deltas(cfg, n_slots: int) -> Contract:
    """Compact serving never materializes the dense per-stream delta tensor
    — neither slot-leading ``[S, L, Kmax, N]`` (public layout) nor
    layer-leading ``[L, S, Kmax, N]`` (engine layout)."""
    k_max = max(cfg.layer_fanins)
    return no_dense_leaves(
        [(n_slots, cfg.n_layers, k_max, cfg.n_hidden),
         (cfg.n_layers, n_slots, k_max, cfg.n_hidden)],
        contract_name="no_dense_deltas")


def no_factor_carries(cfg, n_slots: int, *, chunk_len: Optional[int] = None,
                      max_state_carries: int = 4) -> Contract:
    """With ``want_factors=False`` the DSST ``pre_mag`` / ``post_mag``
    accumulators are compiled OUT of the chunk scan — not zeroed, absent.

    The engine's time scan legitimately carries exactly
    ``max_state_carries`` ``[L, S, n_hidden]`` f32 arrays (the
    ``LayerState`` leaves: v, tr, tr_pc, tr_cc); the factor accumulators
    would add a ``[L, S, k_max]`` and one more ``[L, S, n_hidden]`` on
    top. Works for uniform geometries (``k_max == n_hidden``) where a pure
    shape check cannot distinguish state from accumulator — the *count*
    can. ``chunk_len`` narrows the check to the scan of that length (the
    time scan); None checks every scan. ``n_slots`` is the per-shard slot
    count — under a sharded mesh pass ``S // n_devices``.
    """
    L, N = cfg.n_layers, cfg.n_hidden
    k_max = max(cfg.layer_fanins)
    allowed: Dict[Tuple[int, ...], int] = {(L, n_slots, N): max_state_carries}
    if k_max != N:
        allowed[(L, n_slots, k_max)] = 0

    def run(ctx: _Ctx) -> List[Violation]:
        out = []
        for eqn, _path in iter_eqns(ctx.jaxpr):
            if eqn.primitive.name != "scan":
                continue
            if chunk_len is not None and eqn.params.get("length") != chunk_len:
                continue
            lo = eqn.params["num_consts"]
            carries = [v.aval for v in
                       eqn.invars[lo:lo + eqn.params["num_carry"]]]
            got = _Counter(tuple(a.shape) for a in carries
                           if str(getattr(a, "dtype", "")) == "float32")
            for shape, max_n in allowed.items():
                if got.get(shape, 0) > max_n:
                    out.append(Violation(
                        "no_factor_carries",
                        f"scan(length={eqn.params.get('length')}) carries "
                        f"{got[shape]} f32 arrays of shape {list(shape)} "
                        f"(expected <= {max_n} LayerState leaves) — the "
                        f"DSST factor accumulators were not compiled out"))
        return out
    return Contract("no_factor_carries", run)


def dtype_discipline(forbid: Sequence[str] = ("float64", "complex128")
                     ) -> Contract:
    """No silently-promoted wide dtype anywhere in the jaxpr. The repo runs
    with x64 disabled, so an f64 aval means someone re-enabled it or a
    host constant leaked through unconverted."""
    forbid = tuple(forbid)

    def run(ctx: _Ctx) -> List[Violation]:
        out, seen = [], set()
        for aval, role in all_avals(ctx.jaxpr):
            dt = str(getattr(aval, "dtype", ""))
            if dt in forbid:
                key = (dt, tuple(getattr(aval, "shape", ())))
                if key not in seen:
                    seen.add(key)
                    out.append(Violation(
                        "dtype_discipline",
                        f"{role} aval {dt}{list(key[1])} — silent wide-"
                        f"dtype promotion"))
        return out
    return Contract("dtype_discipline", run)


def compile_count(max_traces: int = 1, runs: int = 2) -> Contract:
    """DYNAMIC contract: the entrypoint traces at most ``max_traces`` times
    across ``runs`` identical calls — the "compile once, stream forever"
    guarantee (``adapt.make_chunk_fn``'s public ``n_traces()`` counter is
    the hook; a target without one fails the contract explicitly rather
    than passing vacuously). The only contract that executes the target."""
    def run(ctx: _Ctx) -> List[Violation]:
        counter = getattr(ctx.fn, "n_traces", None)
        if counter is None:
            return [Violation(
                "compile_count",
                "target exposes no n_traces() trace counter — cannot "
                "verify the single-compilation guarantee")]
        before = counter()
        for _ in range(runs):
            ctx.fn(*ctx.args, **ctx.kwargs)
        grew = counter() - before
        if grew > max_traces:
            return [Violation(
                "compile_count",
                f"entrypoint traced {grew}x across {runs} identical calls "
                f"(max {max_traces}) — it is retracing inside the hot "
                f"loop")]
        return []
    return Contract("compile_count", run)


# --------------------------------------------------------------------------
# the engine's trace-time tree assert (shared definition)
# --------------------------------------------------------------------------

def assert_chunk_carry_slot_separable(carry, outs, *, C: int, S: int,
                                      n_layers: int,
                                      want_factors: bool) -> None:
    """The chunk step's zero-collective contract, checked on the concrete
    scan carry/output trees at trace time: every per-stream quantity keeps
    its slot axis through the scan. A reduction over slots — which would
    silently break the slot-axis ``shard_map`` in serving/adapt.py — shows
    up as a dropped ``S`` dimension here. ``engine._assert_slot_separable``
    is a thin wrapper over this (same error shape: a bare ``assert`` whose
    message is the offending shape), and the static
    :func:`slot_separable` contract checks the same property on jaxpr
    output avals without running the trace."""
    layers, x_tr, ss_mean, t_w, samp, dls, *acc = carry
    for leaf in jtu.tree_leaves(layers):
        assert leaf.shape[:2] == (n_layers, S), leaf.shape
    assert x_tr.shape[0] == S, x_tr.shape
    assert ss_mean.shape == (n_layers, S), ss_mean.shape
    assert t_w.shape == (S,) and samp.shape == (S,), (t_w.shape, samp.shape)
    assert dls.shape[:2] == (n_layers, S), dls.shape
    assert len(acc) == (2 if want_factors else 0), len(acc)
    for a in acc:
        assert a.shape[:2] == (n_layers, S), a.shape
    for name, leaf in outs.items():
        assert leaf.shape[:2] == (C, S), (name, leaf.shape)
