"""Program engine: jaxpr audits of the compiled-program invariants.

Source rules see what the code *says*; this engine checks what the
traced program *is*.  Every audit target (:mod:`analysis.targets`
builds the repo's suite) is traced with ``jax.make_jaxpr`` — tracing
only, no XLA compile, no FLOPs — and the resulting jaxpr is walked
recursively (pjit bodies, scan/while/cond sub-jaxprs, shard_map
bodies, custom_vjp calls) for four properties:

- **no-callbacks-in-hot-program** — ``pure_callback``/``io_callback``/
  ``debug_callback``/``debug_print`` inside a jitted train/eval/serving
  program is a host round-trip per step hiding where no profiler attributes it (and
  pins the program to the host, breaking async dispatch overlap).
- **donation-materialized** — the train step's ``TrainState`` arg must
  actually reach the pjit with every leaf marked donated.  Donation is
  declared at one ``jax.jit(donate_argnums=...)`` site but silently
  voided by wrapper reordering (a wrapper that re-packs the state
  breaks aliasing without an error) — so the audit reads
  ``donated_invars`` off the traced pjit equation itself.
- **no-float64** — an f64 leak (a stray Python float promoted under
  x64, an np.float64 scalar) doubles bandwidth on the exact arrays the
  MFU ceiling analyses assume are f32/bf16, and TPUs emulate f64.
  Scope is honest: with ``jax_enable_x64`` OFF (this repo's every
  config) JAX canonicalizes f64 → f32 at trace time, so no leak can
  exist and the check is vacuous-but-free; it arms the moment a
  process enables x64 (a future double-precision eval config), where
  the audit traces under the same flag and catches real leaks.
  Deliberately NOT forced on for the audit itself: under x64 every
  plain Python float literal traces as weak-f64, which would flag
  every program in the repo.
- **collective-inventory** — every named-axis collective (psum /
  all_gather / ppermute / …) in the program must reference an axis the
  pipeline's declared ``SpecSet`` mesh actually has.  GSPMD-annotated
  programs carry no explicit collectives (XLA inserts them after
  SPMD partitioning), so any named axis that shows up was written by
  hand — and a hand-written axis the declaration doesn't know about is
  exactly the drift the declare-once substrate exists to prevent.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Iterator, List, Optional, Sequence, Set, Tuple

import jax
import numpy as np
from jax.extend.core import ClosedJaxpr, Jaxpr

from analytics_zoo_tpu.analysis.base import Violation

#: host-callback primitives banned from hot programs
CALLBACK_PRIMS = frozenset({"pure_callback", "io_callback",
                            "debug_callback", "debug_print"})

#: named-axis collective primitives whose axes must be declared
COLLECTIVE_PRIMS = frozenset({
    "psum", "psum_invariant", "pmax", "pmin", "pbroadcast", "ppermute",
    "all_gather", "all_gather_invariant", "reduce_scatter",
    "all_to_all", "pgather", "axis_index",
})


@dataclasses.dataclass
class BuiltProgram:
    """One traced-and-audited program.

    ``donate_state``: the pytree passed as argument 0 whose every leaf
    must be donated (``None`` skips the donation check — eval/serving
    programs donate nothing).  ``specs``: the pipeline's declared
    :class:`~analytics_zoo_tpu.parallel.specs.SpecSet`; its mesh axis
    names are the collective-inventory ground truth.  ``hot``: callback
    primitives are violations (every repo program audited today is
    hot)."""

    fn: Callable
    args: Tuple
    static_argnums: Tuple[int, ...] = ()
    specs: Any = None
    donate_state: Any = None
    hot: bool = True


@dataclasses.dataclass(frozen=True)
class AuditProgram:
    """A named, lazily-built audit target: ``build()`` returns the
    :class:`BuiltProgram` (construction is deferred so ``--source``-only
    runs never pay for model construction)."""

    name: str
    build: Callable[[], BuiltProgram]


def _sub_jaxprs(params: dict) -> Iterator[Any]:
    for v in params.values():
        if isinstance(v, (Jaxpr, ClosedJaxpr)):
            yield v
        elif isinstance(v, (tuple, list)):
            for item in v:
                if isinstance(item, (Jaxpr, ClosedJaxpr)):
                    yield item


def iter_eqns(jaxpr) -> Iterator[Any]:
    """Every equation of ``jaxpr`` and (recursively) of every sub-jaxpr
    carried in equation params — pjit bodies, scan/while/cond branches,
    shard_map bodies, custom_jvp/vjp call jaxprs."""
    if isinstance(jaxpr, ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn.params):
            yield from iter_eqns(sub)


def _named_axes(eqn) -> Set[str]:
    axes: Set[str] = set()
    for key in ("axes", "axis_name"):
        v = eqn.params.get(key)
        if v is None:
            continue
        for a in (v if isinstance(v, (tuple, list)) else (v,)):
            if isinstance(a, str):
                axes.add(a)
    return axes


def collective_inventory(jaxpr) -> Set[str]:
    """All named mesh axes referenced by collective primitives anywhere
    in the program."""
    axes: Set[str] = set()
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name in COLLECTIVE_PRIMS:
            axes |= _named_axes(eqn)
    return axes


def _avals(jaxpr) -> Iterator[Any]:
    if isinstance(jaxpr, ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    for v in jaxpr.invars + jaxpr.outvars:
        if hasattr(v, "aval"):
            yield v.aval
    for eqn in iter_eqns(jaxpr):
        for v in list(eqn.invars) + list(eqn.outvars):
            if hasattr(v, "aval"):
                yield v.aval


def audit_program(target: AuditProgram) -> List[Violation]:
    """Trace one target and run every program check against it."""
    where = f"program:{target.name}"
    try:
        built = target.build()
        closed = jax.make_jaxpr(
            built.fn, static_argnums=built.static_argnums)(*built.args)
    except Exception as e:  # a target that cannot trace IS a finding
        return [Violation(
            rule="program-trace-error", file=where, line=0,
            message=f"audit target failed to trace: "
                    f"{type(e).__name__}: {e}")]
    out: List[Violation] = []

    if built.hot:
        seen = set()
        for eqn in iter_eqns(closed):
            name = eqn.primitive.name
            if name in CALLBACK_PRIMS and name not in seen:
                seen.add(name)
                out.append(Violation(
                    rule="no-callbacks-in-hot-program", file=where, line=0,
                    message=f"{name} inside the jitted program — a host "
                            f"round-trip per step; hoist it out of the "
                            f"traced body (obs hooks live host-side)"))

    if built.donate_state is not None:
        n_state = len(jax.tree_util.tree_leaves(built.donate_state))
        # a jitted call traces as one "jit" equation carrying the
        # donation vector
        pjit_eqns = [e for e in closed.jaxpr.eqns
                     if e.primitive.name == "jit"
                     and "donated_invars" in e.params]
        if not pjit_eqns:
            out.append(Violation(
                rule="donation-materialized", file=where, line=0,
                message="no jit equation found at the top level — the "
                        "step is not the single jitted program the "
                        "donation contract assumes"))
        else:
            donated = pjit_eqns[0].params["donated_invars"]
            missing = sum(1 for d in donated[:n_state] if not d)
            if missing:
                out.append(Violation(
                    rule="donation-materialized", file=where, line=0,
                    message=f"{missing}/{n_state} TrainState leaves are "
                            f"NOT donated — the step keeps a second copy "
                            f"of params+optimizer state in HBM (check "
                            f"donate_argnums and wrapper arg order)"))

    f64 = sorted({str(a.dtype) for a in _avals(closed)
                  if getattr(a, "dtype", None) == np.dtype("float64")})
    if f64:
        out.append(Violation(
            rule="no-float64", file=where, line=0,
            message="float64 values inside the program — a leaked "
                    "double (Python float under x64, np.float64 scalar) "
                    "doubles bandwidth and TPUs emulate f64"))

    if built.specs is not None:
        declared = set(built.specs.mesh.axis_names)
        inventory = collective_inventory(closed)
        undeclared = sorted(inventory - declared)
        if undeclared:
            out.append(Violation(
                rule="collective-inventory", file=where, line=0,
                message=f"collectives over axes {undeclared} but the "
                        f"pipeline's SpecSet declares mesh axes "
                        f"{sorted(declared)} — the program communicates "
                        f"over axes the declaration doesn't know about"))
    return out


def run_program_engine(targets: Sequence[AuditProgram]
                       ) -> List[Violation]:
    out: List[Violation] = []
    for t in targets:
        out.extend(audit_program(t))
    return out
