"""Which instructions of a compiled hot-path program stand under which
``jax.named_scope`` — handed over by the program itself.

A profile of the device names an operation by its HLO line alone
(``%fusion.617 = (bf16[64]...) fusion(...)``): the ``XLA Ops`` events of a
TPU v5e trace carry no ``op_name``, neither in their name (looked at by
hand, PR 28) nor as a stat (PR 37, ``ProfileData``: an event of that line
has ``device_offset_ps``, ``device_duration_ps`` and ``Time Scale
Multiplier``; ``Async XLA Ops`` add ``hlo_op``, ``flow`` and ``id``, the
modules' ``run_id`` and the like — no ``tf_op``, no ``long_name``:
PERF.md §7).  The scopes are in the compiled program's text, as each
instruction's ``op_name``.  So the program registers, where it builds or
warms a step or serve program, HOW that program can be compiled again
from shapes, and whoever holds a trace asks for the map afterwards:

- :func:`register_program` ``(name, thunk)`` — a dict store.  ``thunk()``
  → ``(jitted, args[, static_argnums])``, the
  ``ServingTier.device_program`` contract, is called only when a map is
  asked for: registering compiles nothing, and a run nobody traces never
  calls a thunk.  The Optimizer registers ``train/step`` (and
  ``train/step_scalar``), a ``ServingRuntime`` one
  ``serve/<model>/<tier>/<edge>`` for every geometry of its plan whose
  tier has a ``device_program``.  ``args`` are ``ShapeDtypeStruct``\\ s
  (:func:`abstract`): nothing live is held for the train state, and a
  runtime is held weakly.
- :func:`program_scopes` ``(name)`` → ``{"module", "ops", "mixed"}`` from
  ``jitted.lower(*args).compile().as_text()`` — the program the trace
  ran, if function, shapes, dtypes, static arguments and shardings are
  the dispatched ones (the process then hands back the executable it
  already holds: no second compile) — memoized.  ``ops``: instruction name → the ``op_name`` it is
  CHARGED to; ``mixed``: the fusions whose members stand under more than
  one declared scope (:data:`~analytics_zoo_tpu.obs.names.SCOPES`), with
  those scopes.
- :func:`dump_program_scopes` ``(path)`` — every map asked for so far as
  one JSON, so a trace can be read again without the process that made
  it (``benchmarks/scope_table.py``).
- :func:`registered` — the names, for tests and docs.

**The charging rule.**  A fusion whose fused computation (nested fusions
included) holds a ``convolution`` or a ``dot`` is charged to THAT
instruction's ``op_name`` — the heaviest by output elements if several;
any other fusion, and every unfused instruction, to its own.  XLA fuses a
convolution's kernel gradient with the optimizer's update of that kernel:
by its root such a fusion is ``train/update``'s, by its work it is the
model's backward pass.  An instruction WITHOUT an ``op_name`` — the
compiler's own: a scatter expanded into a sort and a custom fusion, a copy
into fast memory ahead of its use — is charged to the nearest instruction
of its computation that uses its result and has one, or failing that to
the nearest that produces its operands (:func:`_inherit`).
"""

from __future__ import annotations

import json
import re
import sys
import weakref
from typing import (Any, Callable, Dict, List, NamedTuple, Optional, Set,
                    Tuple)

from analytics_zoo_tpu.obs.names import SCOPES

_PROGRAMS: Dict[str, Callable[[], tuple]] = {}
_MAPS: Dict[str, Optional[Dict[str, Any]]] = {}


def register_program(name: str, thunk: Callable[[], tuple]) -> None:
    """Note how the program ``name`` can be compiled again from shapes.
    A second registration under one name replaces the first (and its
    map): the newest Optimizer or runtime is the one a trace ran."""
    _PROGRAMS[name] = thunk
    _MAPS.pop(name, None)


def registered() -> List[str]:
    return sorted(_PROGRAMS)


def abstract(tree: Any) -> Any:
    """``tree`` with every array leaf as a ``ShapeDtypeStruct`` of its
    shape, dtype, weak type and — for a ``jax.Array`` — sharding; other
    leaves (Python scalars) as they are."""
    import jax

    def leaf(x):
        if isinstance(x, jax.Array):
            return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                        sharding=x.sharding,
                                        weak_type=x.weak_type)
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            return jax.ShapeDtypeStruct(x.shape, x.dtype)
        return x

    return jax.tree_util.tree_map(leaf, tree)


def weak_thunk(owner: Any, build: Callable[[Any], tuple]
               ) -> Callable[[], tuple]:
    """A thunk that holds ``owner`` weakly and raises ``LookupError``
    once it is gone: a registry entry must not keep a runtime's weights
    on the device after the runtime was dropped."""
    ref = weakref.ref(owner)

    def thunk():
        live = ref()
        if live is None:
            raise LookupError("the program's owner was dropped")
        return build(live)

    return thunk


def program_scopes(name: str) -> Optional[Dict[str, Any]]:
    """The map of the registered program ``name`` (see the module's
    docstring), or ``None`` where nothing is registered under the name,
    its owner is gone, its shapes were never noted (no step was
    dispatched) or what the thunk returns cannot be lowered."""
    if name in _MAPS:
        return _MAPS[name]
    thunk = _PROGRAMS.get(name)
    if thunk is None:
        return None
    try:
        jitted, args = thunk()[:2]
    except LookupError:
        return None
    if not hasattr(jitted, "lower"):
        return None
    _MAPS[name] = parse_hlo_scopes(_compiled_text(jitted.lower(*args)))
    return _MAPS[name]


def _declared_in(text: str) -> Set[str]:
    """The declared scopes that stand anywhere in a program's text."""
    return {s for s in SCOPES if f"/{s}/" in text or f"({s})" in text}


def _compiled_text(lowered) -> str:
    """The compiled program's text WITH THIS SOURCE'S METADATA.  JAX's
    persistent cache leaves metadata out of its key: an executable that
    another revision of the source put there (same program, other scopes)
    is a hit, and its text names that revision's scopes.  So the scopes of
    the lowering — always this source's — are looked for in the text, and
    where one is missing the program is compiled once more under a key
    that holds the metadata (which the next such look then hits)."""
    import jax

    text = lowered.compile().as_text()
    missing = _declared_in(lowered.as_text(debug_info=True)) \
        - _declared_in(text)
    if not missing:
        return text
    print(f"device_scopes: the compiled program names no {sorted(missing)} "
          f"(an executable of another revision out of the compile cache?)"
          f": compiling again with this source's metadata",
          file=sys.stderr, flush=True)
    flag = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, flag)
    jax.config.update(flag, True)
    try:
        # a dump option changes no program and is left out of the cache's
        # key, but a compile with options is never served from memory
        return lowered.compile(
            compiler_options={"xla_dump_hlo_module_re": "-"}).as_text()
    finally:
        jax.config.update(flag, before)


def dump_program_scopes(path: str) -> None:
    """Every map asked for so far, ``{registered name: map}``, as JSON."""
    with open(path, "w") as f:
        json.dump({k: v for k, v in _MAPS.items() if v is not None}, f)


# -- the compiled program's text ---------------------------------------------

_HEADER = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+) = (.*)$")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_SHAPE = re.compile(r"\w+\[([\d,]*)\]")
_OPERAND = re.compile(r"%([\w.\-]+)")
_SCOPE = re.compile(
    r"(?:^|[/(])(" + "|".join(sorted(map(re.escape, SCOPES), key=len,
                                     reverse=True)) + r")(?=$|[/)])")


def declared_scope(op_name: str) -> Optional[str]:
    """The first name of :data:`SCOPES` that ``op_name`` stands under."""
    hit = _SCOPE.search(op_name)
    return hit.group(1) if hit else None


def _type_and_opcode(rest: str) -> Tuple[str, str]:
    """``f32[8]{0} fusion(%a), kind=...`` → (``f32[8]{0}``, ``fusion``);
    a tuple type is cut at its closing parenthesis."""
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        type_, tail = rest[:i + 1], rest[i + 1:].lstrip()
    else:
        type_, _, tail = rest.partition(" ")
    return type_, tail.split("(", 1)[0].strip()


def _elements(type_: str) -> int:
    """Elements of the largest array in an HLO type."""
    best = 0
    for dims in _SHAPE.findall(type_):
        n = 1
        for d in dims.split(","):
            n *= int(d) if d else 1
        best = max(best, n)
    return best


def _operands(tail: str) -> List[str]:
    """``fusion(%a, /*index=1*/%b), kind=...`` → [``a``, ``b``]."""
    inside = tail.split("(", 1)[1].split(")", 1)[0] if "(" in tail else ""
    return _OPERAND.findall(inside)


class _Row(NamedTuple):
    """One instruction of a computation's text."""

    name: str
    opcode: str
    elements: int               # of its largest output array
    op_name: str                # "" where the text gives none
    calls: Optional[str]        # a fusion's fused computation
    operands: List[str]


def parse_hlo_scopes(text: str) -> Dict[str, Any]:
    """``{"module", "ops", "mixed"}`` of one compiled program's text (the
    module's docstring has the charging rule).  ``ops`` holds every
    instruction an event of a trace can be named after: those of the
    entry computation and of every computation that is no fusion's."""
    first = text.split("\n", 1)[0]
    module = first.split(",", 1)[0].replace("HloModule", "").strip()
    comps: Dict[str, List[_Row]] = {}
    current: Optional[List[_Row]] = None
    for line in text.splitlines():
        if current is None:
            m = _HEADER.match(line)
            if m and not line.startswith((" ", "HloModule")):
                current = comps.setdefault(m.group(1), [])
            continue
        if line.startswith("}"):
            current = None
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        head = m.group(2).split(", metadata=", 1)[0]
        type_, opcode = _type_and_opcode(head)
        op = _OP_NAME.search(line)
        calls = _CALLS.search(head) if opcode == "fusion" else None
        current.append(_Row(m.group(1), opcode, _elements(type_),
                            op.group(1) if op else "",
                            calls.group(1) if calls else None,
                            _operands(head[len(type_):])))
    fused = {r.calls for rows in comps.values() for r in rows if r.calls}

    def members(comp: str, seen=()) -> List[_Row]:
        """Every instruction of a fused computation, nested ones too."""
        out = []
        for row in comps.get(comp, ()):
            out.append(row)
            if row.calls and row.calls not in seen:
                out.extend(members(row.calls, seen + (comp,)))
        return out

    ops: Dict[str, str] = {}
    mixed: Dict[str, List[str]] = {}
    for comp, rows in comps.items():
        if comp in fused:
            continue
        for row in rows:
            op_name = row.op_name
            if row.calls:
                inside = members(row.calls)
                heavy = [r for r in inside
                         if r.opcode in ("convolution", "dot") and r.op_name]
                if heavy:
                    op_name = max(heavy, key=lambda r: r.elements).op_name
                scopes = sorted({s for s in (declared_scope(r.op_name)
                                             for r in inside) if s})
                if len(scopes) > 1:
                    mixed[row.name] = scopes
            ops[row.name] = op_name
        _inherit(rows, ops)
    return {"module": module, "ops": ops, "mixed": mixed}


def _inherit(rows: List[_Row], ops: Dict[str, str], reach: int = 6) -> None:
    """The compiler's own instructions carry no ``op_name`` (a scatter
    expanded into a sort and a custom fusion, a copy into fast memory
    ahead of its use, a change of layout): each is charged to the nearest
    instruction of its computation that USES its result and has one —
    the last piece of an expansion keeps the original's name — or,
    failing that, to the nearest that produces its operands."""
    operands = {row.name: row.operands for row in rows}
    users: Dict[str, List[str]] = {}
    for name, args in operands.items():
        for a in args:
            users.setdefault(a, []).append(name)
    found: Dict[str, str] = {}
    for name in operands:
        if ops[name]:
            continue
        for graph in (users, operands):
            ring, seen, named = [name], {name}, []
            for _ in range(reach):
                ring = [n for r in ring for n in graph.get(r, ())
                        if n in operands and n not in seen]
                seen.update(ring)
                named = [ops[n] for n in ring if ops[n]]
                if named or not ring:
                    break
            if named:
                found[name] = named[0]
                break
    ops.update(found)
