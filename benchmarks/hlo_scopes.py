"""Which operations of a compiled program stand under which
``jax.named_scope``: the device trace names an operation by its HLO line
WITHOUT the metadata (looked at on a v5e trace, PR 28: ``%fusion.31 =
bf16[131072,640] fusion(...)``, no ``op_name``), so a scope's device
seconds cannot be read off the trace alone.  The compiled program's text
has the metadata: every instruction's ``op_name`` holds the scopes it was
traced under (a fusion carries its root's).  ``scope_map`` reads the
instruction names a scope, and ``scope_seconds`` sums the trace's
operations of those names."""

from __future__ import annotations

import re
from typing import Dict, Iterable, List, Tuple

_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*metadata=\{[^}]*op_name="([^"]*)"')


def scope_map(hlo_text: str, scope_pattern: str = r"(lm/[a-z_]+)"
              ) -> Dict[str, List[str]]:
    """{scope: [instruction names]} — the first match of ``scope_pattern``
    in an instruction's ``op_name`` is its scope."""
    rx = re.compile(scope_pattern)
    out: Dict[str, List[str]] = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        hit = rx.search(m.group(2))
        if hit:
            out.setdefault(hit.group(1), []).append(m.group(1))
    return out


def scope_seconds(ops: Iterable[Tuple[str, float, float]],
                  names: Iterable[str]) -> Tuple[float, int]:
    """(device seconds, events) of the trace's operations (name, start,
    seconds) whose short name is one of ``names``."""
    names = set(names)
    hits = [d for n, _, d in ops
            if n.split(" = ", 1)[0].lstrip("%") in names]
    return sum(hits), len(hits)
