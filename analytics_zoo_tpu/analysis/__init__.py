"""az-analyze: the two-engine static invariant checker.

Eight PRs of hard-won invariants — one placement site, one injected
clock, seeded-RNG-only determinism, donated step buffers, no host work
inside jitted hot paths, a complete error classification — were enforced by
one grep test, convention, and reviewer memory.  This package turns
them into machine-checked rules (Clockwork's thesis restated for a
codebase: predictable systems come from *consolidating choice* and
removing nondeterminism by construction):

- **source engine** (:mod:`analysis.source`) — AST rules over the
  package source.  No file is imported or executed; a rule sees the
  parse tree, the import-alias table, and the raw lines.  Exceptions
  are declared in-source with ``# az-allow: <rule> — <reason>`` —
  visible, reasoned, and counted, never silent (:mod:`analysis.base`).
- **program engine** (:mod:`analysis.program`) — every registered
  pipeline's jitted train/eval program and the SSD/DS2 serving tiers
  are traced to jaxprs (:mod:`analysis.targets`; abstract
  ``eval_shape`` init, so the audit costs tracing, not FLOPs) and
  audited: no host callbacks in hot programs, donation materialized
  for the ``TrainState`` pytree, no float64 leaks, and the collective
  inventory confined to the mesh axes the pipeline's ``SpecSet``
  declares.

``tools/az_analyze.py --all`` runs both engines and exits non-zero on
any un-waived violation; ``tests/test_analyze.py`` wires it into
tier-1.  Rule catalog and waiver syntax: ``docs/ANALYSIS.md``.
"""

from analytics_zoo_tpu.analysis.base import (
    Violation,
    Waiver,
    apply_waivers,
    format_violation,
    parse_waivers,
)
from analytics_zoo_tpu.analysis.source import (
    SOURCE_RULES,
    default_rules,
    run_source_engine,
)

__all__ = [
    "Violation",
    "Waiver",
    "apply_waivers",
    "format_violation",
    "parse_waivers",
    "SOURCE_RULES",
    "default_rules",
    "run_source_engine",
]
