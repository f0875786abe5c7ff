"""Lint committed drill artifacts: parse + run-metadata presence.

Every ``*_rNN*.json`` / ``OBS_*.json`` at the repo root is a *banked
execution* some ROADMAP claim leans on.  Two failure modes crept in
before PR 7: artifacts that no tool can regenerate (hand-edited, or the
generating tool moved on), and artifacts that cannot be tied to the
commit/backend that produced them.  This lint closes both, and
``tests/test_tools.py`` runs it in tier-1 so a stale or hand-edited
artifact fails the suite:

- every matching artifact (``PATTERN``) must PARSE as JSON;
- every matching artifact must carry the shared ``run_metadata`` block
  (``analytics_zoo_tpu.obs.run_metadata``: tool, seed, git sha,
  backend, jax version) — EXCEPT the frozen ``LEGACY`` set below,
  generated before the stamping helper existed (most on TPU hardware
  this environment cannot re-run).  The legacy set is closed: adding a
  NEW artifact without metadata fails tier-1.

Usage::

    python tools/check_artifacts.py           # lint the repo root
    python tools/check_artifacts.py --root D  # lint another directory
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
from typing import List

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from analytics_zoo_tpu.obs.runmeta import REQUIRED_KEYS  # noqa: E402

#: artifacts this lint governs: revisioned drill bankings plus every
#: obs artifact
PATTERN = re.compile(r"(^OBS_.*\.json$)|(.*_r\d+.*\.json$)")

#: frozen pre-PR-7 artifacts (no run_metadata block; the TPU-side ones
#: cannot be regenerated from this environment).  CLOSED SET — do not
#: add to it; new artifacts must stamp obs.run_metadata().
LEGACY = frozenset({
    "MULTICHIP_r02.json",
    "MULTICHIP_r03.json",
    "MULTICHIP_r04.json",
    "MULTICHIP_r05.json",
    "RESILIENCE_r01.json",
})


def check_artifacts(root: str) -> List[str]:
    """Lint ``root``; returns a list of problem strings (empty = clean)."""
    problems: List[str] = []
    names = sorted(n for n in os.listdir(root)
                   if PATTERN.match(n)
                   and os.path.isfile(os.path.join(root, n)))
    for name in names:
        path = os.path.join(root, name)
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, ValueError) as e:
            problems.append(f"{name}: does not parse as JSON ({e})")
            continue
        if name in LEGACY:
            continue
        meta = doc.get("run_metadata") if isinstance(doc, dict) else None
        if not isinstance(meta, dict):
            problems.append(
                f"{name}: missing run_metadata block (stamp it with "
                f"analytics_zoo_tpu.obs.run_metadata)")
            continue
        missing = [k for k in REQUIRED_KEYS if k not in meta]
        if missing:
            problems.append(
                f"{name}: run_metadata missing keys {missing}")
    return problems


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--root", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    args = ap.parse_args(argv)
    problems = check_artifacts(args.root)
    n = len([x for x in os.listdir(args.root) if PATTERN.match(x)])
    if problems:
        for p in problems:
            print(f"check_artifacts: FAIL {p}")
        return 1
    print(f"check_artifacts: OK — {n} artifacts parse"
          f" ({len(LEGACY)} legacy grandfathered, the rest stamped)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
