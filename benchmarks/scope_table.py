"""A traced run's device time by named scope, by hand:

    python3 benchmarks/scope_table.py <trace dir> [--rest N]

``<trace dir>`` is a ``jax.profiler`` log directory that holds, beside the
profile, the ``scopes.json`` the reader ``readers/scope_device.py`` wrote
there (the program's own maps, ``analytics_zoo_tpu.obs.device_scopes``:
instruction → the ``op_name`` it is charged to).  For every mapped program
whose module ran in the trace, on the first chip and only inside the
module's runs:

- device ms a run by declared scope × pass (``fwd``: no ``transpose(`` in
  the charged ``op_name``, ``bwd``: one), the rest under no scope, and
  their sum against the module's own ms a run;
- inside ``ssd/base``, by flax layer (conv1_1 … fc7) × pass;
- the ten heaviest instructions with the ``op_name`` they are charged to;
- the MIXED SHARE: percent of the module's device seconds in fusions
  whose members stand under more than one declared scope — the error bar
  of every number above (such a fusion is charged whole to one scope);
- with ``--rest N``: the N heaviest instructions under no declared scope,
  by whole HLO line.
"""

import collections
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

LAYER = re.compile(r"ssd/base/(?:\w+/)?(conv\d_\d|fc\d)")


def rows_of(tab, declared):
    """{(scope or None, 'fwd' | 'bwd'): seconds}, {layer: ...} likewise;
    ``declared``: op_name -> its declared scope or None."""
    by_scope = collections.defaultdict(float)
    by_layer = collections.defaultdict(float)
    for name, seconds in tab["by_op"].items():
        op_name = tab["ops"].get(name, "")
        which = "bwd" if "transpose(" in op_name else "fwd"
        by_scope[(declared(op_name), which)] += seconds
        if declared(op_name) == "ssd/base":
            layer = LAYER.search(op_name)
            by_layer[(layer.group(1) if layer else "(pools, relus)",
                      which)] += seconds
    return by_scope, by_layer


def print_program(name, tab, declared, red, rest):
    runs, ms = tab["runs"], 1e3 / tab["runs"]
    total = sum(tab["by_op"].values())
    print(f"== {name}: {runs} runs, {tab['module_s'] * ms:.3f} ms a run "
          f"(operations inside: {total * ms:.3f})")
    by_scope, by_layer = rows_of(tab, declared)
    print(f"{'scope':<18}{'fwd ms':>10}{'bwd ms':>10}{'both':>10}")
    scopes = sorted({s for s, _ in by_scope if s},
                    key=lambda s: -(by_scope[(s, 'fwd')]
                                    + by_scope[(s, 'bwd')]))
    for s in scopes + [None]:
        f, b = by_scope[(s, "fwd")], by_scope[(s, "bwd")]
        print(f"{s or '(no declared scope)':<18}{f * ms:10.3f}"
              f"{b * ms:10.3f}{(f + b) * ms:10.3f}")
    covered = total - by_scope[(None, "fwd")] - by_scope[(None, "bwd")]
    print(f"coverage {100 * covered / total:.2f} %")
    if by_layer:
        print(f"{'layer (ssd/base)':<18}{'fwd ms':>10}{'bwd ms':>10}")
        for layer in sorted({k for k, _ in by_layer}):
            print(f"{layer:<18}{by_layer[(layer, 'fwd')] * ms:10.3f}"
                  f"{by_layer[(layer, 'bwd')] * ms:10.3f}")
    print("heaviest instructions (ms a run, charged op_name):")
    top = sorted(tab["by_op"].items(), key=lambda kv: -kv[1])
    for op, seconds in top[:10]:
        print(f"{seconds * ms:9.3f}  {op:<34} "
              f"{tab['ops'].get(op, '(not in the text)')}"
              f"{'  MIXED ' + '+'.join(tab['mixed'][op]) if op in tab['mixed'] else ''}")
    mixed = sum(s for op, s in tab["by_op"].items() if op in tab["mixed"])
    print(f"mixed share {100 * mixed / total:.2f} % "
          f"({len([o for o in tab['by_op'] if o in tab['mixed']])} fusions)")
    if rest:
        from benchmarks.trace_reduce import short_name

        lines = {short_name(n): n for n, _, _ in red.devices[0].ops}
        loose = [(op, s) for op, s in top
                 if not declared(tab["ops"].get(op, ""))]
        print(f"under no declared scope ({len(loose)} instructions):")
        for op, seconds in loose[:rest]:
            print(f"{seconds * ms:9.3f}  {lines.get(op, op)[:200]}")


def main(argv) -> int:
    from benchmarks import trace_reduce
    from benchmarks.readers import scope_device

    path = argv[1]
    rest = int(argv[argv.index("--rest") + 1]) if "--rest" in argv else 0
    with open(os.path.join(path, "scopes.json")) as f:
        maps = json.load(f)
    red = trace_reduce.reduce_file(trace_reduce.find_xplane(path))
    declared = scope_device.registry().declared_scope
    for name, mapped in maps.items():
        runs, module_s, by_op = scope_device.module_ops(red, mapped["module"])
        if not runs:
            continue
        tab = dict(mapped, runs=runs, module_s=module_s, by_op=by_op)
        print_program(f"{name} ({mapped['module']})", tab, declared, red,
                      rest)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
