#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a device trace of the same window. Without a TPU,
or with fewer chips than the cell asks for, it exits non-zero and prints
no result. ``--control <name>`` runs one of the configuration's controls
(the lower-precision runs that must come out not correct); the
benchmark's own runs never pass it.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0] = ROOT  # import ``benchmark`` as a package, never its modules bare


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", default="")
    ap.add_argument("--keep-trace", default="",
                    help="copy the traced run's xplane.pb to this path")
    args = ap.parse_args(argv)

    from benchmark import harness

    cell = harness.load_cell(args.workload)
    try:
        result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                             control=args.control, keep_trace=args.keep_trace)
    except harness.NoChip as e:
        print(f"run.py: {e}; nothing measured", file=sys.stderr)
        return 3
    checks = result["checks"]
    print(json.dumps(result), flush=True)
    for k, c in checks.items():  # the numbers compared, last on stderr
        print(f"check {k} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
