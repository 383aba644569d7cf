#!/usr/bin/env python3
"""Many runs of one cell in one process: the sound program's seeds and
each control's, at the cell's own size and load, for the readings that
set a limit (PERF.md, "How correct is decided"). The benchmark's own runs
never run this.

    python3 benchmark/controls.py --workload <cell> --seconds <s> \\
        --runs program:<seed>,bf16_wire:<seed>,... [--out <file.jsonl>]

``program`` is the sound program. Each run's result is one JSON line, on
stdout and appended to ``--out``; a control that raises is recorded with
its error (a control that crashes has failed, and sets no upper end).
"""

import argparse
import json
import os
import sys
import traceback

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0] = ROOT


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--runs", required=True)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    from benchmark import harness

    cell = harness.load_cell(args.workload)
    plans = {}
    last = None
    for item in args.runs.split(","):
        control, seed = item.rsplit(":", 1)
        control = "" if control == "program" else control
        if control != last:
            plans.clear()  # one control's programs alive at a time
            last = control
        try:
            r = harness.run(cell, int(seed), args.seconds, False,
                            control=control, plans=plans)
            line = {"control": control or "program", "seed": int(seed),
                    "correct": r["correct"], "steps": r["attempted"],
                    "reference_s": r["reference_s"],
                    "peak_bytes": r["device"]["memory_peak_bytes"],
                    "metrics": r["metrics"], "checks": r["checks"]}
        except harness.NoChip as e:
            print(f"controls.py: {e}", file=sys.stderr)
            return 3
        except Exception as e:  # noqa: BLE001 (a crashing control fails)
            traceback.print_exc()
            line = {"control": control or "program", "seed": int(seed),
                    "error": f"{type(e).__name__}: {e}"[:2000]}
        print(json.dumps(line), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
