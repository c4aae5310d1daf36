#!/usr/bin/env python3
"""The control of a cell's comparison, on the chip at the cell's own size.

    python3 benchmark/control.py --workload <cell> --seed <n> --seconds <s> [--trace 1]

Runs the cell as run.py does (a short window at the cell's own load is
enough), then puts the plain reference computed in bfloat16 - every rollup
output rounded before anything sums or ranks it, what the MXU's default
precision does to the group sum's operands - in the program's place, on the
same sampled answers.  Prints the program's numbers and the control's; the
control has to read NOT correct.  A run of the benchmark never runs this.
"""

from __future__ import annotations

import argparse
import json
import sys

import run
from reference import to_bfloat16


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    result, _ = run.on_the_chip(args.workload, args.seed, args.seconds,
                                bool(args.trace), control=to_bfloat16)
    control = result["control"]
    out = {"workload": args.workload, "seed": args.seed,
           "program_correct": result["correct"],
           "program_rel_err": result["checks"]["rel_err"]["value"],
           "control_correct": control["correct"],
           "control_rel_err": control["rel_err"],
           "limit": result["checks"]["rel_err"]["limit"],
           "answers": control["answers"], "run": result}
    print(json.dumps(out), flush=True)
    return 0 if result["correct"] and not out["control_correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
