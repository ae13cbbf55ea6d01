#!/usr/bin/env python3
"""The control of the comparison: the program in the precision just below
the configuration's, which has to come out as not correct.

    python3 benchmarks/tpu/control.py --workload <cell> --seconds <s> --seeds <n> <n> ...

The configurations state float64 measures; the engine has a float32 path
of its own (``CONFIG.float_dtype = "float32"``), so each seed is one
ordinary run of the cell with that path switched on, all in this one
process.  Prints one line per seed with its checks, then a summary line
with the smallest ``max_rel_err`` read: the upper reading of that
limit.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    readings, verdicts = [], []
    for seed in args.seeds:
        try:
            res = run.run(args.workload, seed, args.seconds, False,
                          engine={"float_dtype": "float32"})
        except run.NoChip as e:
            run.log(f"[device] {e}")
            return 3
        gap = res["checks"]["max_rel_err"]["value"]
        readings.append(gap)
        verdicts.append(res["correct"])
        print(json.dumps({"seed": seed, "correct": res["correct"], "checks": res["checks"],
                          "device": res["device"]}), flush=True)
    print(json.dumps({"workload": args.workload, "control": "float32",
                      "min_max_rel_err": min(readings),
                      "all_not_correct": not any(verdicts)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
