#!/usr/bin/env python3
"""Read the control of a cell's comparison: the plain reference in
bfloat16 put in the program's place, on the requests a run of the cell
answers.  It must read above the limits (``correct`` false).

    python bench/control.py --workload <cell> --seeds 1,2,3 --seconds 10

A classifier's answer depends only on its request, so the control's
answers on the cell's request pool (every pool row; every session
window a run of ``--seconds`` decides) are what it would have served.
Not part of a benchmark run.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import loader  # noqa: E402
import run  # noqa: E402


def keys_for(config, traffic, seconds) -> list:
    if traffic["loop"] == "sessions":
        feeds = int(seconds / run.period_s(config)) + 1
        return [(s, k) for s in range(traffic["sessions"])
                for k in range(feeds)]
    return list(range(traffic["payload"]["pool"]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    import check
    cell = loader.workload(loader.benchmark(run.ROOT), args.workload)
    config = loader.config(cell["config"])
    traffic = loader.traffic(cell["traffic"])
    keys = keys_for(config, traffic, args.seconds)
    for seed in (int(s) for s in args.seeds.split(",")):
        inputs = run.inputs_for(config, traffic, seed, args.seconds)
        numbers = check.control(config, seed, inputs, keys)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "requests": len(keys), "control": numbers}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
