#!/usr/bin/env python3
"""Sweep the offered load of a cell's traffic to find its knee, the
highest rate it sustains; the cell's traffic file then fixes a rate
below it.  Not part of a benchmark run.

    python bench/sweep.py --workload <cell> --seed <n> --seconds 5 \
        --scale 0.5,0.75,1,1.25

Each scale multiplies the traffic's ``sessions`` or ``rate_per_s``; all
loads run in one process on one engine, one window each.
"""

import argparse
import contextlib
import copy
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import loader  # noqa: E402
import run  # noqa: E402
import system  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--scale", required=True)
    args = ap.parse_args(argv)
    if not system.import_program():
        return run.refuse("the program is missing")
    jax = run.configure_jax()
    import generator
    import numpy as np
    cell = loader.workload(loader.benchmark(run.ROOT), args.workload)
    config = loader.config(cell["config"])
    base = loader.traffic(cell["traffic"])
    engine, _ = system.build(config, args.seed, generator,
                             jax.devices()[:cell["chips"]])
    run._warm(engine, config, base)
    for scale in (float(s) for s in args.scale.split(",")):
        traffic = copy.deepcopy(base)
        if traffic["loop"] == "sessions":
            traffic["sessions"] = int(round(base["sessions"] * scale))
            offered = traffic["sessions"] / run.period_s(config)
        else:
            traffic["arrivals"]["rate_per_s"] *= scale
            offered = traffic["arrivals"]["rate_per_s"]
        inputs = run.inputs_for(config, traffic, args.seed, args.seconds)
        server = run._server(engine, config, traffic, inputs)
        rec = run._drive(engine, server, config, traffic, args.seed,
                         args.seconds, inputs, contextlib.nullcontext)
        lat, late = np.asarray(rec.latency_s), np.asarray(rec.lateness_s)
        print(json.dumps({
            "scale": scale, "offered_per_s": offered,
            "done_per_s": rec.done_in_window / rec.seconds,
            "p50_ms": float(np.percentile(lat, 50) * 1e3),
            "p99_ms": float(np.percentile(lat, 99) * 1e3),
            "late_p99_ms": float(np.percentile(late, 99) * 1e3),
            "unanswered": rec.unanswered}), flush=True)
        engine.taken_sums.clear()
    return 0


if __name__ == "__main__":
    sys.exit(main())
