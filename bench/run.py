#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout that holds ``BENCHMARK.json``, this
directory and the program under ``src/``.  The run builds the cell's
model on the device from the seed, warms the batch shapes its traffic
uses (set-up, read from JAX's persistent cache in ``.bench_cache/`` after
the first run), drives the traffic for ``--seconds``, checks every
answer against the plain reference and prints one JSON line last on
standard output: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, with ``--trace 1`` also ``breakdown``, and last ``check``,
each number compared beside its limit (also the last lines of standard
error).  ``--trace 0`` reports the cell's end-to-end metrics, ``--trace
1`` its per-layer ones from a profiler trace of the window.

It refuses to run (exit 2, no result) without a TPU, with fewer chips
than the cell asks for, with a cell whose chips differ from the devices
its configuration's mesh spans, or without the program.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE = os.path.join(ROOT, ".bench_cache")
sys.path.insert(0, HERE)

import loader  # noqa: E402
import system  # noqa: E402

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


def refuse(msg: str) -> int:
    print(f"bench/run.py: {msg}", file=sys.stderr)
    return 2


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        bm = loader.benchmark(ROOT)
        cell = loader.workload(bm, args.workload)
        config = loader.config(cell["config"])
    except (OSError, KeyError) as e:
        return refuse(str(e))
    if system.chips(config) != cell["chips"]:
        return refuse(f"the cell asks for {cell['chips']} chips; its "
                      f"configuration serves from {system.chips(config)}")
    if not system.import_program():
        return refuse(f"the program is not at {system.SRC}")
    devices = configure_jax().devices()
    if devices[0].platform != "tpu":
        return refuse(f"needs a TPU; JAX found {devices[0].platform}")
    if len(devices) < cell["chips"]:
        return refuse(f"the cell needs {cell['chips']} chips; JAX sees "
                      f"{len(devices)}")
    result = run_cell(bm, cell, args.seed, args.seconds, bool(args.trace),
                      devices[:cell["chips"]])
    print(json.dumps(result))
    return 0


def configure_jax():
    """JAX with its persistent compile cache at the checkout's fixed
    ``.bench_cache/jax`` (also handed to the program through the
    environment)."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(CACHE, "jax")
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def run_cell(bm, cell, seed, seconds, traced, devices, *, base=loader.HERE,
             t_start=T_START, tamper=None):
    """One run of ``cell``: the result line as a dict.  ``tamper``, for
    the harness's own tests, receives the built engine and may break
    it."""
    import jax
    import numpy as np

    import check
    import generator
    import tracekit
    import work

    config = loader.config(cell["config"], base)
    traffic = loader.traffic(cell["traffic"], base)
    chips = cell["chips"]
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, _d, **kw: compiles.append(ev) if ev in COMPILE_EVENTS
        else None)

    engine, _ = system.build(config, seed, generator, devices)
    if tamper is not None:
        tamper(engine)
    inputs = inputs_for(config, traffic, seed, seconds)
    server = _server(engine, config, traffic, inputs)
    _warm(engine, config, traffic)
    setup_s = time.monotonic() - t_start

    trace_dir = os.path.join(CACHE, "trace", cell["name"])
    span = contextlib.nullcontext
    if traced:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        span = jax.profiler.TraceAnnotation
    n_compiles = len(compiles)
    rec = _drive(engine, server, config, traffic, seed, seconds, inputs,
                 span)
    in_window = len(compiles) - n_compiles
    if traced:
        t_stop = time.monotonic()
        jax.profiler.stop_trace()
        t_stop = time.monotonic() - t_stop
    peak_bytes = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                     for d in devices)

    summary = None
    if traced:
        t_load = time.monotonic()
        devs, spans, window = tracekit.load(trace_dir)
        t_reduce = time.monotonic()
        summary = tracekit.reduce(devs, spans, window)
        t_reduce, t_load = time.monotonic() - t_reduce, t_reduce - t_load
        shutil.rmtree(trace_dir, ignore_errors=True)
        n_gaps, n_spans = tracekit.counts(devs, spans, window)
        print(f"trace stop {t_stop} s load {t_load} s reduce {t_reduce} s: "
              f"{n_gaps} gaps x {n_spans} spans")
    t_end = rec.t0 + rec.seconds
    run = types.SimpleNamespace(
        rec=rec, setup_s=setup_s, trace=summary, chips=chips,
        dispatches=[d for d in engine.dispatches if rec.t0 <= d[0] <= t_end],
        shape=work.Shape.of(config), peak=work.peak(devices[0].device_kind))
    metrics = {}
    for m in loader.metrics_for(bm, cell["name"], traced):
        value = loader.metric_reader(m["name"], base)(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    answers = engine.taken_sums
    del engine, server
    gc.collect()
    t_check = time.monotonic()
    numbers = check.compare(config, seed, inputs, answers, rec.rid_key,
                            rec.unanswered)
    t_check = time.monotonic() - t_check
    correct = all(v["limit"] is not None and v["value"] <= v["limit"]
                  for v in numbers.values())

    lat = rec.lateness_s
    print(f"window {rec.seconds} s: {rec.attempted} sent, "
          f"{rec.done_in_window} answered in the window, "
          f"{len(rec.latency_s)} latencies, {rec.unanswered} unanswered")
    print(f"compiles_in_window {in_window}")
    print(f"reference check {t_check} s over {len(answers)} answers")
    if rec.latency_s:
        qs = (50, 90, 95, 99, 99.9)
        print("latency ms: " + " ".join(
            f"p{q} {np.percentile(rec.latency_s, q) * 1e3}" for q in qs)
            + f" max {max(rec.latency_s) * 1e3}")
    if lat:
        print(f"generator lateness ms: p50 {np.percentile(lat, 50) * 1e3} "
              f"p99 {np.percentile(lat, 99) * 1e3} max {max(lat) * 1e3}")
    for name, v in numbers.items():
        print(f"check {name} {v['value']} limit {v['limit']}",
              file=sys.stderr)
    result = {
        "correct": bool(correct), "attempted": rec.attempted,
        "failed": rec.unanswered, "metrics": metrics,
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind, "count": len(devices),
                   "memory_peak_bytes": int(peak_bytes)}}
    if summary is not None:
        result["device"]["busy_s"] = summary.busy_s
        result["device"]["window_s"] = summary.window_s
        result["breakdown"] = {"device_ops": summary.top(summary.op_s),
                               "idle_gaps": summary.top(summary.gap_s)}
    result["check"] = numbers
    return result


def inputs_for(config, traffic, seed, seconds):
    """What the traffic sends: a pool of Boolean requests, or the
    session frame streams with the frames the booleanizer is fitted
    to."""
    import generator
    kind, p = config["input"]["kind"], traffic["payload"]
    if kind == "bool_images":
        return generator.bool_images(seed, p["pool"],
                                     config["model"]["features"],
                                     p["density"])
    if kind == "kws_frames":
        s = config["stream"]
        bank = generator.kws_bank(seed, p["bank"], p["frames"],
                                  s["channels"])
        fit = bank.reshape(-1, s["channels"])[:p["fit_frames"]]
        feeds = int(seconds / period_s(config)) + 2
        streams = generator.session_streams(
            bank, seed, traffic["sessions"],
            s["window"] - s["hop"] + s["hop"] * feeds)
        return streams, fit
    raise ValueError(f"unknown input kind {kind!r}")


def period_s(config) -> float:
    s = config["stream"]
    return s["hop"] * s["frame_ms"] / 1e3


def _server(engine, config, traffic, inputs):
    """The stream front end for session traffic, every session's
    window primed short of its first hop (None for other traffic)."""
    if traffic["loop"] != "sessions":
        return None
    streams, fit = inputs
    server = system.stream_server(engine, config, fit)
    prefill = config["stream"]["window"] - config["stream"]["hop"]
    for sid in range(traffic["sessions"]):
        server.feed(str(sid), streams[sid, :prefill])
    return server


def _warm(engine, config, traffic) -> None:
    """Serve one batch of every bucket the traffic can cut, then forget
    it: each shape compiles (or loads) here, not in the window."""
    import numpy as np
    e = config["engine"]
    buckets = ([e["max_batch"]] if traffic["loop"] == "closed"
               else e["buckets"])
    x = np.zeros(config["model"]["features"], np.uint8)
    for b in buckets:
        rids = [engine.submit(x) for _ in range(b)]
        engine.pump(force=True)
        engine.drain()
        for rid in rids:
            engine.take(rid)
    engine.taken_sums.clear()
    engine.dispatches.clear()


def _drive(engine, server, config, traffic, seed, seconds, inputs, span):
    import generator
    import loops
    loop = traffic["loop"]
    if loop == "sessions":
        s = config["stream"]
        period = period_s(config)
        return loops.sessions(
            server, inputs[0], generator.session_phases(traffic, seed, period),
            period, s["hop"], s["window"] - s["hop"], seconds, span)
    pool = len(inputs)
    if loop == "closed":
        order = generator.request_order(seed, pool, pool)
        return loops.closed(engine, inputs, order, traffic["outstanding"],
                            seconds, span)
    if loop == "open":
        due = generator.arrivals(traffic, seed, seconds)
        order = generator.request_order(seed, pool, len(due))
        return loops.open_loop(engine, inputs, order, due, seconds, span)
    raise ValueError(f"unknown loop {loop!r}")


if __name__ == "__main__":
    sys.exit(main())
