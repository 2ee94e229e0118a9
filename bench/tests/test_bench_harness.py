"""The benchmark's own tests, on the CPU: the trace reduction, the work
count, the generators, the loader, the references, the controls and
faults of the comparison, and the refusal off a TPU."""

import collections
import json
import os
import shutil
import signal
import subprocess
import sys
import types

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import check  # noqa: E402
import generator  # noqa: E402
import loader  # noqa: E402
import readings  # noqa: E402
import reference  # noqa: E402
import tracekit  # noqa: E402
import work  # noqa: E402

# F-MNIST at IMBUE Table IV width (10 x 500 clauses, 784 features, 25,742
# includes), an R=4 ensemble under device-to-device variation only: the
# shape of the analog comparison and its control, scaled down per test.
FMNIST_D2D = {
    "model": {"classes": 10, "clauses_per_class": 500, "features": 784,
              "states": 127, "includes": 25742},
    "pool": {"replicas": 4,
             "variation": {"d2d": True, "c2c": False, "csa_offset": False}},
    "engine": {"class": "AsyncServeEngine", "routing": "ensemble",
               "max_in_flight": 2, "max_batch": 128, "max_wait_s": 0.002,
               "buckets": [8, 16, 32, 64, 128], "packed": True,
               "pack_planes": True,
               "expect_backend": "analog-pallas-packed2"},
    "input": {"kind": "bool_images"},
    "check": {"kind": "analog_gap", "sum_gap": None, "unanswered": 0},
}


def fmnist_d2d():
    return json.loads(json.dumps(FMNIST_D2D))

# ---------------------------------------------------------------- trace


def test_merge_and_gaps():
    busy = tracekit.merge([(3, 4), (0, 1), (0.5, 2), (5, 5), (3.5, 3.8)])
    assert busy == [(0, 2), (3, 4)]
    assert tracekit.gaps(busy, -1, 6) == [(-1, 0), (2, 3), (4, 6)]
    assert tracekit.clip(busy, 1, 3.5) == [(1, 2), (3, 3.5)]


def test_attribute_splits_gaps_by_host_span():
    idle = [(0.0, 1.0), (2.0, 4.0)]
    spans = [("bench.intake", 0.0, 0.25), ("bench.pump", 0.5, 2.5),
             ("bench.pump", 3.0, 3.5)]
    got = tracekit.attribute(idle, spans)
    assert got == pytest.approx({"bench.intake": 0.25, "bench.pump": 1.5,
                                 tracekit.NO_SPAN: 1.25})


def attribute_reference(idle, spans):
    """The plain double loop: every idle interval walks the spans from
    the first by start until one starts at or after its end."""
    table = collections.defaultdict(float)
    spans = sorted(spans, key=lambda s: s[1])
    for a, b in idle:
        covered = []
        for name, s0, s1 in spans:
            if s0 >= b:
                break
            lo, hi = max(a, s0), min(b, s1)
            if hi > lo:
                table[name] += hi - lo
                covered.append((lo, hi))
        rest = (b - a) - sum(h - l for l, h in tracekit.merge(covered))
        if rest > 0:
            table[tracekit.NO_SPAN] += rest
    return dict(table)


def _idle(rng, n, lo=0.0, hi=10.0):
    """The gaps between ``n`` seeded busy intervals within ``[lo, hi]``."""
    starts = rng.uniform(lo, hi, n)
    busy = zip(starts, starts + rng.exponential(0.02, n))
    return tracekit.gaps(tracekit.merge(busy), lo, hi)


def _short_spans(rng, n, names, lo=0.0, hi=10.0, mean=0.01):
    starts = rng.uniform(lo, hi, n)
    return [(str(rng.choice(names)), float(a), float(a + d))
            for a, d in zip(starts, rng.exponential(mean, n))]


def _trace_case(case, rng):
    """Idle intervals and host spans of one seeded synthetic trace."""
    idle = _idle(rng, 400)
    names = ["bench.intake", "bench.pump", "bench.take"]
    if case == "nested":
        outer = _short_spans(rng, 150, ["bench.pump"], mean=0.05)
        inner = [("bench.take", a + (b - a) * u, a + (b - a) * (u + v))
                 for (_, a, b), u, v in zip(outer, rng.uniform(0, 0.5, 150),
                                            rng.uniform(0, 0.5, 150))]
        return idle, outer + inner
    if case == "overlapping":
        return idle, _short_spans(rng, 600, names, mean=0.04)
    if case == "wide":
        return idle, _short_spans(rng, 300, names) + [
            ("bench.wide", 2.0, 6.5), ("bench.wide", 6.0, 8.0)]
    if case == "whole_window":
        return idle, _short_spans(rng, 300, names) + [
            ("bench.loop", -1.0, 11.0)]
    if case == "zero_length":
        spans = _short_spans(rng, 300, names)
        spans += [("bench.tick", a, a) for _, a, _ in spans[:50]]
        spans += [("bench.tick", a, a) for a, _ in idle[:20]]
        return idle + [(b, b) for _, b in idle[:30]], spans
    if case == "untouched":
        return idle, _short_spans(rng, 100, names, lo=0.0, hi=4.0)
    if case == "after_last_gap":
        idle = _idle(rng, 400, 0.0, 6.0)[:-1]
        return idle, _short_spans(rng, 300, names, 0.0, 10.0)
    raise ValueError(case)


@pytest.mark.parametrize("seed", [0, 1, 2 ** 31 + 7])
@pytest.mark.parametrize("case", ["nested", "overlapping", "wide",
                                  "whole_window", "zero_length", "untouched",
                                  "after_last_gap"])
def test_attribute_equals_the_double_loop(case, seed):
    idle, spans = _trace_case(case, np.random.default_rng(seed))
    want = attribute_reference(sorted(idle), spans)
    got = tracekit.attribute(idle, spans)
    assert set(got) == set(want)
    for name, t in want.items():
        assert got[name] == pytest.approx(t, rel=1e-12, abs=1e-12), name


def test_attribute_stays_linear_with_a_whole_window_span():
    rng = np.random.default_rng(2 ** 31 + 11)
    starts = np.sort(rng.uniform(0.0, 20.0, 100_000))
    idle = tracekit.gaps(tracekit.merge(zip(starts.tolist(),
                                            (starts + 1e-6).tolist())),
                         0.0, 20.0)
    spans = _short_spans(rng, 50_000, ["bench.pump", "bench.intake"],
                         0.0, 20.0, mean=1e-4)
    spans.append(("bench.loop", 0.0, 20.0))
    assert len(idle) > 99_000

    def too_slow(*_):
        raise TimeoutError("attribute took over 10 s: quadratic again?")

    # A quadratic sweep would run for hours here: stop it at the limit.
    handler = signal.signal(signal.SIGALRM, too_slow)
    signal.setitimer(signal.ITIMER_REAL, 10.0)
    try:
        got = tracekit.attribute(idle, spans)
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, handler)
    assert tracekit.NO_SPAN not in got
    assert got["bench.loop"] == pytest.approx(sum(b - a for a, b in idle))


def test_reduce_synthetic_trace():
    dev = tracekit.DeviceTrace(
        ops=[("kernel", 1.0, 2.0), ("pad", 1.5, 2.5), ("kernel", 4.0, 5.0),
             ("kernel", 9.0, 11.0)],
        modules=[("jit_fwd(1)", 1.0, 2.5), ("jit_fwd(1)", 4.0, 5.0),
                 ("jit_fwd(1)", 9.0, 11.0)])
    spans = [(tracekit.WINDOW_SPAN, 0.0, 10.0),
             ("bench.intake", 2.5, 4.0), ("bench.pump", 5.0, 6.0)]
    s = tracekit.reduce([dev, dev], spans, (0.0, 10.0))
    assert s.window_s == 10.0 and s.n_devices == 2
    assert s.busy_s == pytest.approx(1.5 + 1.0 + 1.0)
    assert s.module_s == pytest.approx({"jit_fwd(1)": 3.5})
    assert s.op_s == pytest.approx({"kernel": 3.0, "pad": 1.0})
    assert s.gap_s == pytest.approx({"bench.intake": 1.5, "bench.pump": 1.0,
                                     tracekit.NO_SPAN: 4.0})
    assert s.top(s.op_s, 1) == [["kernel", 3.0]]
    assert tracekit.counts([dev, dev], spans, (0.0, 10.0)) == (6, 2)


def _reading_run(op_s, module_s, dispatches, chips=1):
    shape = work.Shape(classes=6, clauses_per_class=300, features=377,
                       replicas_read=1, deviation_planes=0)
    trace = tracekit.TraceSummary(window_s=1.0, busy_s=0.1, n_devices=chips,
                                  module_s=module_s, op_s=op_s, gap_s={})
    return types.SimpleNamespace(trace=trace, dispatches=dispatches,
                                 shape=shape, chips=chips,
                                 peak=work.peak("TPU v5 lite"))


def test_kernel_roofline_and_step_mfu_readings():
    kernel = "%imbue_class_sums_stack_planes.1 = f32[1,32,128] custom-call"
    disp = [(0.0, 32, 20), (0.1, 32, 30)]
    run = _reading_run({kernel: 1e-3, "%select_multiply_fusion = f32": 1e-3},
                       {"jit_fwd(12)": 3e-3, "jit_other(1)": 5.0}, disp)
    least = sum(work.least_time_s(run.shape, r, run.peak) for *_, r in disp)
    assert readings.class_sums_roofline_pct(run) == pytest.approx(
        100 * least / 1e-3)
    ops = 50 * work.ops_per_decision(run.shape)
    assert readings.step_mfu_pct(run) == pytest.approx(
        100 * ops / (197e12 * 3e-3))
    # The whole step bounds the kernel: its share is never the larger.
    assert readings.step_mfu_pct(run) < readings.class_sums_roofline_pct(run)
    # A path without the kernel leaves its roofline silent, not 0; a run
    # without a trace or dispatches reads nothing.
    silent = _reading_run({"%dot.3 = f32": 1e-3}, {"jit_fwd(1)": 2e-3}, disp)
    assert readings.class_sums_roofline_pct(silent) is None
    assert readings.step_mfu_pct(silent) > 0
    assert readings.step_mfu_pct(_reading_run({}, {}, disp)) is None
    assert readings.class_sums_roofline_pct(
        _reading_run({kernel: 1e-3}, {"jit_fwd(1)": 2e-3}, [])) is None


# ----------------------------------------------------------------- work

def _shape(name):
    return work.Shape.of(loader.config(name))


def test_work_fmnist_by_hand():
    s = work.Shape.of(fmnist_d2d())
    assert 4 * s.literals * s.clauses == 31_360_000     # per row, replica
    assert work.ops_per_decision(s) == 4 * (31_360_000 + 100_000)
    assert s.literals * s.clauses // 8 == 980_000
    assert work.resident_bytes(s) == 980_000 + 125_440_000
    assert work.dispatch_bytes(s, 128) == (980_000 + 125_440_000
                                           + 128 * 196 + 128 * 11 * 4)
    pk = work.peak("TPU v5 lite")
    # 128 rows: 16.1 GFLOP against 126.4 MB -> bytes bound, ~154 us
    assert work.least_time_s(s, 128, pk) == pytest.approx(
        work.dispatch_bytes(s, 128) / 819e9)


def test_work_kws_by_hand():
    s = _shape("kws6-t4-r1-nominal")
    assert (s.literals, s.clauses, s.replicas_read) == (754, 1800, 1)
    assert work.ops_per_decision(s) == 4 * 754 * 1800 + 2 * 1800 * 6
    assert work.resident_bytes(s) == 169_650
    pk = work.peak("TPU v5 lite")
    assert work.least_time_s(s, 128, pk) == pytest.approx(
        128 * work.ops_per_decision(s) / 197e12)
    with pytest.raises(KeyError):
        work.peak("cpu")


def test_kws_geometry_is_377_features():
    c = loader.config("kws6-t4-r1-nominal")
    s = c["stream"]
    assert s["window"] * s["channels"] * s["bits"] == c["model"]["features"]
    assert c["model"]["features"] == 377
    assert 1000 * 1 / (s["hop"] * s["frame_ms"]) == 12.5   # windows/s


# ------------------------------------------------------------ generator

POISSON = {"loop": "open",
           "arrivals": {"process": "poisson", "rate_per_s": 20000,
                        "shape_seed": 0},
           "payload": {"pool": 4096, "density": 0.1}}


def test_arrivals_same_set_other_order():
    t = POISSON
    a, b = (generator.arrivals(t, s, 2.0) for s in (7, 2 ** 31 + 7))
    assert np.array_equal(a, generator.arrivals(t, 7, 2.0))
    assert not np.array_equal(a, b)
    assert np.allclose(np.sort(np.diff(a, prepend=0)),
                       np.sort(np.diff(b, prepend=0)))
    rate = t["arrivals"]["rate_per_s"]
    assert abs(np.searchsorted(a, 2.0) / 2.0 - rate) < 0.05 * rate


def test_arrivals_refuse_an_unknown_process():
    onoff = {**POISSON, "arrivals": {**POISSON["arrivals"],
                                     "process": "onoff"}}
    with pytest.raises(ValueError, match="onoff"):
        generator.arrivals(onoff, 7, 2.0)


def test_requests_and_sessions_deterministic():
    t = loader.traffic("kws-sessions")
    assert np.array_equal(generator.session_phases(t, 5, 0.08),
                          generator.session_phases(t, 5, 0.08))
    assert np.allclose(np.sort(generator.session_phases(t, 5, 0.08)),
                       np.sort(generator.session_phases(t, 6, 0.08)))
    x = generator.bool_images(9, 64, 784, 0.1)
    assert np.array_equal(x, generator.bool_images(9, 64, 784, 0.1))
    assert abs(x.mean() - 0.1) < 0.01
    bank = generator.kws_bank(9, 8, 32, 13)
    assert bank.shape == (8, 32, 13) and bank.dtype == np.float32
    assert np.array_equal(bank, generator.kws_bank(9, 8, 32, 13))
    st = generator.session_streams(bank, 9, 3, 100)
    assert st.shape == (3, 100, 13)
    assert np.array_equal(st, generator.session_streams(bank, 9, 3, 100))


def test_seed_key_uses_all_64_bits():
    import jax
    a, b = generator.seed_key(5), generator.seed_key(5 + 2 ** 32)
    assert not np.array_equal(jax.random.key_data(a),
                              jax.random.key_data(b))
    with pytest.raises(ValueError):
        generator.seed_key(-1)


# --------------------------------------------------------------- loader

def test_loader_finds_new_files_by_name(tmp_path):
    for d in ("configs", "traffic", "metrics"):
        (tmp_path / d).mkdir()
    (tmp_path / "configs" / "new-model.json").write_text('{"model": 1}')
    (tmp_path / "traffic" / "new-mix.json").write_text('{"loop": "open"}')
    (tmp_path / "metrics" / "new_metric.x.py").write_text(
        "def read(run):\n    return run * 2\n")
    assert loader.config("new-model", str(tmp_path)) == {"model": 1}
    assert loader.traffic("new-mix", str(tmp_path)) == {"loop": "open"}
    assert loader.metric_reader("new_metric.x", str(tmp_path))(21) == 42
    bm = {"end_to_end": [{"name": "a"}, {"name": "b", "workloads": ["c2"]}],
          "per_layer": [{"name": "x", "moves": "a"},
                        {"name": "y", "moves": "b"},
                        {"name": "z", "moves": "a", "workloads": ["c2"]}]}
    assert [m["name"] for m in loader.metrics_for(bm, "c1", False)] == ["a"]
    assert [m["name"] for m in loader.metrics_for(bm, "c1", True)] == ["x"]
    assert [m["name"] for m in loader.metrics_for(bm, "c2", True)] == \
        ["x", "y", "z"]


def test_benchmark_json_names_existing_files():
    bm = loader.benchmark(ROOT)
    for c in bm["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
    for cell in bm["workloads"]:
        loader.config(cell["config"])
        loader.traffic(cell["traffic"])
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert callable(loader.metric_reader(m["name"]))


# ------------------------------------------------------------ reference

def test_reference_d2d_draws_match_the_programmed_pool():
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    from repro.core.variations import VariationConfig
    from repro.serve.replica import program_replica_pool
    inc = jax.random.bernoulli(jax.random.PRNGKey(0), 0.1, (40, 96))
    key = generator.model_key(11, generator.STREAM_ENGINE)
    pool = program_replica_pool(inc, jax.random.split(key)[0], 3,
                                VariationConfig(c2c=False, csa_offset=False))
    ref = reference.d2d_resistance(key, inc, 3)
    assert np.allclose(np.asarray(pool.r_stack), np.asarray(ref),
                       rtol=1e-6)


def test_digital_sums_by_hand():
    include = np.zeros((4, 6), bool)          # 2 classes x 2 clauses, F=3
    include[0, 0] = True                      # x0
    include[1, 4] = True                      # not x1
    include[2, [0, 1]] = True                 # x0 and x1
    x = np.array([[1, 0, 0], [1, 1, 0], [0, 1, 1]], np.uint8)
    got = reference.digital_sums(include, x, 2)
    assert got.tolist() == [[0, 0], [1, 1], [0, 0]]


def test_sum_gap_counts_the_cheapest_flips():
    # clauses +, -, +, - : the first two fire
    mu = np.array([[[-0.5, -0.01, 0.3, 0.002]]], np.float32)   # [1,1,4]
    ref = reference.margin_sums(mu, 1)
    assert ref.tolist() == [[1 - 1]]
    rows = np.zeros(1, np.int64)
    assert reference.sum_gap(ref, rows, mu, 1) == 0.0
    # one more vote: fire the +clause at 0.3 or unfire the -clause at 0.01
    assert reference.sum_gap(ref + 1, rows, mu, 1) == pytest.approx(0.01)
    # one fewer: unfire the +clause at 0.5 or fire the -clause at 0.002
    assert reference.sum_gap(ref - 1, rows, mu, 1) == pytest.approx(0.002)
    assert reference.sum_gap(ref + 3, rows, mu, 1) == np.inf


def test_median_thresholds_are_frames():
    f = np.random.default_rng(0).normal(size=(255, 13)).astype(np.float32)
    thr = reference.median_thresholds(f)
    assert all(thr[c] in f[:, c] for c in range(13))
    with pytest.raises(ValueError):
        reference.median_thresholds(f[:254])


# ------------------------------------------------ control, faults, runs

def _tiny(tmp_path):
    """Tiny configurations and traffic in a scratch directory, with the
    benchmark's metric readers beside them."""
    for d in ("configs", "traffic"):
        (tmp_path / d).mkdir(exist_ok=True)
    shutil.copytree(os.path.join(BENCH, "metrics"), tmp_path / "metrics",
                    dirs_exist_ok=True)
    d2d = fmnist_d2d()
    d2d["model"].update(classes=4, clauses_per_class=20, features=64,
                        includes=300)
    d2d["pool"]["replicas"] = 2
    d2d["engine"].update(max_batch=16, buckets=[8, 16])
    d2d["check"]["sum_gap"] = 1e-4      # sound tiny runs read 0
    kws = loader.config("kws6-t4-r1-nominal")
    kws["model"].update(clauses_per_class=10, features=5 * 13,
                        includes=120)
    kws["stream"]["window"] = 5
    kws["engine"].update(max_batch=16, buckets=[8, 16])
    files = {
        "configs/tiny-d2d.json": d2d, "configs/tiny-kws.json": kws,
        "traffic/closed.json": {"loop": "closed", "outstanding": 32,
                                "payload": {"pool": 64, "density": 0.1}},
        "traffic/open.json": {"loop": "open",
                              "arrivals": {"process": "poisson",
                                           "rate_per_s": 200,
                                           "shape_seed": 0},
                              "payload": {"pool": 64, "density": 0.1}},
        "traffic/sess.json": {"loop": "sessions", "sessions": 6,
                              "shape_seed": 0,
                              "payload": {"bank": 16, "frames": 32,
                                          "fit_frames": 255}}}
    for name, body in files.items():
        (tmp_path / name).write_text(json.dumps(body))
    return str(tmp_path)


CELLS = {
    "closed": {"name": "c_bulk", "config": "tiny-d2d", "traffic": "closed",
               "chips": 1},
    "open": {"name": "c_open", "config": "tiny-d2d", "traffic": "open",
             "chips": 1},
    "sess": {"name": "c_sess", "config": "tiny-kws", "traffic": "sess",
             "chips": 1},
}
BM = {"end_to_end": [{"name": "decisions_per_s", "unit": "decisions/s"},
                     {"name": "latency_p90_ms", "unit": "ms",
                      "workloads": ["c_open", "c_sess"]},
                     {"name": "setup_s", "unit": "s"}],
      "per_layer": [{"name": "intake_us.open", "unit": "us",
                     "moves": "latency_p90_ms"},
                    {"name": "batch_occupancy.open", "unit": "%",
                     "moves": "latency_p90_ms"}]}


def _run(tmp_path, monkeypatch, kind, tamper=None, seconds=0.6,
         traced=False):
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    import run
    monkeypatch.setitem(work.PEAKS, jax.devices()[0].device_kind,
                        {"flops_per_s": 1e12, "bytes_per_s": 1e11})
    monkeypatch.setattr(run, "CACHE", str(tmp_path / "cache"))
    return run.run_cell(BM, CELLS[kind], 2 ** 31 + 5, seconds, traced,
                        jax.devices()[:1], base=_tiny(tmp_path),
                        tamper=tamper)


@pytest.mark.parametrize("kind", ["closed", "open", "sess"])
def test_sound_runs_are_correct(tmp_path, monkeypatch, kind):
    r = _run(tmp_path, monkeypatch, kind)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    assert list(r)[-1] == "check"
    assert r["metrics"]["decisions_per_s"]["value"] > 0
    assert ("latency_p90_ms" in r["metrics"]) == (kind != "closed")


def test_traced_run_reports_per_layer_metrics(tmp_path, monkeypatch):
    r = _run(tmp_path, monkeypatch, "open", traced=True)
    assert r["correct"]
    assert set(r["metrics"]) == {"intake_us.open", "batch_occupancy.open"}
    assert r["device"]["window_s"] > 0
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}


def _alter_answers(engine):
    """A fault where answers are produced: every row's first class sum
    gains 5 votes."""
    fwd = engine._fwd

    def altered(*a, **kw):
        sums, preds = fwd(*a, **kw)
        return sums.at[:, 0].add(5), preds

    engine._fwd = altered


def _drop_answers(engine):
    """A fault where answers never come back: every 7th is lost."""
    take = engine.take

    def lossy(rid):
        return None if rid % 7 == 3 else take(rid)

    engine.take = lossy


@pytest.mark.parametrize("kind,fault,number", [
    ("closed", _alter_answers, "sum_gap"),
    ("sess", _alter_answers, "mismatched"),
    ("open", _drop_answers, "unanswered"),
])
def test_faults_make_the_run_incorrect(tmp_path, monkeypatch, kind, fault,
                                       number):
    import loops
    monkeypatch.setattr(loops, "DRAIN_LIMIT_S", 1.0)
    r = _run(tmp_path, monkeypatch, kind, tamper=fault)
    assert not r["correct"]
    assert r["check"][number]["value"] > r["check"][number]["limit"]


def test_controls_fail(tmp_path):
    """bfloat16 put in the program's place fails each comparison: the
    crossbar operands at F-MNIST's width (500 clauses, 256 requests;
    the chip read 9.1e-4 at full size), the keyword frames before
    thresholding."""
    import control
    fm = fmnist_d2d()
    fm["model"].update(clauses_per_class=50, includes=2574)
    fm["pool"]["replicas"] = 2
    x = generator.bool_images(3, 256, 784, 0.1)
    got = check.control(fm, 3, x, list(range(256)))
    assert got["sum_gap"]["value"] > 2e-4
    kws = loader.config("kws6-t4-r1-nominal")
    kws["model"]["clauses_per_class"] = 30
    kws["model"]["includes"] = 800
    traffic = {"sessions": 4, "payload": {"bank": 16, "frames": 32,
                                          "fit_frames": 511}}
    import run
    inputs = run.inputs_for(kws, traffic, 3, 4.0)
    keys = control.keys_for(kws, {**traffic, "loop": "sessions"}, 4.0)
    got = check.control(kws, 3, inputs, keys)
    assert got["mismatched"]["value"] > 0


MESH_RUN = """
import json, sys
import jax
sys.path[:0] = [{bench!r}, {src!r}]
import run, work
work.PEAKS[jax.devices()[0].device_kind] = {{"flops_per_s": 1e12,
                                             "bytes_per_s": 1e11}}
run.CACHE = {cache!r}
cell = {{"name": "c_mesh", "config": "tiny-mesh", "traffic": "closed",
         "chips": 4}}
sharded = []
r = run.run_cell({bm!r}, cell, 2 ** 31 + 5, 0.6, False, jax.devices()[:4],
                 base={base!r},
                 tamper=lambda e: sharded.append(e.state.is_sharded))
try:
    run.run_cell({bm!r}, cell, 5, 0.6, False, jax.devices()[:1],
                 base={base!r})
    refused = False
except ValueError:
    refused = True
print(json.dumps({{"result": r, "sharded": sharded, "refused": refused}}))
"""


def test_mesh_cell_serves_sharded_over_its_devices(tmp_path):
    """A four-chip configuration builds its replica mesh over the cell's
    devices (four forced host devices here), serves from the sharded
    pool, reports four devices and passes the check; one device is
    refused."""
    base = _tiny(tmp_path)
    mesh = fmnist_d2d()
    mesh["model"].update(classes=4, clauses_per_class=20, features=64,
                         includes=300)
    mesh["pool"]["replicas"] = 4
    mesh["mesh"] = {"replica": 4, "batch": 1}
    mesh["engine"].update(max_batch=16, buckets=[8, 16], packed=False,
                          pack_planes=False, expect_backend="analog-jnp")
    mesh["check"]["sum_gap"] = 1e-4
    (tmp_path / "configs" / "tiny-mesh.json").write_text(json.dumps(mesh))
    code = MESH_RUN.format(bench=BENCH, src=os.path.join(ROOT, "src"),
                           cache=str(tmp_path / "cache"), bm=BM, base=base)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    r = out["result"]
    assert out["sharded"] == [True] and out["refused"]
    assert r["correct"] and r["device"]["count"] == 4 and r["attempted"] > 0


def test_run_refuses_a_cell_whose_chips_differ_from_its_mesh(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bm = loader.benchmark(ROOT)
    bm["workloads"][0]["chips"] = 4
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))
    cmd = [sys.executable, "bench/run.py", "--workload",
           bm["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
           "--trace", "0"]
    p = subprocess.run(cmd, cwd=tmp_path, capture_output=True, text=True,
                       timeout=120, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 2 and "serves from 1" in p.stderr
    assert p.stdout.strip() == ""


def test_run_refuses_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, "bench/run.py", "--workload", "kws6_stream_open",
           "--seed", "1", "--seconds", "1", "--trace", "0"]
    p = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                       text=True, timeout=120)
    assert p.returncode == 2 and "needs a TPU" in p.stderr
    assert p.stdout.strip() == ""
    # A checkout with only the benchmark (no program) refuses too.
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    p = subprocess.run(cmd, cwd=tmp_path, env=env, capture_output=True,
                       text=True, timeout=120)
    assert p.returncode == 2 and p.stdout.strip() == ""
