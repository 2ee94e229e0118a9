"""Serving subsystem tests: dynamic batcher, replica pool, engine.

The digital TM (``core/tm.py``) is the oracle throughout: with
``VariationConfig.nominal()`` every analog path must reproduce it
bit-for-bit (the paper's zero-variation equivalence).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import imbue, tm
from repro.core.variations import VariationConfig
from repro.serve import (CANARY, AsyncServeEngine, BatcherConfig,
                         DynamicBatcher, EngineConfig, ServeEngine,
                         ensemble_vote, program_replica_pool)


class FakeClock:
    """Deterministic clock for deadline tests."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


# ------------------------------------------------------------- batcher

def test_bucket_selection():
    cfg = BatcherConfig(max_batch=128, bucket_sizes=(8, 16, 32, 64, 128))
    assert cfg.bucket_for(1) == 8
    assert cfg.bucket_for(8) == 8
    assert cfg.bucket_for(9) == 16
    assert cfg.bucket_for(128) == 128
    with pytest.raises(ValueError):
        cfg.bucket_for(129)


def test_bucket_config_validation():
    with pytest.raises(ValueError):
        BatcherConfig(max_batch=64, bucket_sizes=(8, 32))   # max not a bucket
    with pytest.raises(ValueError):
        BatcherConfig(max_batch=12, bucket_sizes=(12,))     # not sublane-mult


def test_batcher_pads_and_keeps_fifo_order():
    clock = FakeClock()
    b = DynamicBatcher(BatcherConfig(max_batch=16, bucket_sizes=(8, 16)))
    for rid in range(11):
        b.submit(rid, np.full(4, rid % 2, dtype=np.uint8), clock())
    batch = b.cut(clock(), force=True)
    assert batch.bucket == 16 and batch.n_valid == 11 and batch.n_padding == 5
    assert [r.rid for r in batch.requests] == list(range(11))
    assert batch.x.shape == (16, 4)
    # padding rows are ZEROS, never a replay of a real request: a pad row
    # leaking through unpad must surface as an obviously-wrong all-zero
    # input, not duplicate request 0's prediction
    np.testing.assert_array_equal(batch.x[11:], np.zeros((5, 4), np.uint8))


def test_batcher_packed_mode_packs_once_at_submit():
    """Packed mode: the queue holds uint32 literal words (packed at
    submit), pad rows are zero words, and the packed row equals the
    host-side pack of [x, 1-x]."""
    from repro.serve.batching import pack_request_np
    clock = FakeClock()
    b = DynamicBatcher(BatcherConfig(max_batch=8, bucket_sizes=(8,)),
                       packed=True)
    xs = [np.array([1, 0, 1, 1, 0], np.uint8) for _ in range(3)]
    for rid, x in enumerate(xs):
        b.submit(rid, x, clock())
    assert b._queues["bulk"][0].x.dtype == np.uint32  # packed in the queue
    batch = b.cut(clock(), force=True)
    assert batch.packed and batch.x.dtype == np.uint32
    assert batch.x.shape == (8, 1)                   # ceil(10/32) = 1 word
    np.testing.assert_array_equal(batch.x[0], pack_request_np(xs[0]))
    np.testing.assert_array_equal(batch.x[3:], np.zeros((5, 1), np.uint32))
    assert batch.nbytes == batch.x.nbytes


def test_batcher_deadline_trigger():
    clock = FakeClock()
    cfg = BatcherConfig(max_batch=16, bucket_sizes=(8, 16), max_wait_s=1e-3)
    b = DynamicBatcher(cfg)
    b.submit(0, np.zeros(4, np.uint8), clock())
    assert not b.ready(clock())            # under-full, deadline not hit
    assert b.cut(clock()) is None
    clock.advance(2e-3)
    assert b.ready(clock())                # oldest request timed out
    batch = b.cut(clock())
    assert batch is not None and batch.n_valid == 1 and batch.bucket == 8


def test_batcher_full_bucket_triggers_immediately():
    clock = FakeClock()
    b = DynamicBatcher(BatcherConfig(max_batch=8, bucket_sizes=(8,)))
    for rid in range(9):
        b.submit(rid, np.zeros(4, np.uint8), clock())
    assert b.ready(clock())
    batch = b.cut(clock())
    assert batch.n_valid == 8 and [r.rid for r in batch.requests] == \
        list(range(8))
    assert len(b) == 1                     # the ninth request stays queued


# ---------------------------------------------------------- replica pool

@pytest.mark.parametrize("n_replicas", [1, 4])
def test_pool_zero_variation_matches_digital_oracle(small_cfg, random_ta,
                                                    boolean_batch, keys,
                                                    n_replicas):
    """Stacked clause outputs == digital ``clause_outputs`` exactly."""
    cfg = small_cfg
    inc = tm.include_mask(random_ta, cfg)
    pool = program_replica_pool(inc, keys["program"], n_replicas,
                                VariationConfig.nominal())
    lits = tm.literals(jnp.asarray(boolean_batch))
    got = imbue.stacked_clause_outputs(pool.r_stack, pool.include, lits,
                                       cfg, None, VariationConfig.nominal())
    oracle = tm.clause_outputs(random_ta, lits, cfg, training=True)
    for r in range(n_replicas):
        np.testing.assert_array_equal(np.asarray(got[r]), np.asarray(oracle))


@pytest.mark.parametrize("routing", ["round_robin", "least_loaded",
                                     "ensemble"])
@pytest.mark.parametrize("n_replicas", [1, 4])
def test_engine_zero_variation_matches_digital_argmax(
        small_cfg, random_ta, boolean_batch, keys, routing, n_replicas):
    """End-to-end: engine predictions == digital TM argmax, R in {1, 4}."""
    eng = ServeEngine.from_ta_state(
        random_ta, small_cfg, n_replicas=n_replicas, key=keys["route"],
        vcfg=VariationConfig.nominal(),
        ecfg=EngineConfig(routing=routing,
                          batcher=BatcherConfig(max_batch=32,
                                                bucket_sizes=(8, 16, 32))))
    eng.submit_many(list(boolean_batch))
    preds = np.array([r.pred for r in eng.drain()])
    digital = np.asarray(tm.predict(random_ta, jnp.asarray(boolean_batch),
                                    small_cfg))
    np.testing.assert_array_equal(preds, digital)


def test_engine_preserves_request_order(small_cfg, random_ta, boolean_batch,
                                        keys):
    """Responses come back in submission order, each with its own row's
    prediction (no cross-wiring inside padded/bucketed batches)."""
    eng = ServeEngine.from_ta_state(
        random_ta, small_cfg, n_replicas=2, key=keys["route"],
        vcfg=VariationConfig.nominal(),
        ecfg=EngineConfig(batcher=BatcherConfig(max_batch=16,
                                                bucket_sizes=(8, 16))))
    perm = np.random.default_rng(0).permutation(len(boolean_batch))
    rids = eng.submit_many([boolean_batch[i] for i in perm])
    responses = eng.drain()
    assert [r.rid for r in responses] == rids
    digital = np.asarray(tm.predict(
        random_ta, jnp.asarray(boolean_batch[perm]), small_cfg))
    np.testing.assert_array_equal(np.array([r.pred for r in responses]),
                                  digital)


def test_ensemble_vote_deterministic_under_fixed_key(small_cfg, random_ta,
                                                     boolean_batch, keys):
    """Full-noise ensemble serving is bit-reproducible given one key."""
    def run():
        eng = ServeEngine.from_ta_state(
            random_ta, small_cfg, n_replicas=4, key=keys["route"],
            vcfg=VariationConfig(),
            ecfg=EngineConfig(routing="ensemble"))
        eng.submit_many(list(boolean_batch[:16]))
        return [r.pred for r in eng.drain()]

    assert run() == run()


def test_ensemble_vote_majority_and_ties():
    # 3 replicas, 2 datapoints, 3 classes: [replica, batch, class] sums
    sums = jnp.asarray([
        [[3.0, 1.0, 0.0], [0.0, 2.0, 1.0]],
        [[0.0, 2.0, 1.0], [0.0, 2.0, 1.0]],
        [[3.0, 1.0, 0.0], [1.0, 0.0, 2.0]],
    ])
    got = ensemble_vote(sums)
    np.testing.assert_array_equal(np.asarray(got), [0, 1])
    # 2-2 tie breaks toward the lowest class index
    tie = jnp.asarray([[[1.0, 0.0]], [[0.0, 1.0]]])
    assert int(ensemble_vote(tie)[0]) == 0


def test_least_loaded_balances_rows(small_cfg, random_ta, keys):
    eng = ServeEngine.from_ta_state(
        random_ta, small_cfg, n_replicas=2, key=keys["route"],
        vcfg=VariationConfig.nominal(),
        ecfg=EngineConfig(routing="least_loaded",
                          batcher=BatcherConfig(max_batch=8,
                                                bucket_sizes=(8,))))
    x = np.zeros((32, small_cfg.n_features), np.uint8)
    eng.submit_many(list(x))
    eng.drain()
    assert eng.router.rows_dispatched == [16, 16]


def test_kernel_and_jnp_paths_agree(small_cfg, random_ta, boolean_batch,
                                    keys):
    preds = []
    for backend in ("analog-pallas-packed", "analog-pallas", "analog-jnp"):
        eng = ServeEngine.from_ta_state(
            random_ta, small_cfg, n_replicas=2, key=keys["route"],
            vcfg=VariationConfig.nominal(),
            ecfg=EngineConfig(backend=backend))
        assert eng.backend.name == backend        # preference satisfied
        eng.submit_many(list(boolean_batch))
        preds.append([r.pred for r in eng.drain()])
    assert preds[0] == preds[1] == preds[2]


def test_default_engine_selects_packed_backend(small_cfg, random_ta, keys,
                                               boolean_batch):
    """EngineConfig() defaults to the packed wire AND the plane-packed
    resident format: the pool state gets a packed include plane (shared
    with the LRS/HRS index bitplane), selection lands on
    analog-pallas-packed2, the batcher queues uint32 words, and
    bytes-moved shrinks accordingly."""
    eng = ServeEngine.from_ta_state(
        random_ta, small_cfg, n_replicas=2, key=keys["route"],
        vcfg=VariationConfig.nominal(), ecfg=EngineConfig())
    assert eng.state.packed and eng.state.plane_packed
    assert eng.backend.name == "analog-pallas-packed2"
    assert eng.packed_io and eng.batcher.packed
    eng.submit_many(list(boolean_batch[:16]))
    eng.drain()
    s = eng.summary()
    assert s["packed_io"] is True
    # 16 requests pad to one bucket of 8? no: max_batch 128 deadline cut
    # -> one batch; words = ceil(2F/32) * 4 bytes per row
    words = -(-2 * small_cfg.n_features // 32)
    assert s["bytes_moved"] % (words * 4) == 0
    # unpacked engine moves 8x more per row (uint8 literals vs packed)
    eng2 = ServeEngine.from_ta_state(
        random_ta, small_cfg, n_replicas=2, key=keys["route"],
        vcfg=VariationConfig.nominal(), ecfg=EngineConfig(packed=False))
    assert eng2.backend.name == "analog-pallas" and not eng2.packed_io


def test_engine_consumes_registry_tuning_table(small_cfg, random_ta, keys):
    """Autotuned bucket sizes come from the registry tuning table, not a
    hard-coded ladder: a for_max_batch batcher picks up the measured
    buckets (capped at max_batch) and records which backend they were
    measured for; kernel tiles flow into the dispatch opts.  The table
    is keyed by (backend, shape bucket), so the entry is registered
    under THIS model's bucket."""
    from repro import api
    shape_key = api.shape_bucket_key(small_cfg.n_clauses,
                                     small_cfg.n_literals)
    saved = api.tuning_snapshot()
    api.register_tuning("analog-pallas-packed2",
                        {"tiles": {"ct": 32, "kt": 128},
                         "bucket_sizes": [8, 24, 96]},
                        shape_key=shape_key)
    try:
        eng = ServeEngine.from_ta_state(
            random_ta, small_cfg, n_replicas=1, key=keys["route"],
            vcfg=VariationConfig.nominal(),
            ecfg=EngineConfig(batcher=BatcherConfig.for_max_batch(64)))
        assert eng.backend.name == "analog-pallas-packed2"
        assert eng.shape_key == shape_key
        # 96 exceeds max_batch and is dropped; max_batch caps the ladder
        assert eng.batcher.cfg.bucket_sizes == (8, 24, 64)
        assert eng.batcher.cfg.tuned_for == "analog-pallas-packed2"
        assert eng.summary()["kernel_tiles"] == {"ct": 32, "kt": 128}
        # an explicit (hand-picked) ladder is NEVER overridden
        eng2 = ServeEngine.from_ta_state(
            random_ta, small_cfg, n_replicas=1, key=keys["route"],
            vcfg=VariationConfig.nominal(),
            ecfg=EngineConfig(batcher=BatcherConfig(
                max_batch=16, bucket_sizes=(8, 16))))
        assert eng2.batcher.cfg.bucket_sizes == (8, 16)
        assert eng2.batcher.cfg.tuned_for is None
    finally:
        api.restore_tuning(saved)


def test_pad_rows_are_dropped_on_unpad(small_cfg, random_ta, keys,
                                       boolean_batch):
    """A padded dispatch returns exactly n_valid responses, and each
    matches the digital oracle — zero pad rows cannot alias a real
    request's prediction."""
    eng = ServeEngine.from_ta_state(
        random_ta, small_cfg, n_replicas=1, key=keys["route"],
        vcfg=VariationConfig.nominal(),
        ecfg=EngineConfig(batcher=BatcherConfig(max_batch=16,
                                                bucket_sizes=(16,))))
    rids = eng.submit_many(list(boolean_batch[:5]))   # 5 valid, 11 pad
    responses = eng.drain()
    assert [r.rid for r in responses] == rids and len(responses) == 5
    digital = np.asarray(tm.predict(
        random_ta, jnp.asarray(boolean_batch[:5]), small_cfg))
    np.testing.assert_array_equal(np.array([r.pred for r in responses]),
                                  digital)
    assert eng.metrics.padded_rows == 11


def test_use_kernel_flag_is_a_deprecated_alias(small_cfg, random_ta, keys):
    with pytest.warns(DeprecationWarning):
        eng = ServeEngine.from_ta_state(
            random_ta, small_cfg, key=keys["route"],
            vcfg=VariationConfig.nominal(),
            ecfg=EngineConfig(use_kernel=False))
    assert eng.backend.name == "analog-jnp"


def test_csa_offset_fallback_is_loud(small_cfg, random_ta, boolean_batch,
                                     keys):
    """csa_offset on + analog-pallas preferred -> engine switches to the
    jnp path AND says so: construction warns, metrics/summary record the
    reason and count every affected dispatch (satellite: no silent
    noise-semantics changes)."""
    with pytest.warns(UserWarning, match="fallback"):
        eng = ServeEngine.from_ta_state(
            random_ta, small_cfg, n_replicas=2, key=keys["route"],
            vcfg=VariationConfig(),          # csa_offset=True
            ecfg=EngineConfig(backend="analog-pallas"))
    assert eng.backend.name == "analog-jnp"
    assert eng.selection.fell_back
    eng.submit_many(list(boolean_batch[:16]))
    eng.drain()
    s = eng.summary()
    assert s["backend"] == "analog-jnp"
    assert s["backend_preferred"] == "analog-pallas"
    assert s["fallback_dispatches"] == eng.metrics.batches
    assert any("models_csa_offset" in r for r in s["forward_fallbacks"])
    # a nominal pool keeps the preferred kernel and records nothing
    eng2 = ServeEngine.from_ta_state(
        random_ta, small_cfg, key=keys["route"],
        vcfg=VariationConfig.nominal(),
        ecfg=EngineConfig(backend="analog-pallas"))
    eng2.submit_many(list(boolean_batch[:8]))
    eng2.drain()
    s2 = eng2.summary()
    assert s2["backend"] == "analog-pallas"
    assert s2["forward_fallbacks"] == [] and s2["fallback_dispatches"] == 0


# -------------------------------------------------------- async engine

@pytest.mark.parametrize("routing", ["round_robin", "ensemble"])
def test_async_engine_matches_digital_and_order(small_cfg, random_ta,
                                                boolean_batch, keys,
                                                routing):
    """AsyncServeEngine: same responses as the digital oracle, in
    submission order, with every in-flight dispatch collected by
    drain()."""
    eng = AsyncServeEngine.from_ta_state(
        random_ta, small_cfg, n_replicas=2, key=keys["route"],
        vcfg=VariationConfig.nominal(),
        ecfg=EngineConfig(routing=routing,
                          batcher=BatcherConfig(max_batch=16,
                                                bucket_sizes=(8, 16))))
    rids = eng.submit_many(list(boolean_batch))
    responses = eng.drain()
    assert [r.rid for r in responses] == rids
    assert eng.in_flight == 0
    digital = np.asarray(tm.predict(random_ta, jnp.asarray(boolean_batch),
                                    small_cfg))
    np.testing.assert_array_equal(np.array([r.pred for r in responses]),
                                  digital)


def test_async_engine_double_buffers_and_reports_overlap(
        small_cfg, random_ta, boolean_batch, keys):
    """The double buffer really holds dispatches in flight (bounded by
    max_in_flight), result() collects on demand, and the overlap
    accounting lands in summary()."""
    eng = AsyncServeEngine.from_ta_state(
        random_ta, small_cfg, n_replicas=2, key=keys["route"],
        vcfg=VariationConfig.nominal(),
        ecfg=EngineConfig(max_in_flight=2,
                          batcher=BatcherConfig(max_batch=8,
                                                bucket_sizes=(8,))))
    depths = []
    orig = eng._issue
    eng._issue = lambda b: depths.append(eng.in_flight) or orig(b)
    rids = eng.submit_many(list(boolean_batch[:32]))   # 4 batches of 8
    eng.pump(force=True)
    # bounded by max_in_flight; may already be 0 if the device finished
    # (pump collects ready futures opportunistically)
    assert 0 <= eng.in_flight <= 2
    assert max(depths) >= 1                            # pipelined issues
    first = eng.result(rids[0])                        # on-demand collect
    assert first is not None and first.rid == rids[0]
    eng.drain()
    assert eng.in_flight == 0
    s = eng.summary()
    assert s["requests"] == 32 and s["batches"] == 4
    assert 0.0 <= s["overlap_fraction"] <= 1.0
    assert s["host_pack_s"] >= 0 and s["device_wait_s"] >= 0
    # the synchronous engine never leaves anything in flight and its
    # summary carries the same keys (~zero overlap by construction)
    sync = ServeEngine.from_ta_state(
        random_ta, small_cfg, n_replicas=2, key=keys["route"],
        vcfg=VariationConfig.nominal())
    sync.submit_many(list(boolean_batch[:8]))
    sync.drain()
    assert "overlap_fraction" in sync.summary()


def test_async_engine_validates_depth(small_cfg, random_ta, keys):
    with pytest.raises(ValueError, match="max_in_flight"):
        AsyncServeEngine.from_ta_state(
            random_ta, small_cfg, key=keys["route"],
            vcfg=VariationConfig.nominal(),
            ecfg=EngineConfig(max_in_flight=0))


def _noisy_engine(cls, small_cfg, random_ta, keys, canary):
    """A D2D + C2C engine over buckets 8/16/32, optionally with half of
    its batches served by a canary (replica 1's state as version 1)."""
    eng = cls.from_ta_state(
        random_ta, small_cfg, n_replicas=2, key=keys["route"],
        vcfg=VariationConfig(csa_offset=False),
        ecfg=EngineConfig(batcher=BatcherConfig(
            max_batch=32, bucket_sizes=(8, 16, 32))))
    if canary:
        eng.arm_canary(eng._slices[1], 1, 0.5)
    return eng


@pytest.mark.parametrize("canary", [False, True])
def test_async_responses_bit_identical_to_sync(small_cfg, random_ta,
                                               boolean_batch, keys, canary,
                                               monkeypatch):
    """Starting the result copies at issue and collecting on a later
    pump changes no response: an async engine hands back the sync
    engine's predictions, class sums, replicas and versions bit for bit
    (same read keys), across buckets and with a canary armed.  Only the
    async engine starts the copies, for every output of every
    dispatch."""
    array_cls = type(jnp.zeros(1))
    copy = array_cls.copy_to_host_async
    started = []
    monkeypatch.setattr(array_cls, "copy_to_host_async",
                        lambda a: (started.append(a.shape), copy(a))[1])
    out = {}
    for cls in (ServeEngine, AsyncServeEngine):
        started.clear()
        eng = _noisy_engine(cls, small_cfg, random_ta, keys, canary)
        rids = []
        for lo, hi in ((0, 5), (5, 17), (17, 47), (47, 64)):  # 8/16/32/32
            rids += eng.submit_many(list(boolean_batch[lo:hi]))
            eng.pump(force=True)
        rs = eng.drain()
        assert [r.rid for r in rs] == rids
        out[cls] = (rs, eng.summary(), list(started))
    (sync, s_sync, c_sync), (asy, s_async, c_async) = (
        out[ServeEngine], out[AsyncServeEngine])
    for a, b in zip(asy, sync):
        assert (a.pred, a.replica, a.version) == (b.pred, b.replica,
                                                  b.version)
        np.testing.assert_array_equal(a.class_sums, b.class_sums)
    assert s_async["batches"] == s_sync["batches"] == 4
    assert c_sync == []
    shadows = s_async["canary"]["batches"] if canary else 0
    assert len(c_async) == 2 * s_async["batches"] + shadows
    assert s_async["host_fetch_s"] >= 0 and s_sync["host_fetch_s"] > 0
    assert ("canary" in s_async) == canary
    if canary:
        assert s_async["canary"] == s_sync["canary"]
        assert {r.replica for r in asy} >= {CANARY}


def test_async_engine_serves_non_jax_results(small_cfg, random_ta,
                                             boolean_batch, keys):
    """A forward that hands back plain numpy arrays (no async copy, no
    readiness) is still served, in order and unchanged."""
    eng = AsyncServeEngine.from_ta_state(
        random_ta, small_cfg, n_replicas=2, key=keys["route"],
        vcfg=VariationConfig.nominal(),
        ecfg=EngineConfig(batcher=BatcherConfig(max_batch=16,
                                                bucket_sizes=(8, 16))))
    fwd = eng._fwd
    eng._fwd = lambda *a, **kw: tuple(np.asarray(o) for o in fwd(*a, **kw))
    rids = eng.submit_many(list(boolean_batch))
    rs = eng.drain()
    assert [r.rid for r in rs] == rids
    digital = np.asarray(tm.predict(random_ta, jnp.asarray(boolean_batch),
                                    small_cfg))
    np.testing.assert_array_equal(np.array([r.pred for r in rs]), digital)
    s = eng.summary()
    assert s["batches"] == 4 and s["requests"] == len(rids)


def _bucket8_engine(random_ta, small_cfg, keys, clock):
    """A nominal async engine on ``clock`` whose 8-row bucket cuts as
    soon as 8 requests are queued (2 ms bulk deadline)."""
    return AsyncServeEngine.from_ta_state(
        random_ta, small_cfg, n_replicas=2, key=keys["route"],
        vcfg=VariationConfig.nominal(), clock=clock,
        ecfg=EngineConfig(batcher=BatcherConfig(max_batch=8,
                                                bucket_sizes=(8,))))


@pytest.mark.parametrize("then", ["pump", "result", "drain"])
def test_async_collects_a_dispatch_on_a_later_pump(small_cfg, random_ta,
                                                   boolean_batch, keys,
                                                   then, monkeypatch):
    """A dispatch whose device work is still running at the end of the
    pump that issued it stays in flight through that pump (``take``
    finds nothing), with its result copies already started.  The next
    ``pump()`` that finds it done collects it, and ``result()`` or
    ``drain()`` collect it at once."""
    array_cls = type(jnp.zeros(1))
    copy = array_cls.copy_to_host_async
    started = []
    monkeypatch.setattr(array_cls, "copy_to_host_async",
                        lambda a: (started.append(a.shape), copy(a))[1])
    eng = _bucket8_engine(random_ta, small_cfg, keys, FakeClock())
    running = [True]                    # the device, as the pump sees it
    ready = AsyncServeEngine._is_ready
    eng._is_ready = lambda fl: not running[0] and ready(fl)
    rids = eng.submit_many(list(boolean_batch[:8]))
    eng.pump()
    assert eng.in_flight == 1
    assert eng.take(rids[0]) is None
    assert sorted(started) == [(8,), (8, small_cfg.n_classes)]
    running[0] = False
    jax.block_until_ready(eng._pending[0].outputs())
    if then == "pump":
        eng.pump()
        got = [eng.take(r) for r in rids]
    elif then == "result":
        got = [eng.result(r) for r in rids]
    else:
        got = eng.drain()
    assert eng.in_flight == 0
    assert [r.rid for r in got] == rids
    digital = np.asarray(tm.predict(
        random_ta, jnp.asarray(boolean_batch[:8]), small_cfg))
    np.testing.assert_array_equal(np.array([r.pred for r in got]), digital)


@pytest.mark.parametrize("gap_s", [None, 1e-3, 10e-3])
def test_async_returns_a_finished_dispatch_from_its_own_pump(
        small_cfg, random_ta, boolean_batch, keys, gap_s):
    """A dispatch whose device work is done by the end of the pump that
    issued it comes back from that pump, whether pumps come often
    (1 ms apart), seldom (10 ms, past the 2 ms deadline, as a caller
    that pumps once per arrival at a low rate does) or for the first
    time: nothing waits for a pump that may come only with the next
    arrival."""
    clock = FakeClock()
    eng = _bucket8_engine(random_ta, small_cfg, keys, clock)
    ready = AsyncServeEngine._is_ready
    eng._is_ready = lambda fl: (jax.block_until_ready(fl.outputs())
                                and ready(fl))
    if gap_s is not None:
        eng.pump()
        clock.advance(gap_s)
    rids = eng.submit_many(list(boolean_batch[:8]))
    eng.pump()
    assert eng.in_flight == 0
    got = [eng.take(r) for r in rids]
    assert [r.rid for r in got] == rids
    digital = np.asarray(tm.predict(
        random_ta, jnp.asarray(boolean_batch[:8]), small_cfg))
    np.testing.assert_array_equal(np.array([r.pred for r in got]), digital)


def test_metrics_accounting(small_cfg, random_ta, keys):
    eng = ServeEngine.from_ta_state(
        random_ta, small_cfg, n_replicas=1, key=keys["route"],
        vcfg=VariationConfig.nominal(),
        ecfg=EngineConfig(batcher=BatcherConfig(max_batch=16,
                                                bucket_sizes=(8, 16))))
    eng.submit_many([np.zeros(small_cfg.n_features, np.uint8)] * 11)
    eng.drain()
    s = eng.summary()
    assert s["requests"] == 11 and s["batches"] == 1
    assert s["padding_overhead"] == pytest.approx(5 / 16)
    assert s["p50_ms"] <= s["p95_ms"] <= s["p99_ms"]
    hw = s["hardware"]
    assert hw["latency_ns"] == pytest.approx(60.0)
    assert hw["energy_nj_per_dp"] > 0 and hw["top_j_inv"] > 0


# -------------------------------------------- coalesced pools (ISSUE 6)

def _coalesced_model(m=4, c=24, f=32):
    from repro.core.coalesced import CoalescedConfig
    cfg = CoalescedConfig(n_classes=m, n_clauses=c, n_features=f,
                          n_states=100)
    key = jax.random.PRNGKey(11)
    inc = jax.random.bernoulli(key, 0.08, (c, cfg.n_literals))
    ta = jnp.where(inc, cfg.n_states + 1, cfg.n_states).astype(
        cfg.state_dtype)
    w = jax.random.randint(jax.random.PRNGKey(12), (c, m), -5, 6,
                           jnp.int32)
    return cfg, ta, w


@pytest.mark.parametrize("engine_cls", [ServeEngine, AsyncServeEngine])
def test_coalesced_engine_matches_offline_forward(engine_cls):
    """A coalesced engine serves bit-exactly the offline weighted
    forward, on the packed fused kernel by default, with no fallback."""
    import warnings
    from repro.core import coalesced as co
    cfg, ta, w = _coalesced_model()
    x = np.asarray(jax.random.bernoulli(
        jax.random.PRNGKey(13), 0.4, (20, cfg.n_features)), dtype=np.uint8)
    ref = np.asarray(co.forward(ta, w, jnp.asarray(x), cfg))
    with warnings.catch_warnings():
        warnings.simplefilter("error")           # any fallback = failure
        eng = engine_cls.from_coalesced(ta, w, cfg)
    eng.submit_many(list(x))
    resps = eng.drain()
    np.testing.assert_array_equal(
        np.stack([r.class_sums for r in resps]), ref)
    assert [r.pred for r in resps] == list(np.argmax(ref, axis=-1))
    s = eng.summary()
    assert s["backend"] == "coalesced-pallas-packed2"
    assert s["packed_io"] and s["forward_fallbacks"] == []
    assert s["n_replicas"] == 1
    assert s["hardware"]["energy_nj_per_dp"] > 0


def test_coalesced_engine_unpacked_and_ensemble_routing():
    """packed=False lands on the unpacked fused kernel; 'ensemble'
    routing over the single shared chip degenerates to the argmax."""
    from repro.core import coalesced as co
    cfg, ta, w = _coalesced_model()
    x = np.asarray(jax.random.bernoulli(
        jax.random.PRNGKey(14), 0.4, (12, cfg.n_features)), dtype=np.uint8)
    ref = np.asarray(co.forward(ta, w, jnp.asarray(x), cfg))
    eng = ServeEngine.from_coalesced(
        ta, w, cfg, ecfg=EngineConfig(routing="ensemble", packed=False))
    eng.submit_many(list(x))
    resps = eng.drain()
    assert eng.summary()["backend"] == "coalesced-pallas"
    assert [r.pred for r in resps] == list(np.argmax(ref, axis=-1))


def test_coalesced_pool_surface_and_pytree():
    """CoalescedPool presents the ReplicaPool duck-type the engine
    drives, and survives tree_map with its config intact."""
    from repro.serve import CoalescedPool
    cfg, ta, w = _coalesced_model()
    pool = CoalescedPool(ta_state=ta, weights=w, cfg=cfg)
    assert pool.n_replicas == 1
    assert not (pool.vcfg.c2c or pool.vcfg.csa_offset or pool.vcfg.d2d)
    assert pool.include.shape == (cfg.n_clauses, cfg.n_literals)
    assert pool.router().n_replicas == 1
    st = pool.state()
    assert st.cfg == cfg and st.n_classes == cfg.n_classes
    pool2 = jax.tree_util.tree_map(lambda a: a, pool)
    assert type(pool2) is CoalescedPool and pool2.cfg == cfg
    with pytest.raises(ValueError, match="must match"):
        import dataclasses as _dc
        pool.state(_dc.replace(cfg, n_states=50))


def test_coalesced_engine_explicit_jnp_backend_no_fallback():
    """Pinning the GSPMD jnp path by name is honoured (it satisfies the
    capability floor), and the wire format follows the selection."""
    cfg, ta, w = _coalesced_model()
    eng = ServeEngine.from_coalesced(
        ta, w, cfg, ecfg=EngineConfig(backend="coalesced"))
    assert eng.backend.name == "coalesced"
    assert not eng.selection.fell_back and not eng.packed_io
