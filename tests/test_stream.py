"""Streaming KWS-6 serving tests (ISSUE 5).

The acceptance bar is **bit-exactness**: a ``StreamSession`` fed
frame-by-frame must produce per-window predictions identical to offline
batched ``api.predict`` over ``StreamingBooleanizer.transform_offline``
of the same frames, at ``VariationConfig.nominal()`` — for sync and
async engines, single-device and mesh-sharded (the sharded case runs in
a subprocess with 8 forced host devices, same pattern as
``test_serve_sharded.py``).  On top of that: chunking invariance of the
windower, vote smoothing determinism, session isolation on a shared
engine, and the per-session metrics block.
"""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.core import tm
from repro.core.booleanize import (StreamingBooleanizer, fit_quantile,
                                   fit_uniform)
from repro.core.tm import TMConfig
from repro.core.variations import VariationConfig
from repro.data.tm_datasets import kws6_windows, synthetic_kws6
from repro.serve import (AsyncServeEngine, BatcherConfig, EngineConfig,
                         ServeEngine, StreamConfig, StreamServer,
                         majority_vote)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

MELS, BITS, WINDOW, HOP, VOTE = 6, 2, 4, 2, 3


@pytest.fixture(scope="module")
def kws():
    """Small KWS-6 streaming fixture: booleanizer, TM at the stream
    shape (training-free sparse includes), and raw frame streams."""
    frames, labels = synthetic_kws6(jax.random.PRNGKey(0),
                                    n_utterances=8, n_frames=24,
                                    n_mels=MELS)
    booleanizer = fit_quantile(np.asarray(frames).reshape(-1, MELS),
                               bits=BITS)
    cfg = TMConfig(n_classes=6, clauses_per_class=6,
                   n_features=WINDOW * MELS * BITS, n_states=100)
    inc = jax.random.bernoulli(jax.random.PRNGKey(5), 0.1,
                               (cfg.n_clauses, cfg.n_literals))
    ta = jnp.where(inc, cfg.n_states + 1, cfg.n_states).astype(
        cfg.state_dtype)
    return dict(frames=np.asarray(frames), labels=np.asarray(labels),
                booleanizer=booleanizer, cfg=cfg, ta=ta)


def make_engine(kws, engine_cls=ServeEngine, **ecfg_kw):
    ecfg_kw.setdefault("batcher", BatcherConfig(max_batch=16,
                                                bucket_sizes=(8, 16)))
    return engine_cls.from_ta_state(
        kws["ta"], kws["cfg"], n_replicas=2, key=jax.random.PRNGKey(3),
        vcfg=VariationConfig.nominal(), ecfg=EngineConfig(**ecfg_kw))


def feed_stream(server, sid, stream, chunk):
    for lo in range(0, len(stream), chunk):
        server.feed(sid, stream[lo:lo + chunk])
        server.pump()
    server.drain()


# ------------------------------------------------- streaming booleanizer

def test_streaming_booleanizer_chunking_invariance(kws):
    """Any chunking of the stream — single frames, ragged chunks, one
    big push — emits exactly the offline window rows."""
    sb = StreamingBooleanizer(kws["booleanizer"], WINDOW, HOP)
    stream = kws["frames"].reshape(-1, MELS)[:50]
    offline = sb.transform_offline(stream)
    assert offline.shape == ((50 - WINDOW) // HOP + 1,
                             sb.n_boolean_features)
    for chunks in ([1] * 50, [3, 7, 1, 19, 20], [50], [5] * 10):
        sb2 = StreamingBooleanizer(kws["booleanizer"], WINDOW, HOP)
        rows, lo = [], 0
        for c in chunks:
            rows.append(sb2.push(stream[lo:lo + c]))
            lo += c
        np.testing.assert_array_equal(np.concatenate(rows), offline)
    # single [F] frame pushes work too
    sb3 = StreamingBooleanizer(kws["booleanizer"], WINDOW, HOP)
    rows = [sb3.push(f) for f in stream]
    np.testing.assert_array_equal(np.concatenate(rows), offline)


def test_streaming_booleanizer_hop_geometries(kws):
    """hop > window (gaps) and hop == window (tumbling) both stream
    correctly, and the ring buffer never grows past one window."""
    stream = kws["frames"].reshape(-1, MELS)[:40]
    for window, hop in ((3, 5), (4, 4), (1, 1), (5, 2)):
        sb = StreamingBooleanizer(kws["booleanizer"], window, hop)
        off = sb.transform_offline(stream)
        got = []
        for f in stream:
            got.append(sb.push(f))
            assert sb.frames_buffered <= max(window, hop)
        np.testing.assert_array_equal(np.concatenate(got), off)


def test_streaming_booleanizer_validates(kws):
    with pytest.raises(ValueError, match="window and hop"):
        StreamingBooleanizer(kws["booleanizer"], 0, 1)
    sb = StreamingBooleanizer(kws["booleanizer"], 4, 2)
    with pytest.raises(ValueError, match="frames"):
        sb.push(np.zeros((3, MELS + 1)))
    # short stream: no window yet, empty row block with the right width
    out = sb.push(np.zeros((2, MELS)))
    assert out.shape == (0, sb.n_boolean_features)
    sb.reset()
    assert sb.frames_buffered == 0


def test_streaming_matches_per_frame_booleanizer(kws):
    """The windower's bits are the plain Booleanizer's bits, windowed:
    row t == concat(transform(frame) for frame in window t)."""
    b = kws["booleanizer"]
    stream = kws["frames"].reshape(-1, MELS)[:12]
    sb = StreamingBooleanizer(b, WINDOW, HOP)
    rows = sb.transform_offline(stream)
    per_frame = np.asarray(b.transform(jnp.asarray(stream)))
    for t in range(rows.shape[0]):
        want = per_frame[t * HOP:t * HOP + WINDOW].reshape(-1)
        np.testing.assert_array_equal(rows[t], want)


# --------------------------------------------- bit-exactness vs offline

@pytest.mark.parametrize("engine_cls", [ServeEngine, AsyncServeEngine])
@pytest.mark.parametrize("routing", ["round_robin", "ensemble"])
def test_streamed_equals_offline_batched(kws, engine_cls, routing):
    """ACCEPTANCE: per-window streamed predictions == offline batched
    api.predict over the same windows, sync and async, routed and
    ensemble — and both equal the digital TM."""
    eng = make_engine(kws, engine_cls, routing=routing)
    server = StreamServer(eng, kws["booleanizer"],
                          StreamConfig(window=WINDOW, hop=HOP, vote=VOTE))
    stream = kws["frames"].reshape(-1, MELS)[:60]
    feed_stream(server, "u0", stream, chunk=5)
    sess = server.sessions["u0"]
    assert sess.backlog == 0
    streamed = np.array([d.pred for d in sess.decisions])

    sb = StreamingBooleanizer(kws["booleanizer"], WINDOW, HOP)
    rows = sb.transform_offline(stream)
    assert len(streamed) == len(rows)
    offline = np.asarray(api.predict(eng.state, jnp.asarray(rows)))
    np.testing.assert_array_equal(streamed, offline)
    digital = np.asarray(tm.predict(kws["ta"], jnp.asarray(rows),
                                    kws["cfg"]))
    np.testing.assert_array_equal(streamed, digital)


def test_coalesced_engine_streams_bit_exact(kws):
    """A coalesced engine serves KWS-6 streaming UNCHANGED (ISSUE 6):
    StreamServer/StreamSession are state-agnostic, so per-window
    streamed predictions == offline ``co.predict`` over the same
    windows, on the packed fused kernel with zero fallbacks."""
    from repro.core import coalesced as co
    ccfg = co.CoalescedConfig(n_classes=6, n_clauses=18,
                              n_features=WINDOW * MELS * BITS,
                              n_states=100)
    k1, k2 = jax.random.split(jax.random.PRNGKey(7))
    inc = jax.random.bernoulli(k1, 0.1, (ccfg.n_clauses, ccfg.n_literals))
    ta = jnp.where(inc, ccfg.n_states + 1,
                   ccfg.n_states).astype(ccfg.state_dtype)
    w = jax.random.randint(k2, (ccfg.n_clauses, ccfg.n_classes),
                           -ccfg.max_weight, ccfg.max_weight + 1,
                           jnp.int32)
    eng = ServeEngine.from_coalesced(
        ta, w, ccfg, ecfg=EngineConfig(batcher=BatcherConfig(
            max_batch=16, bucket_sizes=(8, 16))))
    assert eng.backend.name == "coalesced-pallas-packed2"
    assert not eng.selection.fell_back
    server = StreamServer(eng, kws["booleanizer"],
                          StreamConfig(window=WINDOW, hop=HOP, vote=VOTE))
    stream = kws["frames"].reshape(-1, MELS)[:60]
    feed_stream(server, "u", stream, chunk=5)
    streamed = np.array([d.pred
                         for d in server.sessions["u"].decisions])
    sb = StreamingBooleanizer(kws["booleanizer"], WINDOW, HOP)
    rows = sb.transform_offline(stream)
    assert len(streamed) == len(rows)
    offline = np.asarray(co.predict(ta, w, jnp.asarray(rows), ccfg))
    np.testing.assert_array_equal(streamed, offline)
    assert eng.summary()["forward_fallbacks"] == []


def test_sessions_share_engine_without_crosstalk(kws):
    """Three interleaved sessions on ONE engine: each session's stream
    reproduces its own offline predictions (no cross-wiring inside the
    shared batcher), and their windows really did batch together."""
    eng = make_engine(kws)
    server = StreamServer(eng, kws["booleanizer"],
                          StreamConfig(window=WINDOW, hop=HOP, vote=VOTE))
    streams = {f"u{i}": kws["frames"][i * 2:i * 2 + 2].reshape(-1, MELS)
               for i in range(3)}
    for lo in range(0, 48, HOP):                  # interleave hop-by-hop
        for sid, stream in streams.items():
            server.feed(sid, stream[lo:lo + HOP])
        server.pump()
    server.drain()
    sb = StreamingBooleanizer(kws["booleanizer"], WINDOW, HOP)
    for sid, stream in streams.items():
        rows = sb.transform_offline(stream)
        offline = np.asarray(api.predict(eng.state, jnp.asarray(rows)))
        got = np.array([d.pred for d in server.sessions[sid].decisions])
        np.testing.assert_array_equal(got, offline, err_msg=sid)
    s = eng.summary()
    total = sum(len(v.decisions) for v in server.sessions.values())
    assert s["requests"] == total
    assert s["mean_batch"] > 1.5          # cross-session batching happened


def test_chunking_does_not_change_decisions(kws):
    """Delivery granularity is irrelevant: frame-by-frame vs big-chunk
    feeds give identical decision streams (preds AND smoothed
    keywords)."""
    stream = kws["frames"].reshape(-1, MELS)[:40]
    outs = []
    for chunk in (1, 7, 40):
        eng = make_engine(kws)
        server = StreamServer(eng, kws["booleanizer"],
                              StreamConfig(window=WINDOW, hop=HOP,
                                           vote=VOTE))
        feed_stream(server, "u", stream, chunk)
        outs.append([(d.pred, d.keyword)
                     for d in server.sessions["u"].decisions])
    assert outs[0] == outs[1] == outs[2]


def test_streaming_keeps_engine_bookkeeping_bounded(kws):
    """Always-on hygiene: sessions consume Responses destructively
    (engine.take), so after collection the engine retains nothing, and
    a reset session's abandoned windows are discarded on arrival rather
    than retained forever."""
    eng = make_engine(kws)
    server = StreamServer(eng, kws["booleanizer"],
                          StreamConfig(window=WINDOW, hop=HOP, vote=VOTE))
    feed_stream(server, "u", kws["frames"].reshape(-1, MELS)[:60], 6)
    assert len(server.sessions["u"].decisions) > 0
    assert eng._results == {}                 # all taken by the session
    server.pump()                             # prune pass
    assert eng._submitted == []
    # reset with a backlog: windows are submitted but never collected
    sess = server.sessions["u"]
    sess.feed(kws["frames"].reshape(-1, MELS)[:20])
    assert sess.backlog > 0
    served_before = eng.metrics.valid_rows
    sess.reset()
    assert sess.backlog == 0
    # posterior + history are forgotten: a reset session is fresh
    assert sess.keyword is None and len(sess.decisions) == 0
    server.drain()                            # serves the abandoned rows
    assert eng.metrics.valid_rows > served_before   # still counted...
    assert eng._results == {} and eng._discard == set()  # ...not retained
    server.pump()
    assert eng._submitted == []


# ------------------------------------------------------- vote smoothing

def test_majority_vote_ties_and_counts():
    assert majority_vote([2, 2, 5]) == 2
    assert majority_vote([5]) == 5
    assert majority_vote([1, 3, 3, 1]) == 1      # tie -> lowest class
    assert majority_vote([4, 0, 4, 0, 4]) == 4


def test_decision_smoothing_is_majority_over_last_votes(kws):
    """Every decision's keyword == majority vote over the trailing
    ``vote`` raw preds (recomputed independently here), and the vote
    count ramps 1, 2, ..., vote."""
    eng = make_engine(kws)
    server = StreamServer(eng, kws["booleanizer"],
                          StreamConfig(window=WINDOW, hop=HOP, vote=VOTE))
    feed_stream(server, "u", kws["frames"].reshape(-1, MELS)[:60], 6)
    decisions = server.sessions["u"].decisions
    preds = [d.pred for d in decisions]
    for i, d in enumerate(decisions):
        trail = preds[max(0, i - VOTE + 1):i + 1]
        assert d.votes == len(trail)
        assert d.keyword == majority_vote(trail), i
        assert d.index == i


# ----------------------------------------------------- session metrics

def test_per_session_metrics_in_summary(kws):
    eng = make_engine(kws)
    server = StreamServer(eng, kws["booleanizer"],
                          StreamConfig(window=WINDOW, hop=HOP, vote=VOTE))
    for sid in ("a", "b"):
        feed_stream(server, sid, kws["frames"].reshape(-1, MELS)[:30], 10)
    s = server.summary()
    assert set(s["sessions"]) == {"a", "b"}
    for block in s["sessions"].values():
        assert block["decisions"] == len(server.sessions["a"].decisions)
        assert block["p50_ms"] >= 0 and block["p95_ms"] >= block["p50_ms"]
        # None (JSON null) until two decisions span clock time — never
        # NaN, which would break strict-JSON consumers of summary()
        assert block["decisions_per_s"] is None \
            or block["decisions_per_s"] > 0


def test_server_close_retires_session_state(kws):
    """Session churn hygiene: close() drops the session, its pending
    windows, and its metrics entry — a long-lived server with per-
    connection session ids must not accumulate state per closed id."""
    eng = make_engine(kws)
    server = StreamServer(eng, kws["booleanizer"],
                          StreamConfig(window=WINDOW, hop=HOP, vote=VOTE))
    for sid in ("keep", "gone"):
        feed_stream(server, sid, kws["frames"].reshape(-1, MELS)[:30], 10)
    server.session("gone").feed(kws["frames"].reshape(-1, MELS)[:20])
    closed = server.close("gone")
    assert closed is not None and len(closed.decisions) > 0
    assert closed.backlog == 0                  # pending discarded
    assert set(server.sessions) == {"keep"}
    server.drain()                              # abandoned rows served...
    assert eng._results == {}                   # ...but never retained
    s = server.summary()
    assert set(s["sessions"]) == {"keep"}       # metrics entry dropped
    assert server.close("gone") is None         # idempotent
    # a plain (non-streaming) engine summary carries no sessions noise
    assert "sessions" not in make_engine(kws).summary()


def test_stream_config_validates():
    with pytest.raises(ValueError, match="window, hop and vote"):
        StreamConfig(window=0)
    with pytest.raises(ValueError, match="window, hop and vote"):
        StreamConfig(vote=0)


def test_fit_uniform_windower_also_roundtrips(kws):
    """The windower is booleanizer-agnostic: a uniform-threshold fit
    streams == offline too."""
    b = fit_uniform(kws["frames"].reshape(-1, MELS), bits=3)
    sb = StreamingBooleanizer(b, 3, 3)
    stream = kws["frames"].reshape(-1, MELS)[:20]
    got = np.concatenate([sb.push(f) for f in stream])
    np.testing.assert_array_equal(got, sb.transform_offline(stream))


def test_kws6_windows_labels_follow_utterances(kws):
    sb = StreamingBooleanizer(kws["booleanizer"], WINDOW, HOP)
    rows, ys = kws6_windows(kws["frames"][:4], kws["labels"][:4], sb)
    per_utt = (24 - WINDOW) // HOP + 1
    assert rows.shape == (4 * per_utt, sb.n_boolean_features)
    np.testing.assert_array_equal(
        ys, np.repeat(kws["labels"][:4], per_utt))


# ---------------------------------------------------- mesh-sharded e2e

@pytest.mark.slow
def test_streamed_equals_offline_on_sharded_mesh():
    """ACCEPTANCE (mesh half): the same bit-exactness on a replica pool
    sharded over 8 forced host devices, sync and async.  Subprocess
    because XLA_FLAGS must be set before jax initializes — the same
    pattern as test_serve_sharded.py."""
    code = """
        import numpy as np
        import jax
        import jax.numpy as jnp
        from repro import api
        from repro.core.booleanize import StreamingBooleanizer, fit_quantile
        from repro.core.tm import TMConfig
        from repro.core.variations import VariationConfig
        from repro.data.tm_datasets import synthetic_kws6
        from repro.launch.mesh import make_replica_mesh
        from repro.serve import (AsyncServeEngine, BatcherConfig,
                                 EngineConfig, ServeEngine, StreamConfig,
                                 StreamServer)

        assert jax.device_count() == 8, jax.device_count()
        MELS, BITS, WINDOW, HOP = 6, 2, 4, 2
        frames, _ = synthetic_kws6(jax.random.PRNGKey(0), n_utterances=8,
                                   n_frames=24, n_mels=MELS)
        booleanizer = fit_quantile(
            np.asarray(frames).reshape(-1, MELS), bits=BITS)
        cfg = TMConfig(n_classes=6, clauses_per_class=6,
                       n_features=WINDOW * MELS * BITS, n_states=100)
        inc = jax.random.bernoulli(jax.random.PRNGKey(5), 0.1,
                                   (cfg.n_clauses, cfg.n_literals))
        ta = jnp.where(inc, cfg.n_states + 1,
                       cfg.n_states).astype(cfg.state_dtype)
        stream = np.asarray(frames).reshape(-1, MELS)[:48]
        sb = StreamingBooleanizer(booleanizer, WINDOW, HOP)
        rows = sb.transform_offline(stream)
        mesh = make_replica_mesh(8, 1)
        single = ServeEngine.from_ta_state(
            ta, cfg, n_replicas=8, key=jax.random.PRNGKey(3),
            vcfg=VariationConfig.nominal(),
            ecfg=EngineConfig(routing="ensemble"))
        offline_single = np.asarray(api.predict(single.state,
                                                jnp.asarray(rows)))
        for cls in (ServeEngine, AsyncServeEngine):
            eng = cls.from_ta_state(
                ta, cfg, n_replicas=8, key=jax.random.PRNGKey(3),
                vcfg=VariationConfig.nominal(),
                ecfg=EngineConfig(routing="ensemble",
                                  batcher=BatcherConfig(
                                      max_batch=16, bucket_sizes=(8, 16))),
                mesh=mesh)
            assert eng.state.is_sharded
            server = StreamServer(eng, booleanizer,
                                  StreamConfig(window=WINDOW, hop=HOP,
                                               vote=3))
            for lo in range(0, len(stream), 5):
                server.feed("u", stream[lo:lo + 5])
                server.pump()
            server.drain()
            streamed = np.array(
                [d.pred for d in server.sessions["u"].decisions])
            offline = np.asarray(api.predict(eng.state, jnp.asarray(rows)))
            np.testing.assert_array_equal(streamed, offline,
                                          err_msg=cls.__name__)
            # the mesh changes placement, never predictions
            np.testing.assert_array_equal(streamed, offline_single,
                                          err_msg=cls.__name__)
        print("OK sharded stream")
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=900)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    assert "OK sharded stream" in out.stdout
