"""The IMBUE serving engine: requests in, deadline-batched analog reads out.

Layering (ISSUE 2: unified backend API; ISSUE 3: packed datapath +
measured autotuning):

  submit() -> DynamicBatcher — in packed mode the request is packed to
              uint32 literal words HERE, once; the queue and every
              host->device transfer carry ``[bucket, L/32]`` words
           -> RouterState routing (round-robin / least-loaded / ensemble)
           -> ONE fused jit'd dispatch per batch: the capability-selected
              ``repro.api`` backend (``analog-pallas-packed`` by default,
              measured (ct, kt) tiles from the registry tuning table),
              plus the argmax / ensemble vote — no per-dispatch eager ops
           -> Response records + metrics accounting (incl. bytes moved).

The backend is capability-selected once at construction
(``select_backend``); a fallback (e.g. csa_offset forcing the jnp path,
which also forfeits packed io) is surfaced LOUDLY in ``ServeMetrics``.
Bucket ladders come from the measured per-backend tuning table
(``kernels/autotune.py`` -> ``api.get_tuning``) whenever the batcher
config was built by ``BatcherConfig.for_max_batch``.

The engine is synchronous and single-threaded by design: ``pump()`` cuts
and dispatches every due batch, so callers drive it from their own event
loop (the CLI in ``launch/serve.py``), a benchmark harness, or tests.
An injectable ``clock`` makes deadline behaviour fully deterministic
under test.  Every analog read draws its noise from one engine-owned
PRNG key, so a fixed seed gives bit-reproducible serving traces.

ISSUE 4 makes the engine device-parallel and latency-hiding:

* **sharded pools** — pass ``mesh=`` (see ``launch.mesh.
  make_replica_mesh`` / ``--mesh`` on the CLI) and the pool is placed
  with ``pool.shard(mesh, rules)``: the programmed ``[R, C, L]`` stack
  splits over the ``replica`` mesh axis, so one fused ensemble dispatch
  spans every device instead of one.  Capability selection extends to
  ``CAP_SHARDED``: a partitioned state only matches backends declared
  safe under ``NamedSharding`` (the GSPMD jnp paths) and any other
  preference falls back LOUDLY, exactly like ``csa_offset``.
* **overlapped host batching** — :class:`AsyncServeEngine` double-
  buffers dispatches: a batch's jit'd call is *issued* without blocking
  (JAX dispatch is async; results are device futures) and only
  *collected* — ``jax.block_until_ready`` — once ``max_in_flight``
  later batches have been issued or at drain.  Host-side
  packing/bucketing of batch N+1 therefore proceeds while batch N is in
  flight; ``ServeMetrics`` reports the per-dispatch host-pack vs
  blocked-device-wait split and the resulting ``overlap_fraction``.
  The synchronous ``ServeEngine`` collects immediately (single-device
  behavior is unchanged by default).

ISSUE 7 makes the pool *live*: "program once, read forever" becomes
"re-program live, keep reading".

* **versioned pools** — the pool carries a monotonic model ``version``
  (bumped by ``pool.reprogram``); every :class:`Response` and
  :class:`RequestRecord` records the version that served it.  A batch's
  version is captured once at issue, so no batch ever mixes versions by
  construction.
* **atomic install** — :meth:`ServeEngine.install_pool` swaps the
  serving pool between dispatches: it first :meth:`quiesce`\\ s (waits
  for in-flight async batches to collect), then replaces the state and
  replica slices in one step.  Queued-but-undispatched requests are NOT
  dropped — they serve at the new version.  Routing counters, metrics,
  the PRNG stream, backend selection and every compiled kernel survive
  (same shapes and static configs ⇒ jit cache hits), so a swap costs
  one pipeline drain, not a recompile.
* **canary dispatch** — :meth:`arm_canary` mounts a freshly programmed
  candidate chip *beside* the stable pool (the include plane is shared
  per pool, so a half-reprogrammed pool is not representable — the
  canary rides as its own single-chip state addressed by the routing
  override).  A deterministic accumulator routes ``fraction`` of
  batches to it; each canary batch is additionally shadow-evaluated on
  the stable pool with the SAME read key, and the argmax agreement
  lands in ``ServeMetrics`` — the promote/rollback evidence
  (``serve/swap.py`` orchestrates snapshot → canary → promote/rollback).
"""

from __future__ import annotations

import dataclasses
import time
import warnings
from collections import deque
from typing import Callable, Deque, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro import api
from repro.api.registry import CAP_FUSED_KERNEL, CAP_PACKED_IO
from repro.core import tm
from repro.core.imbue import IMBUEConfig
from repro.core.tm import TMConfig
from repro.core.variations import VariationConfig
from repro.kernels import ops
from repro.serve.batching import (QOS_BULK, Batch, BatcherConfig,
                                  DynamicBatcher, QueueFull,
                                  pack_request_np, validate_qos)
from repro.serve.health import HealthConfig, HealthProbe
from repro.serve.metrics import (SPANS, RequestRecord, ServeMetrics,
                                 hardware_figures)
from repro.serve.replica import ReplicaPool, RouterState, ensemble_vote, \
    program_replica_pool

ENSEMBLE = -1      # Response.replica value when every chip voted
CANARY = -2        # Response.replica value when the canary chip served
EXPIRED = -3       # Response.replica value when the deadline expired queued

# The engine's default backend preferences: the fused Pallas kernel with
# single-dispatch replica vmap — packed literal wire when the pool state
# is packed (EngineConfig.packed, the default), unpacked otherwise.
# Capability selection overrides either when the pool's noise model
# needs physics the kernel doesn't implement.  Sharded (mesh) pools
# default straight to the GSPMD-partitioned jnp path: the Pallas
# kernels are single-device custom calls and do not declare
# CAP_SHARDED, so preferring them would only produce a (correct, loud)
# fallback warning on every construction.
DEFAULT_BACKEND = "analog-pallas"
DEFAULT_PACKED_BACKEND = "analog-pallas-packed"
DEFAULT_PLANES_BACKEND = "analog-pallas-packed2"
DEFAULT_SHARDED_BACKEND = "analog-jnp"
# Coalesced pools get the same ladder in their own backend family: the
# fused weighted-tail kernel, its packed-wire variant, and the GSPMD
# jnp path ("coalesced") for class-sharded weights.
DEFAULT_COALESCED_BACKEND = "coalesced-pallas"
DEFAULT_COALESCED_PACKED_BACKEND = "coalesced-pallas-packed"
DEFAULT_COALESCED_PLANES_BACKEND = "coalesced-pallas-packed2"
DEFAULT_COALESCED_SHARDED_BACKEND = "coalesced"


def _resident_model_nbytes(state, backend: "api.Backend",
                           columns: Optional[str] = None) -> int:
    """Programmed-model operand bytes the forward streams from HBM for
    ONE dispatch of ``state`` under ``backend``.

    Dense analog paths stream two f32 planes (conductance + leak) per
    programmed cell; coalesced paths stream the include plane (uint32
    bitplane when packed); plane-packed states stream the uint32 index
    bitplane plus the optional f32 deviation plane — ISSUE 9's resident
    reduction, surfaced as ``resident_bytes_per_dispatch``.  A read
    whose column operand is programmed (``columns``, from the backend's
    ``column_operand``) streams that f32 operand alone instead; one that
    builds it in its own graph reads the planes."""
    caps = backend.capabilities
    if api.CAP_PACKED_PLANES in caps and getattr(state, "plane_packed",
                                                 False):
        if columns == ops.COLUMNS_PROGRAMMED:
            return 4 * (int(state.col_diff_t.size)
                        + int(state.col_leak_t.size))
        n = int(state.plane_index.size) * 4
        dev = getattr(state, "plane_dev", None)
        if dev is not None:
            n += int(dev.size) * 4
        return n
    if isinstance(state, api.CoalescedState):
        if api.CAP_PACKED_IO in caps and state.packed:
            return int(state.include_packed.size) * 4
        return int(state.include.size) * 4
    r = getattr(state, "r_stack", None)
    if r is None:
        r = getattr(state, "r_mem", None)
    if r is None:                        # DigitalState: the include plane
        return int(state.include.size) * 4
    return 2 * int(r.size) * 4


class _ReplicaViews:
    """Single-replica views ``[1, C, L]`` of a stack state for routed
    dispatch, each sliced on its first use and kept: an ensemble pool
    dispatches the whole stack and reads a view only for a health probe
    or a canary, so it holds no second copy of its planes until then."""

    def __init__(self, state, n: int):
        self._state = state
        self._views: List = [None] * n

    def __getitem__(self, i: int):
        if self._views[i] is None:
            self._views[i] = self._state.replica_slice(i)
        return self._views[i]


def _replica_views(state, n: int):
    """Routed dispatch's per-replica states: sliced views of a stack,
    or ``state`` itself for every route of a single shared chip."""
    if hasattr(state, "replica_slice"):
        return _ReplicaViews(state, n)
    return [state] * n


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Serving policy knobs."""

    batcher: BatcherConfig = BatcherConfig()
    routing: str = "round_robin"     # round_robin | least_loaded | ensemble
    ensemble_mode: str = "majority"  # majority | sum (see ensemble_vote)
    # Prefer the packed uint32 literal wire format: the pool state gets
    # a packed include plane and (absent an explicit backend preference)
    # selection lands on the packed_io kernels.  Bit-exact vs unpacked;
    # turn off to force the dense uint8 datapath.
    packed: bool = True
    # Plane-packed resident model (ISSUE 9): after packing, fold the
    # programmed conductance stack into an LRS/HRS index bitplane (+ a
    # per-cell deviation plane when the pool is off-nominal) so the
    # fused kernels stream ~64x fewer resident bytes per dispatch at
    # nominal.  Bit-exact vs the dense planes; only takes effect when
    # ``packed`` is also on (plane packing implies the packed wire).
    pack_planes: bool = True
    # Backend *preference* for the forward path (repro.api registry name).
    # None -> DEFAULT_PACKED_BACKEND / DEFAULT_BACKEND (per ``packed``).
    # Selection is capability-checked against the pool's
    # VariationConfig: e.g. the fused kernels sense against a scalar
    # reference and do not model the per-column CSA offset, so a
    # csa_offset-enabled pool falls back to `analog-jnp` — and the
    # engine records that switch in ServeMetrics instead of hiding it.
    backend: Optional[str] = None
    # DEPRECATED (one release): the old boolean kernel toggle.  True maps
    # to backend="analog-pallas", False to "analog-jnp".
    use_kernel: Optional[bool] = None
    interpret: Optional[bool] = None  # None -> interpret off-TPU
    # AsyncServeEngine only: how many dispatched batches may be in
    # flight (un-collected device futures) at once.  2 = classic double
    # buffering — pack batch N+1 while batch N computes.
    max_in_flight: int = 2
    # Shape-aware autotuning (ISSUE 5): the tuning table is keyed by
    # (backend, shape bucket), so an engine whose model shape has no
    # measured entry gets DEFAULT tiles/buckets rather than another
    # shape's.  With lazy_tune=True the engine measures the missing
    # entry ONCE at construction (a small tile/bucket sweep,
    # ``kernels.autotune.ensure_tuning``) and registers it for every
    # later engine at the same (backend, bucket).  Off by default:
    # measurement costs seconds of kernel compiles, which tests and
    # short-lived engines shouldn't pay — streaming deployments
    # (``launch/stream.py``, ``benchmarks/stream_bench.py``) turn it on.
    lazy_tune: bool = False
    # Admission control (ISSUE 8): queued-but-undispatched requests the
    # engine will hold before ``submit()`` raises :class:`QueueFull`.
    # None (default) keeps the unbounded legacy behavior.  Rejections
    # are metered (``summary()['rejected']``).
    max_queue_depth: Optional[int] = None
    # Health probing (ISSUE 8): a HealthConfig here commits probe
    # vectors at construction (``engine.health``) so ``probe()`` works
    # immediately; None leaves probing opt-in via ``enable_health()``.
    # Probing never happens spontaneously — ``pump()`` is pure serving.
    health: Optional[HealthConfig] = None

    def backend_preference(self) -> Optional[str]:
        """The explicit preference, or None for the packed-aware default."""
        if self.use_kernel is not None:
            warnings.warn(
                "EngineConfig.use_kernel is deprecated; set "
                "EngineConfig.backend to a repro.api backend name "
                "('analog-pallas' / 'analog-jnp')",
                DeprecationWarning, stacklevel=2)
            if self.backend is not None:
                raise ValueError("set EngineConfig.backend or the "
                                 "deprecated use_kernel, not both")
            return "analog-pallas" if self.use_kernel else "analog-jnp"
        return self.backend


@dataclasses.dataclass
class Response:
    """One served prediction."""

    rid: int
    pred: int
    class_sums: np.ndarray           # [M] (summed over chips in ensemble)
    replica: int                     # serving chip, ENSEMBLE/CANARY/EXPIRED
    latency_s: float
    version: int = 0                 # pool model generation that served it
    # True when the request's deadline_s elapsed while still queued: it
    # was never dispatched (pred == -1, zero sums) rather than silently
    # served late (ISSUE 8).
    expired: bool = False


@dataclasses.dataclass
class InFlight:
    """One issued-but-not-collected dispatch: the device futures of a
    batch's fused forward call plus the timestamps the overlap
    accounting needs.  ``sums``/``preds`` are lazy jax arrays until
    :meth:`ServeEngine._collect` blocks on them."""

    batch: Batch
    seq: int                         # dispatch sequence number: the
                                     # ``batch`` arg of its spans and its
                                     # dispatch_log entry
    sums: jax.Array                  # [bucket, M] device future
    preds: jax.Array                 # [bucket] device future
    replica: int                     # serving chip, or ENSEMBLE
    t_dispatch: float                # clock at dispatch start
    t_issue: float                   # clock right after the jit call
    # Engine-cumulative blocked-wait seconds at issue time: lets the
    # collect side subtract OTHER batches' block_until_ready stalls
    # from this batch's in-flight window, so overlap_fraction only
    # counts time the host spent doing productive work.
    blocked_snapshot: float = 0.0
    # Pool model generation serving this batch, captured at issue — a
    # later install_pool cannot retroactively change it, so no batch
    # ever mixes versions.
    version: int = 0
    # Canary batches only: the stable pool's predictions on the SAME
    # rows with the SAME read key (device future), for the agreement
    # comparison at collect time.
    shadow_preds: Optional[jax.Array] = None
    # Resident-model operand bytes this dispatch streamed from HBM
    # (see _resident_model_nbytes); lands in ServeMetrics at collect.
    resident_nbytes: int = 0

    def outputs(self) -> tuple:
        """The device outputs the host fetches: sums, preds, and a
        canary batch's shadow predictions."""
        return (self.sums, self.preds) if self.shadow_preds is None \
            else (self.sums, self.preds, self.shadow_preds)


@dataclasses.dataclass
class _Canary:
    """One armed canary: a dispatchable single-chip state riding beside
    the stable pool, its candidate version, and its traffic share."""

    state: object                    # [1, C, L]-shaped dispatchable state
    version: int
    fraction: float


class ServeEngine:
    """Dynamic-batching inference engine over a crossbar replica pool."""

    def __init__(
        self,
        pool: ReplicaPool,
        tm_cfg: TMConfig,
        ecfg: EngineConfig = EngineConfig(),
        *,
        key: jax.Array | None = None,
        clock: Callable[[], float] = time.monotonic,
        mesh=None,
        rules=None,
    ):
        # Device-parallel pools: shard the [R, C, L] stack over the
        # mesh's replica axis BEFORE anything reads it; the shared
        # include planes replicate.  Routing/ensemble semantics and the
        # per-seed noise stream are placement-independent.
        self.mesh = mesh
        if mesh is not None:
            from repro.distributed.sharding import replica_rules
            rules = rules if rules is not None else replica_rules(mesh)
            pool = pool.shard(mesh, rules)
        self.rules = rules
        self.pool = pool
        self.tm_cfg = tm_cfg
        self.ecfg = ecfg
        self.clock = clock
        self.metrics = ServeMetrics()
        self.router: RouterState = pool.router()
        # ReplicaStackState for crossbar pools, CoalescedState for
        # CoalescedPool — everything downstream goes through the
        # capability-selected backend, so the engine never branches on
        # the concrete state type outside selection defaults.
        self.state = pool.state(tm_cfg)
        if ecfg.packed:
            self.state = self.state.pack()
            # Plane-pack after packing (the index bitplane IS the packed
            # include plane).  Sharded pools skip it: the packed2
            # kernels are single-device custom calls, so a mesh engine
            # would only buy a loud fallback.
            if ecfg.pack_planes and not self.state.is_sharded and \
                    hasattr(self.state, "pack_planes"):
                self.state = self.state.pack_planes()
        self._key = key if key is not None else jax.random.PRNGKey(0)
        self._noise_free = not (pool.vcfg.c2c or pool.vcfg.csa_offset)
        # Capability-based backend selection, once, up front.  The noise
        # model is static per engine, so the choice is too; a fallback
        # (preference rejected) is surfaced immediately and accounted per
        # dispatch in ServeMetrics.
        sel_key = None if self._noise_free else self._key
        if isinstance(self.state, api.CoalescedState):
            default = (DEFAULT_COALESCED_SHARDED_BACKEND
                       if self.state.is_sharded
                       else DEFAULT_COALESCED_PLANES_BACKEND
                       if self.state.plane_packed
                       else DEFAULT_COALESCED_PACKED_BACKEND
                       if self.state.packed
                       else DEFAULT_COALESCED_BACKEND)
        else:
            default = (DEFAULT_SHARDED_BACKEND if self.state.is_sharded
                       else DEFAULT_PLANES_BACKEND
                       if self.state.plane_packed
                       else DEFAULT_PACKED_BACKEND if self.state.packed
                       else DEFAULT_BACKEND)
        prefer = ecfg.backend_preference() or default
        self.selection: api.Selection = api.select_backend(
            self.state, key=sel_key, prefer=prefer)
        self.backend: api.Backend = self.selection.backend
        if self.selection.fell_back:
            warnings.warn(
                f"serve backend fallback: {self.selection.fallback_reason} "
                "(noise semantics differ from the preferred backend; see "
                "engine.summary()['forward_fallbacks'])", stacklevel=2)
        # Wire format follows the SELECTED backend: a fallback off the
        # packed kernel also falls back to the dense uint8 queue.
        self.packed_io = CAP_PACKED_IO in self.backend.capabilities
        # Measured per-backend tuning (kernels/autotune.py): kernel tiles
        # for every dispatch; bucket ladder when the batcher config was
        # built by for_max_batch (auto_tune) rather than hand-picked.
        # Keyed by (backend, shape bucket) since ISSUE 5 — this engine's
        # model shape only ever consumes tiles measured at a matching
        # shape, falling back to defaults (or, with ecfg.lazy_tune, one
        # lazy measurement) for unseen shapes.
        self.shape_key: str = api.shape_bucket_key(tm_cfg.n_clauses,
                                                   tm_cfg.n_literals)
        self.tuning: Optional[dict] = api.get_tuning(
            self.backend.name, shape_key=self.shape_key)
        if (self.tuning is None and ecfg.lazy_tune
                and CAP_FUSED_KERNEL in self.backend.capabilities):
            from repro.kernels.autotune import ensure_tuning
            self.tuning = ensure_tuning(self.backend, tm_cfg)
        bcfg = ecfg.batcher
        if bcfg.auto_tune and self.tuning and \
                self.tuning.get("bucket_sizes"):
            bcfg = bcfg.with_tuned_buckets(self.tuning["bucket_sizes"],
                                           self.backend.name)
        self.batcher = DynamicBatcher(bcfg, packed=self.packed_io)
        tiles = (self.tuning or {}).get("tiles") or {}
        self._tiles: Dict[str, int] = {
            name: int(tiles[name]) for name in ("ct", "kt")
            if name in tiles}
        # Pre-sliced single-replica states for routed dispatch (all share
        # one [1, C, L] shape -> one compiled kernel for every chip) and
        # ONE fused jit'd forward covering backend + argmax/vote.  A
        # coalesced pool has exactly one shared chip: every route lands
        # on the full state.
        self._slices = _replica_views(self.state, pool.n_replicas)
        self._refresh_resident_nbytes()
        self._fwd = self._build_forward()
        self._next_rid = 0
        self._submitted: List[int] = []
        self._results: Dict[int, Response] = {}
        # Streaming hygiene (ISSUE 5): rids consumed via take()/discard()
        # are pruned from _submitted on the next pump/drain, so an
        # always-on front-end doesn't grow engine bookkeeping forever.
        self._taken: set = set()
        self._discard: set = set()
        self._blocked_s = 0.0           # cumulative block_until_ready time
        self._n_dispatched = 0          # last dispatch sequence number
        # Live hot-swap state (ISSUE 7): the armed canary (None when
        # plain serving) and its deterministic traffic accumulator.
        self._canary: Optional[_Canary] = None
        self._canary_acc = 0.0
        # Health + quarantine (ISSUE 8).  The vote mask is a TRACED
        # argument of the fused forward ([R] bool — all-True is
        # bit-identical to the unmasked vote), so quarantining a chip
        # never recompiles a kernel; the single-chip mask serves routed
        # slice/canary dispatches.  The health PRNG stream is separate
        # from the serving stream, so probing never perturbs the
        # bit-reproducible serving noise trace.
        self._healthy_mask = jnp.ones(pool.n_replicas, bool)
        self._mask_one = jnp.ones(1, bool)
        self.health: Optional[HealthProbe] = None
        self._health_key = jax.random.PRNGKey(0)
        if ecfg.health is not None:
            self.enable_health(ecfg.health)

    def _build_forward(self):
        """One jit'd callable per engine: backend forward + prediction.

        Folding the argmax (or ensemble vote) into the same jit removes
        every per-dispatch eager op from the hot path; ``bt`` is static,
        so each bucket size compiles once and is then cache-hit.
        """
        backend = self.backend
        fused = CAP_FUSED_KERNEL in backend.capabilities
        kernel_opts: Dict[str, object] = {}
        if fused:
            kernel_opts = dict(self._tiles, interpret=self.ecfg.interpret)
        routing = self.ecfg.routing
        mode = self.ecfg.ensemble_mode

        def fwd(state, lits, key, mask, *, bt):
            # ``mask`` ([R] bool, traced) is the quarantine vote mask:
            # all-True reproduces the unmasked path bit-for-bit (integer
            # one-hot votes / exact sums), so a healthy engine is
            # byte-stable vs pre-fault builds and flipping a chip out
            # never recompiles.
            opts = dict(kernel_opts, bt=bt) if fused else {}
            sums = backend.fn(state, lits, key, **opts)  # [R,B,M] | [B,M]
            if sums.ndim == 3:                   # replica-stacked output
                if routing == "ensemble":
                    preds = ensemble_vote(sums, mode, mask=mask)
                    sums = jnp.where(mask[:, None, None], sums,
                                     0).sum(axis=0)
                else:
                    sums = sums[0]
                    preds = jnp.argmax(sums, axis=-1)
            else:            # single-chip [B, M] (coalesced shared pool):
                preds = jnp.argmax(sums, axis=-1)    # ensemble == argmax
            return sums, preds

        return jax.jit(fwd, static_argnames=("bt",))

    @classmethod
    def from_ta_state(
        cls,
        ta_state: jax.Array,
        tm_cfg: TMConfig,
        *,
        n_replicas: int = 1,
        key: jax.Array | None = None,
        vcfg: VariationConfig = VariationConfig(),
        icfg: IMBUEConfig = IMBUEConfig(),
        ecfg: EngineConfig = EngineConfig(),
        clock: Callable[[], float] = time.monotonic,
        mesh=None,
        rules=None,
    ) -> "ServeEngine":
        """Program a fresh pool from trained TA state and wrap an engine.

        Programming happens BEFORE placement, so a ``mesh``-sharded
        engine serves bit-identical responses to the single-device
        engine at the same seed."""
        key = key if key is not None else jax.random.PRNGKey(0)
        k_prog, k_serve = jax.random.split(key)
        pool = program_replica_pool(tm.include_mask(ta_state, tm_cfg),
                                    k_prog, n_replicas, vcfg, icfg)
        return cls(pool, tm_cfg, ecfg, key=k_serve, clock=clock,
                   mesh=mesh, rules=rules)

    @classmethod
    def from_coalesced(
        cls,
        ta_state: jax.Array,
        weights: jax.Array,
        cfg,                             # CoalescedConfig
        *,
        ecfg: EngineConfig = EngineConfig(),
        key: jax.Array | None = None,
        clock: Callable[[], float] = time.monotonic,
        mesh=None,
        rules=None,
    ) -> "ServeEngine":
        """Serve a trained coalesced model: one shared clause pool, the
        weighted digital tail as the combine matrix.

        The engine surface is unchanged — submit/pump/drain, streaming
        sessions, metrics — only the pool behind it is a single-chip
        :class:`~repro.serve.replica.CoalescedPool`.  A ``mesh`` shards
        the ``[C, M]`` weights class axis (class-parallel GSPMD path,
        backend ``"coalesced"``)."""
        from repro.serve.replica import CoalescedPool
        pool = CoalescedPool(ta_state=jnp.asarray(ta_state),
                             weights=jnp.asarray(weights), cfg=cfg)
        return cls(pool, cfg, ecfg, key=key, clock=clock,
                   mesh=mesh, rules=rules)

    # --------------------------------------------------------------- intake

    def submit(self, x: np.ndarray, *,
               deadline_s: Optional[float] = None,
               qos: str = QOS_BULK) -> int:
        """Queue one request (``[F]`` Boolean features); returns its id.

        ``deadline_s`` (ISSUE 8) is a *request* deadline relative to
        now: if it elapses while the request is still queued, the
        request is never dispatched and resolves to a ``Response`` with
        ``expired=True`` (pred ``-1``) instead of silently serving
        late.  (Distinct from the batcher's ``max_wait_s``, which only
        shapes batch cutting.)  With ``EngineConfig.max_queue_depth``
        set, a full queue raises :class:`QueueFull` — the typed
        admission-control rejection — and the rejection is metered.

        ``qos`` (ISSUE 10) picks the request's deadline class:
        ``"latency"`` requests cut (small) batches early and are popped
        first among ready queues; ``"bulk"`` (the default — the exact
        pre-QoS behaviour) waits out the full ``max_wait_s`` to ride
        large buckets.  Per-class ``BatcherConfig`` depth limits reject
        a full class with :class:`QueueFull` without touching the other.
        """
        with TraceAnnotation(SPANS["submit"]):
            validate_qos(qos)
            if (self.ecfg.max_queue_depth is not None
                    and len(self.batcher) >= self.ecfg.max_queue_depth):
                self.metrics.note_rejected(qos=qos)
                raise QueueFull(
                    f"queue depth {len(self.batcher)} is at "
                    f"max_queue_depth={self.ecfg.max_queue_depth}; retry "
                    "after pump() or raise the limit")
            class_depth = self.batcher.cfg.queue_depth_for(qos)
            if (class_depth is not None
                    and self.batcher.depth(qos) >= class_depth):
                self.metrics.note_rejected(qos=qos)
                raise QueueFull(
                    f"{qos} class depth {self.batcher.depth(qos)} is at its "
                    f"per-class limit {class_depth}; retry after pump() or "
                    "raise the limit")
            rid = self._next_rid
            self._next_rid += 1
            t_pack = time.perf_counter()
            self.batcher.submit(rid, x, self.clock(), deadline_s=deadline_s,
                                qos=qos)
            self.metrics.note_pack(time.perf_counter() - t_pack)
            self._submitted.append(rid)
            return rid

    def submit_many(self, xs: Sequence[np.ndarray], *,
                    deadline_s: Optional[float] = None,
                    qos: str = QOS_BULK) -> List[int]:
        return [self.submit(x, deadline_s=deadline_s, qos=qos)
                for x in xs]

    # ------------------------------------------------------------- serving

    def _reap_expired(self, now: Optional[float] = None) -> None:
        """Resolve queued requests whose deadline has passed: each gets
        an ``expired=True`` Response (never dispatched) and a metrics
        tick.  Requests already abandoned via :meth:`discard` are
        dropped without a retained Response, matching the served path."""
        if now is None:
            now = self.clock()
        for req in self.batcher.reap_expired(now):
            self.metrics.note_expired(qos=req.qos)
            if req.rid in self._discard:
                self._discard.discard(req.rid)
                continue
            self._results[req.rid] = Response(
                rid=req.rid, pred=-1,
                class_sums=np.zeros(self.tm_cfg.n_classes, np.int32),
                replica=EXPIRED, latency_s=now - req.t_enqueue,
                version=self.pool.version, expired=True)

    def pump(self, force: bool = False) -> int:
        """Cut and dispatch every due batch; returns #requests served.

        Expiry is re-checked at EVERY cut with the same clock reading
        the cut uses: dispatches take real time, so during a multi-batch
        drain a still-queued request's deadline can pass between cuts —
        it must resolve ``expired=True``, never dispatch late (the
        batcher's cut paths also reap internally, making the invariant
        hold for direct ``cut(force=True)`` callers)."""
        with TraceAnnotation(SPANS["pump"]):
            self._prune_consumed()
            served = 0
            while True:
                with TraceAnnotation(SPANS["cut"]):
                    now = self.clock()
                    self._reap_expired(now)
                    batch = self.batcher.cut(now, force=force)
                if batch is None:
                    break
                self._dispatch(batch)
                served += batch.n_valid
            self._collect_ready()
            return served

    def drain(self) -> List[Response]:
        """Force-serve everything queued; responses in submission order
        (excluding responses already consumed by :meth:`take` /
        :meth:`discard` — the streaming front-end's path)."""
        self.pump(force=True)
        self._collect_pending()
        return [self._results[rid] for rid in self._submitted
                if rid in self._results]

    def _prune_consumed(self) -> None:
        """Drop bookkeeping for rids consumed via take()/discard(), so
        long-running streaming keeps _submitted bounded by the backlog."""
        if self._taken:
            self._submitted = [r for r in self._submitted
                               if r not in self._taken]
            self._taken.clear()

    def result(self, rid: int) -> Optional[Response]:
        if rid not in self._results:
            self._collect_pending()
        return self._results.get(rid)

    def poll(self, rid: int) -> Optional[Response]:
        """:meth:`result` without forcing collection: returns the
        Response if its batch has already been collected, else None.
        Streaming front-ends use this so polling a queued window never
        blocks on an async engine's in-flight dispatches."""
        return self._results.get(rid)

    def take(self, rid: int) -> Optional[Response]:
        """:meth:`poll` + forget: pops the Response so the engine drops
        its bookkeeping for ``rid``.  The streaming front-end consumes
        results this way — an always-on session must not grow
        ``_results``/``_submitted`` without bound.  After a successful
        take, :meth:`result`/:meth:`drain` no longer see the rid."""
        resp = self.poll(rid)
        if resp is not None:
            del self._results[rid]
            self._taken.add(rid)
        return resp

    def discard(self, rid: int) -> None:
        """Forget ``rid`` entirely: drop its Response now, or on arrival
        if it is still queued/in flight (a reset streaming session
        abandons its pending windows; their reads still happen and are
        still counted in metrics, but the Responses are not retained)."""
        if self._results.pop(rid, None) is None:
            self._discard.add(rid)
        self._taken.add(rid)

    def _collect_pending(self) -> None:
        """Collect any outstanding dispatches (no-op: the synchronous
        engine collects inside ``_dispatch``; AsyncServeEngine
        overrides)."""

    def _collect_ready(self) -> None:
        """Collect the dispatches whose device work has finished, at the
        end of a pump (no-op for the synchronous engine)."""

    # ------------------------------------------------------------ dispatch

    def _read_key(self) -> Optional[jax.Array]:
        """Fresh noise key for one analog read cycle (None when the pool
        is noise-free, keeping the nominal path key-independent)."""
        if self._noise_free:
            return None
        self._key, k = jax.random.split(self._key)
        return k

    def _shard_lits(self, lits: jax.Array) -> jax.Array:
        """Place the batch operand onto the engine mesh: rows split over
        the ``batch`` logical axis when it divides (data-parallel
        reads), replicated otherwise.  No-op off-mesh."""
        if self.mesh is None or self.rules is None:
            return lits
        from jax.sharding import NamedSharding, PartitionSpec as P
        ax = self.rules.batch
        axes = (ax,) if isinstance(ax, str) else tuple(ax or ())
        size = 1
        for a in axes:
            size *= self.mesh.shape[a]
        spec = (P(self.rules.batch, *([None] * (lits.ndim - 1)))
                if axes and lits.shape[0] % size == 0 else P())
        return jax.device_put(lits, NamedSharding(self.mesh, spec))

    def _dispatch(self, batch: Batch) -> None:
        """Synchronous dispatch: issue the fused call and collect it
        immediately (all device time shows up as blocked wait)."""
        self._collect(self._issue(batch))

    def _issue(self, batch: Batch) -> InFlight:
        """Issue one batch's fused jit'd forward WITHOUT blocking on the
        result: JAX dispatch is asynchronous, so the returned
        :class:`InFlight` holds device futures."""
        self._n_dispatched += 1
        seq = self._n_dispatched
        with TraceAnnotation(SPANS["issue"], batch=seq, bucket=batch.bucket,
                             rows=batch.n_valid):
            t_dispatch = self.clock()
            # Packed batches already ARE the literal wire format (packed at
            # submit); dense batches expand to literals on device.
            lits = jnp.asarray(batch.x)
            if not batch.packed:
                lits = tm.literals(lits)
            lits = self._shard_lits(lits)
            key = self._read_key()
            if self.selection.fell_back:
                self.metrics.note_forward_fallback(
                    self.selection.fallback_reason)
            canary = self._take_canary_turn()
            ensemble = self.ecfg.routing == "ensemble"
            self.metrics.note_column_operand(
                self._column_source(canary.state) if canary is not None
                else self._columns_full if ensemble
                else self._columns_slice)
            if canary is not None:
                # Canary dispatch: the candidate chip SERVES this batch, and
                # the stable pool shadow-evaluates the same rows with the
                # same read key — so argmax disagreement measures the model
                # change, not a different noise draw.  The stable chip did a
                # real read, so its router load counter still advances.
                sums, preds = self._fwd(canary.state, lits, key,
                                        self._mask_one, bt=batch.bucket)
                if ensemble:
                    _, shadow = self._fwd(self.state, lits, key,
                                          self._healthy_mask, bt=batch.bucket)
                    for i in self.router.healthy_replicas():
                        self.router.note_dispatch(i, batch.bucket)
                else:
                    stable = self.router.pick(self.ecfg.routing)
                    _, shadow = self._fwd(self._slices[stable], lits, key,
                                          self._mask_one, bt=batch.bucket)
                    self.router.note_dispatch(stable, batch.bucket)
                shadow_nbytes = (self._resident_full if ensemble
                                 else self._resident_slice)
                return InFlight(batch=batch, seq=seq, sums=sums, preds=preds,
                                replica=CANARY, t_dispatch=t_dispatch,
                                t_issue=self.clock(),
                                blocked_snapshot=self._blocked_s,
                                version=canary.version, shadow_preds=shadow,
                                resident_nbytes=_resident_model_nbytes(
                                    canary.state, self.backend,
                                    self._column_source(canary.state))
                                + shadow_nbytes)
            if ensemble:
                sums, preds = self._fwd(self.state, lits, key,
                                        self._healthy_mask, bt=batch.bucket)
                replica = ENSEMBLE
                # Only voting chips count as load: a quarantined chip's
                # sums are computed in the fused dispatch but masked out of
                # the vote, so it did not *serve* the batch.
                for i in self.router.healthy_replicas():
                    self.router.note_dispatch(i, batch.bucket)
            else:
                replica = self.router.pick(self.ecfg.routing)
                sums, preds = self._fwd(self._slices[replica], lits, key,
                                        self._mask_one, bt=batch.bucket)
                self.router.note_dispatch(replica, batch.bucket)
            return InFlight(batch=batch, seq=seq, sums=sums, preds=preds,
                            replica=replica, t_dispatch=t_dispatch,
                            t_issue=self.clock(),
                            blocked_snapshot=self._blocked_s,
                            version=self.pool.version,
                            resident_nbytes=(self._resident_full
                                             if replica == ENSEMBLE
                                             else self._resident_slice))

    def _take_canary_turn(self) -> Optional[_Canary]:
        """Deterministic traffic split: an accumulator hands ~fraction
        of batches to the armed canary.  No RNG — a fixed request trace
        replays to the identical canary/stable schedule."""
        if self._canary is None:
            return None
        self._canary_acc += self._canary.fraction
        if self._canary_acc >= 1.0 - 1e-9:
            self._canary_acc -= 1.0
            return self._canary
        return None

    def _collect(self, fl: InFlight) -> None:
        """Block on one in-flight dispatch and materialize Responses.

        Overlap accounting: of the window ``t_issue -> collection
        start``, only the part where the host was doing productive work
        counts as hidden device time — stalls spent inside OTHER
        batches' ``block_until_ready`` (tracked via ``_blocked_s``
        snapshots) are subtracted, so a deep pipeline cannot claim its
        neighbours' blocked waits as overlap.  The remainder of this
        batch's device time shows up as its own blocked wait."""
        with TraceAnnotation(SPANS["collect"], batch=fl.seq):
            t_wait0 = self.clock()
            with TraceAnnotation(SPANS["block"]):
                jax.block_until_ready(fl.outputs())
            t_done = self.clock()
            blocked_elsewhere = self._blocked_s - fl.blocked_snapshot
            overlapped = max(0.0, (t_wait0 - fl.t_issue) - blocked_elsewhere)
            self._blocked_s += t_done - t_wait0
            preds = np.asarray(fl.preds)
            sums = np.asarray(fl.sums)
            shadow = (None if fl.shadow_preds is None
                      else np.asarray(fl.shadow_preds))
            self.metrics.note_fetch(self.clock() - t_done)
            batch = fl.batch
            if shadow is not None:                # canary batch: score the
                agree = int((preds[:batch.n_valid]  # stable pool's argmax
                             == shadow[:batch.n_valid]).sum())  # valid rows
                self.metrics.note_canary(batch.n_valid, agree)

            records = []
            for row, req in enumerate(batch.requests):
                if req.rid in self._discard:      # abandoned by a session
                    self._discard.discard(req.rid)  # reset; served + counted,
                else:                               # never retained
                    self._results[req.rid] = Response(
                        rid=req.rid, pred=int(preds[row]),
                        class_sums=sums[row], replica=fl.replica,
                        latency_s=t_done - req.t_enqueue,
                        version=fl.version)
                records.append(RequestRecord(
                    rid=req.rid, t_enqueue=req.t_enqueue,
                    t_dispatch=fl.t_dispatch, t_done=t_done,
                    bucket=batch.bucket, n_valid=batch.n_valid,
                    replica=fl.replica, version=fl.version, qos=req.qos))
            # Pad rows (batch.n_padding of them) are dropped here by
            # construction: only batch.requests rows produce Responses.
            assert len(records) == batch.n_valid
            self.metrics.record_batch(records, batch.bucket, batch.nbytes,
                                      resident_nbytes=fl.resident_nbytes)
            self.metrics.note_dispatch(
                fl.seq, fl.t_dispatch, batch.bucket, batch.n_valid,
                fl.t_dispatch - batch.requests[0].t_enqueue)
            self.metrics.note_dispatch_timing(
                pack_s=batch.pack_s, wait_s=t_done - t_wait0,
                overlapped_s=overlapped)

    # ------------------------------------------------------------ hot swap

    @property
    def version(self) -> int:
        """Monotonic model generation of the serving pool."""
        return self.pool.version

    @property
    def canary_active(self) -> bool:
        return self._canary is not None

    def quiesce(self) -> None:
        """Wait until no dispatch is in flight (collects async futures).

        Queued-but-undispatched requests stay queued — quiescing is a
        barrier between dispatches, not a drain."""
        self._collect_pending()

    def install_pool(self, pool, *, kind: str = "swap") -> None:
        """Atomically install a new pool version between dispatches.

        The swap is atomic at batch granularity: in-flight dispatches
        are collected first (they complete at the version captured when
        they were issued), then the state, replica slices, and pool
        reference are replaced in one step — the next ``_issue`` serves
        entirely from the new version.  Nothing queued is dropped:
        undispatched requests serve post-swap at the new version.

        The new pool must be *hot-compatible* with the serving one —
        same pool type, replica count, model shape, and static noise /
        crossbar configs — because backend selection, tuning, and the
        compiled forward were chosen once at construction and are
        deliberately KEPT (same shapes + static configs ⇒ every kernel
        is a jit cache hit; a swap costs one pipeline drain, not a
        recompile).  Routing counters, metrics, and the engine PRNG
        stream also survive.  An armed canary is disarmed: its
        comparison was against the pre-swap stable pool.

        ``kind`` labels the ServeMetrics swap event ("swap" | "promote"
        | "rollback"); ``serve/swap.py`` passes the latter two."""
        old = self.pool
        if type(pool) is not type(old):
            raise ValueError(
                f"install_pool: pool type changed "
                f"({type(old).__name__} -> {type(pool).__name__}); "
                "build a new engine instead")
        if pool.n_replicas != old.n_replicas:
            raise ValueError(
                f"install_pool: n_replicas changed ({old.n_replicas} -> "
                f"{pool.n_replicas}); the compiled forward and router "
                "are sized to the pool — build a new engine instead")
        if isinstance(pool, ReplicaPool):
            if pool.include.shape != old.include.shape:
                raise ValueError(
                    f"install_pool: model shape changed "
                    f"({tuple(old.include.shape)} -> "
                    f"{tuple(pool.include.shape)})")
            if (pool.icfg, pool.vcfg) != (old.icfg, old.vcfg):
                raise ValueError(
                    "install_pool: crossbar/noise config changed; "
                    "backend selection is static per engine — build a "
                    "new engine instead")
        else:                        # CoalescedPool (single shared chip)
            if pool.cfg != old.cfg:
                raise ValueError(
                    "install_pool: coalesced config changed; build a "
                    "new engine instead")
            if pool.ta_state.shape != old.ta_state.shape or \
                    pool.weights.shape != old.weights.shape:
                raise ValueError("install_pool: model shape changed")
        self.quiesce()
        self._set_pool(pool)
        self.disarm_canary()
        if self.health is not None:
            # Re-commit the probe reference against the (possibly new)
            # clean model — deterministic, so a same-model install (e.g.
            # kind="repair") recommits to identical expected answers.
            self.health = HealthProbe.commit(self.pool, self.tm_cfg,
                                             self.health.hcfg)
        self.metrics.note_swap(old.version, pool.version, kind)

    def _set_pool(self, pool) -> None:
        """Replace the serving pool/state/slices in one step (callers
        quiesce first).  Shared by :meth:`install_pool` and the fault
        path (:meth:`inject_faults`, repair installs) — same shapes and
        static configs, so every compiled kernel stays cache-hit."""
        if self.mesh is not None:
            pool = pool.shard(self.mesh, self.rules)
        state = pool.state(self.tm_cfg)
        if self.ecfg.packed:
            state = state.pack()
            if self.ecfg.pack_planes and not state.is_sharded and \
                    hasattr(state, "pack_planes"):
                state = state.pack_planes()
        self.pool = pool
        self.state = state
        self._slices = _replica_views(state, pool.n_replicas)
        self._refresh_resident_nbytes()

    def _column_source(self, state) -> Optional[str]:
        """Where a dispatch of ``state`` takes its column operand from
        (``"programmed"``, ``"built"``), None where it takes none."""
        source = self.backend.column_operand
        return None if source is None else source(
            state, not self._noise_free, **self._tiles)

    def _refresh_resident_nbytes(self) -> None:
        """Per-dispatch resident operand bytes and column-operand source
        for the full state (ensemble dispatch) and one replica slice
        (routed dispatch) — recomputed whenever the pool changes, since
        fault injection can grow a nominal plane-packed pool a deviation
        plane (which also changes the kernel's dot mode)."""
        one = (jax.eval_shape(lambda s: s.replica_slice(0), self.state)
               if hasattr(self.state, "replica_slice") else self.state)
        self._columns_full = self._column_source(self.state)
        self._columns_slice = self._column_source(one)
        self._resident_full = _resident_model_nbytes(
            self.state, self.backend, self._columns_full)
        self._resident_slice = _resident_model_nbytes(
            one, self.backend, self._columns_slice)
        dots = self.backend.dot_mode
        self.metrics.crossbar_dots = (
            None if dots is None else dots(self.state, not self._noise_free))

    def arm_canary(self, state, version: int, fraction: float) -> None:
        """Mount a candidate single-chip state beside the stable pool.

        While armed, a deterministic ``fraction`` of batches are served
        by ``state`` (Response.replica == CANARY, Response.version ==
        ``version``) and shadow-scored against the stable pool; the
        agreement tally lands in ``ServeMetrics``.  ``state`` must be
        dispatchable by this engine's compiled forward — in practice a
        ``replica_slice``/full state of a pool built with the same
        shapes and configs (``serve/swap.py`` constructs it)."""
        if not (0.0 < fraction <= 1.0):
            raise ValueError(f"canary fraction must be in (0, 1], "
                             f"got {fraction}")
        if getattr(self.state, "packed", False) and \
                not getattr(state, "packed", False):
            state = state.pack()     # match the serving wire format
        if getattr(self.state, "plane_packed", False) and \
                not getattr(state, "plane_packed", False) and \
                hasattr(state, "pack_planes"):
            state = state.pack_planes()  # match the resident format
        self._canary = _Canary(state=state, version=int(version),
                               fraction=float(fraction))
        self._canary_acc = 0.0

    def disarm_canary(self) -> None:
        self._canary = None
        self._canary_acc = 0.0

    # ------------------------------------------------- health + self-healing

    @property
    def quarantined(self) -> List[int]:
        """Replica indices currently masked out of routing/voting."""
        return sorted(self.router.quarantined)

    def enable_health(self, hcfg: Optional[HealthConfig] = None) -> None:
        """Commit probe vectors + known-good answers for this pool's
        clean model, and seed the dedicated health PRNG stream."""
        hcfg = hcfg if hcfg is not None else HealthConfig()
        self.health = HealthProbe.commit(self.pool, self.tm_cfg, hcfg)
        self._health_key = jax.random.PRNGKey(hcfg.seed + 1)

    def _health_read_key(self) -> Optional[jax.Array]:
        """Noise key for probe reads, from the health stream — probing
        must not advance the serving stream (bit-reproducible traces)."""
        if self._noise_free:
            return None
        self._health_key, k = jax.random.split(self._health_key)
        return k

    def inject_faults(self, key: jax.Array, fcfg=None,
                      replicas=None) -> None:
        """Chaos surface (ISSUE 8): bake persistent device faults into
        the serving pool in place — stuck-at cells + retention drift per
        ``fcfg`` (default: the pool's ``vcfg.fault``), restricted to
        ``replicas`` when given.  Quiesces first (batch-atomic, like
        :meth:`install_pool`), keeps the pool version (the model didn't
        change), and meters the event.  Nominal/missing ``fcfg`` is a
        no-op."""
        pool = self.pool.inject_faults(key, fcfg, replicas=replicas)
        if pool is self.pool:
            return
        self.quiesce()
        self._set_pool(pool)
        self.metrics.note_fault_injection(
            None if replicas is None else sorted(int(r) for r in replicas))

    def probe(self, probe: Optional[HealthProbe] = None) -> Dict[int, float]:
        """Score every replica against the committed probe set and apply
        quarantine/readmit (ISSUE 8).

        Each chip evaluates the probe rows through the engine's own
        compiled forward (same backend, same bucket shapes, the packed
        wire format if serving uses it) under keys from the health PRNG
        stream; row-exact agreement of its class sums with the digital
        reference is its health.
        Chips below ``quarantine_threshold`` are quarantined (routing
        and ensemble votes skip them), quarantined chips at/above
        ``readmit_threshold`` are readmitted — with the hysteresis band
        between, and a hard floor: the last healthy chip is never
        quarantined (serving degrades, it never halts).  Results land in
        ``ServeMetrics`` (``summary()['replica_health']``)."""
        if probe is None:
            if self.health is None:
                self.enable_health()
            probe = self.health
        self.quiesce()
        mb = self.batcher.cfg.max_batch
        sums = [[] for _ in range(self.pool.n_replicas)]
        for start in range(0, probe.n_probes, mb):
            chunk = probe.x[start:start + mb]
            bucket = self.batcher.cfg.bucket_for(len(chunk))
            if self.packed_io:
                rows = np.stack([pack_request_np(r) for r in chunk])
            else:
                rows = np.asarray(chunk, np.uint8)
            if bucket > len(chunk):
                pad = np.zeros((bucket - len(chunk), rows.shape[1]),
                               rows.dtype)
                rows = np.concatenate([rows, pad], axis=0)
            lits = jnp.asarray(rows)
            if not self.packed_io:
                lits = tm.literals(lits)
            lits = self._shard_lits(lits)
            # One key per chunk, shared across chips: the chips differ
            # by their programmed arrays, not by the noise draw, so the
            # comparison isolates device health.
            key = self._health_read_key()
            for i in range(self.pool.n_replicas):
                s, _ = self._fwd(self._slices[i], lits, key,
                                 self._mask_one, bt=bucket)
                sums[i].append(np.asarray(s)[:len(chunk)])
        health = {i: probe.score(np.concatenate(sums[i]))
                  for i in range(self.pool.n_replicas)}
        self._apply_health(health, probe)
        return health

    def _apply_health(self, health: Dict[int, float],
                      probe: HealthProbe) -> None:
        """Turn probe scores into quarantine/readmit transitions."""
        self.metrics.note_health(health)
        actions = probe.classify(health, self.router.quarantined)
        for i, act in actions.items():
            if act == "quarantine":
                if self.router.healthy_replicas() == [i]:
                    # Floor: degrading to zero chips would halt serving;
                    # the held chip keeps serving (and the held state is
                    # visible in the metrics event trail).
                    self.metrics.note_quarantine(i, health[i],
                                                 "held_last_healthy")
                    continue
                self.router.quarantine(i)
                self.metrics.note_quarantine(i, health[i], "quarantine")
            elif act == "readmit":
                self.router.readmit(i)
                self.metrics.note_quarantine(i, health[i], "readmit")
        self._refresh_healthy_mask()

    def _refresh_healthy_mask(self) -> None:
        mask = np.ones(self.pool.n_replicas, bool)
        for i in self.router.quarantined:
            if 0 <= i < len(mask):
                mask[i] = False
        if not mask.any():          # same floor as RouterState
            mask[:] = True
        self._healthy_mask = jnp.asarray(mask)

    # ------------------------------------------------------------- metrics

    def summary(self, includes: Optional[int] = None) -> Dict:
        """Simulation metrics + the crossbar's hardware figures of merit."""
        out = self.metrics.summary()
        out["replica_load_rows"] = list(self.router.rows_dispatched)
        out["routing"] = self.ecfg.routing
        out["pool_version"] = self.version
        out["canary_active"] = self.canary_active
        out["n_replicas"] = self.pool.n_replicas
        out["quarantined"] = self.quarantined
        out["backend"] = self.backend.name
        out["backend_preferred"] = self.selection.preferred
        out["packed_io"] = self.packed_io
        out["plane_packed"] = bool(getattr(self.state, "plane_packed",
                                           False))
        out["resident_nbytes_full"] = self._resident_full
        out["resident_nbytes_slice"] = self._resident_slice
        out["sharded"] = self.state.is_sharded
        out["mesh"] = (dict(self.mesh.shape) if self.mesh is not None
                       else None)
        out["bucket_sizes"] = list(self.batcher.cfg.bucket_sizes)
        out["buckets_tuned_for"] = self.batcher.cfg.tuned_for
        out["kernel_tiles"] = dict((self.tuning or {}).get("tiles") or {})
        out["shape_key"] = self.shape_key
        out["tuning_lazy"] = bool((self.tuning or {}).get("lazy"))
        if includes is None:
            includes = int(jnp.sum(self.pool.include))
        out["hardware"] = hardware_figures(
            self.tm_cfg, includes, self.pool.n_replicas,
            ensemble=self.ecfg.routing == "ensemble")
        return out


class AsyncServeEngine(ServeEngine):
    """Double-buffered serving: overlap host batching with device compute.

    Same construction surface, routing semantics, and per-seed noise
    stream as :class:`ServeEngine` — only the dispatch schedule changes.
    ``_dispatch`` *issues* the fused jit'd call (device futures; no
    host block), starts the copies of its outputs to the host, and
    defers collection until ``ecfg.max_in_flight`` newer dispatches are
    outstanding, a pump finds it finished, a result is requested, or
    the engine drains.  With the default depth of 2, the host packs and
    issues batch N+1 while batch N's kernel is in flight — the classic
    pipeline that makes serving throughput track device time instead of
    host+device time.  Responses still come back in submission order
    from :meth:`drain`, and ``summary()['overlap_fraction']`` reports
    how much device time the pipelining actually hid."""

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        if self.ecfg.max_in_flight < 1:
            raise ValueError("max_in_flight must be >= 1")
        self._pending: Deque[InFlight] = deque()

    @property
    def in_flight(self) -> int:
        """Issued-but-uncollected dispatches right now."""
        return len(self._pending)

    def _dispatch(self, batch: Batch) -> None:
        while len(self._pending) >= self.ecfg.max_in_flight:
            self._collect(self._pending.popleft())
        fl = self._issue(batch)
        self._prefetch(fl)
        self._pending.append(fl)

    @staticmethod
    def _prefetch(fl: InFlight) -> None:
        """Start the device-to-host copies of a dispatch's outputs now:
        the runtime moves them as soon as the kernel finishes, and the
        ``np.asarray`` in :meth:`_collect` picks up the landed copy
        instead of waiting out the transfer."""
        try:
            for out in fl.outputs():
                out.copy_to_host_async()
        except AttributeError:      # non-jax arrays (test doubles)
            pass

    def _collect_ready(self) -> None:
        # Opportunistically collect, at every pump, dispatches whose
        # device work already finished: results land as early as the
        # event loop allows, and host *idle* time between request
        # arrivals is not misattributed as overlap (the in-flight window
        # closes at the first pump after completion, not whenever the
        # next batch forces a collect).  The overlap accounting
        # therefore remains a host-side observation — exact under
        # continuous load, an approximation when the engine sits idle
        # between pumps.
        while self._pending and self._is_ready(self._pending[0]):
            self._collect(self._pending.popleft())

    @staticmethod
    def _is_ready(fl: InFlight) -> bool:
        try:
            return all(out.is_ready() for out in fl.outputs())
        except AttributeError:      # non-jax arrays (test doubles)
            return True

    def _collect_pending(self) -> None:
        while self._pending:
            self._collect(self._pending.popleft())
