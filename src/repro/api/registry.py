"""Capability-based backend registry: the *code* half of the unified API.

Every backend implements ONE signature

    class_sums(state, lits, key=None, **opts) -> [..., M] int32

where ``state`` is a registered pytree state (``repro.api.states``),
``lits`` is the ``[B, 2F]`` literal matrix, and ``key`` (when not None)
draws one read cycle of noise.  Beyond the signature, a backend declares

* which state types it accepts, and
* a **capability set** — what physics/deployment features it models
  (``models_csa_offset``, ``supports_replica_vmap``, ``fused_kernel``,
  ...).

Selection is then explicit: callers state what they *need* and what they
*prefer*; :func:`select_backend` returns the chosen backend plus a
``Selection`` record saying whether the preference had to be overridden
and why.  This replaces the serve engine's old silent boolean fallback
(``EngineConfig.use_kernel`` + the csa_offset special case): when
capability selection changes noise semantics, the caller gets a loud,
inspectable reason to surface in metrics.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, FrozenSet, List, Optional, Tuple, Type

from repro.api.states import (CoalescedState, CrossbarState, DigitalState,
                              ReplicaStackState)

# The capability vocabulary.  A backend MAY model more than it declares,
# never less.
CAP_DIGITAL = "digital"                     # Boolean-domain evaluation
CAP_ANALOG = "analog"                       # current-domain crossbar model
CAP_FUSED_KERNEL = "fused_kernel"           # single fused Pallas dispatch
CAP_MODELS_C2C = "models_c2c"               # cycle-to-cycle R excursions
CAP_MODELS_CSA_OFFSET = "models_csa_offset"  # per-column CSA input offset
CAP_REPLICA_VMAP = "supports_replica_vmap"  # [R, C, L] in one dispatch
CAP_COALESCED = "coalesced_weights"         # weighted digital tail
CAP_TPU_ONLY = "tpu_only"                   # no interpret-mode fallback
CAP_PACKED_IO = "packed_io"                 # uint32 bitplane literal wire
CAP_SHARDED = "sharded_dispatch"            # safe under NamedSharding
CAP_PACKED_PLANES = "packed_planes"         # resident index+dev plane format

KNOWN_CAPABILITIES = frozenset({
    CAP_DIGITAL, CAP_ANALOG, CAP_FUSED_KERNEL, CAP_MODELS_C2C,
    CAP_MODELS_CSA_OFFSET, CAP_REPLICA_VMAP, CAP_COALESCED, CAP_TPU_ONLY,
    CAP_PACKED_IO, CAP_SHARDED, CAP_PACKED_PLANES,
})


@dataclasses.dataclass(frozen=True)
class Backend:
    """One registered forward implementation."""

    name: str
    fn: Callable                            # class_sums(state, lits, key)
    state_types: Tuple[Type, ...]
    capabilities: FrozenSet[str]
    priority: int = 0                       # higher wins among candidates
    doc: str = ""
    # Optional extra acceptance check beyond isinstance — e.g. the packed
    # backends require the state to carry a packed include plane
    # (``state.packed``).  None means "type match is enough".
    predicate: Optional[Callable] = None
    # How the forward's crossbar column dots run, as the backend itself
    # chooses them: ``dot_mode(state, keyed)`` -> a mode name (``keyed``:
    # the reads carry a noise key).  None on paths without crossbar
    # currents.
    dot_mode: Optional[Callable] = None

    def accepts(self, state) -> bool:
        if not isinstance(state, self.state_types):
            return False
        return self.predicate is None or bool(self.predicate(state))

    def provides(self, caps) -> bool:
        return frozenset(caps) <= self.capabilities


@dataclasses.dataclass(frozen=True)
class Selection:
    """Outcome of one capability-based backend choice."""

    backend: Backend
    required: FrozenSet[str]
    preferred: Optional[str] = None
    fallback_reason: Optional[str] = None   # set iff preference overridden

    @property
    def fell_back(self) -> bool:
        return self.fallback_reason is not None


_REGISTRY: Dict[str, Backend] = {}


def register_backend(name: str, *, state_types, capabilities,
                     priority: int = 0, doc: str = "", predicate=None,
                     dot_mode=None):
    """Decorator: register ``fn`` as backend ``name``."""
    unknown = frozenset(capabilities) - KNOWN_CAPABILITIES
    if unknown:
        raise ValueError(f"unknown capabilities {sorted(unknown)}; extend "
                         "KNOWN_CAPABILITIES to add vocabulary")

    def deco(fn):
        if name in _REGISTRY:
            raise ValueError(f"backend {name!r} already registered")
        _REGISTRY[name] = Backend(
            name=name, fn=fn, state_types=tuple(state_types),
            capabilities=frozenset(capabilities), priority=priority,
            doc=doc or (fn.__doc__ or "").strip().splitlines()[0]
            if (doc or fn.__doc__) else "", predicate=predicate,
            dot_mode=dot_mode)
        return fn

    return deco


def get_backend(name: str) -> Backend:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown backend {name!r}; registered: "
                       f"{sorted(_REGISTRY)}") from None


def list_backends() -> List[Backend]:
    return sorted(_REGISTRY.values(), key=lambda b: b.name)


def required_capabilities(state, key=None) -> FrozenSet[str]:
    """The capability floor implied by ``state`` (and a noise key).

    * a replica stack needs single-dispatch replica support;
    * a noisy read (``key`` given) against a ``VariationConfig`` with
      ``csa_offset`` on needs a backend that models the per-column CSA
      offset — the fused kernel thresholds against one scalar reference
      and therefore does NOT;
    * a state *partitioned* across devices (``state.shard(mesh)``) needs
      a backend whose dispatch is safe under ``NamedSharding`` — the
      Pallas kernels are single-device custom calls and do not declare
      it, so sharded states fall back (loudly) to the GSPMD-partitioned
      jnp paths.
    """
    from repro.distributed.sharding import tree_is_sharded
    need = set()
    if tree_is_sharded(state):
        need.add(CAP_SHARDED)
    if isinstance(state, ReplicaStackState):
        need.add(CAP_REPLICA_VMAP)
    if isinstance(state, (CrossbarState, ReplicaStackState)):
        need.add(CAP_ANALOG)
        if key is not None and state.vcfg.csa_offset:
            need.add(CAP_MODELS_CSA_OFFSET)
        if key is not None and state.vcfg.c2c:
            need.add(CAP_MODELS_C2C)
    if isinstance(state, DigitalState):
        need.add(CAP_DIGITAL)
    if isinstance(state, CoalescedState):
        need.add(CAP_COALESCED)
    return frozenset(need)


def _candidates(state, need) -> List[Backend]:
    cands = [b for b in _REGISTRY.values()
             if b.accepts(state) and b.provides(need)]
    return sorted(cands, key=lambda b: (-b.priority, b.name))


def select_backend(state, *, key=None, prefer: Optional[str] = None,
                   require=()) -> Selection:
    """Pick the backend for ``state``: explicit capability matching.

    ``prefer`` names a backend to use *if it satisfies* the required
    capability set; when it does not, the highest-priority satisfying
    backend is chosen instead and ``Selection.fallback_reason`` records
    exactly which capabilities forced the switch — callers must surface
    this (the serve engine logs it into ``ServeMetrics``).

    ``require`` adds caller capabilities on top of the state-implied set.
    """
    need = frozenset(required_capabilities(state, key)) | frozenset(require)
    cands = _candidates(state, need)
    if not cands:
        raise ValueError(
            f"no registered backend accepts {type(state).__name__} with "
            f"capabilities {sorted(need)}; registered: "
            f"{[(b.name, sorted(b.capabilities)) for b in list_backends()]}")
    if prefer is not None:
        pref = get_backend(prefer)
        if not pref.accepts(state):
            reason = (f"{prefer} does not accept "
                      f"{type(state).__name__}")
        elif not pref.provides(need):
            missing = sorted(need - pref.capabilities)
            reason = f"{prefer} lacks {missing}"
        else:
            return Selection(backend=pref, required=need, preferred=prefer)
        return Selection(backend=cands[0], required=need, preferred=prefer,
                         fallback_reason=f"{reason}; selected "
                                         f"{cands[0].name}")
    return Selection(backend=cands[0], required=need)


# ---------------------------------------------------------------------------
# Per-(backend, shape bucket) tuning tables (measured autotuning,
# ISSUE 3; shape-aware since ISSUE 5)
# ---------------------------------------------------------------------------
#
# The registry is the designated home for *measured* per-backend tuning:
# ``kernels/autotune.py`` times (bt, ct, kt) tile candidates and bucket
# sizes against each registered backend and registers the result here.
# Consumers (``ServeEngine``, ``BatcherConfig.for_max_batch``) read the
# table instead of hard-coding tile/bucket constants.  A committed
# default table (``repro/kernels/tuning_table.json``, regenerated by
# ``benchmarks/kernel_bench.py``) is lazily loaded on first lookup.
#
# Entries are keyed by **(backend name, shape bucket)**: the right tiles
# depend on the model's (C, L) as much as on the backend, so a KWS-shaped
# model must never inherit tiles measured at the serve-bench shape.
# ``shape_bucket_key`` rounds (n_clauses, n_literals) up to powers of two
# ("c64-l1024"), so near-identical shapes share an entry while genuinely
# different workloads get their own — measured lazily on first sight when
# the consumer opts in (``EngineConfig.lazy_tune`` ->
# ``kernels.autotune.ensure_tuning``).
#
# Entry schema (plain JSON-shaped dict):
#   {"tiles": {"ct": int, "kt": int},        # best measured kernel tiles
#    "bucket_sizes": [int, ...],             # measured-good batch buckets
#    "bucket_latency_us": {"8": float, ...}, # evidence
#    "tile_latency_us": {"ctxkt": float, ...},
#    "shape": {...},                         # exact workload measured
#    "jax_backend": "cpu" | "tpu" | ...,     # withholding guard
#    "lazy": bool}                           # measured on first sight?

# The serve-bench reference bucket: TMConfig(4 classes x 8 clauses,
# 64 features) -> C=32, L=128.  Legacy (pre-shape-key) lookups and
# entries without shape information land here.
REF_SHAPE_KEY = "c32-l128"

_TUNING: Dict[str, Dict[str, dict]] = {}      # name -> shape_key -> entry
_TUNING_DEFAULTS_LOADED = False


def _pow2ceil(n: int) -> int:
    return 1 << max(0, int(n) - 1).bit_length()


def shape_bucket_key(n_clauses: int, n_literals: int) -> str:
    """The tuning-table shape bucket for a ``[C, L]`` model: both dims
    rounded up to the next power of two (``"c64-l1024"``)."""
    return f"c{_pow2ceil(n_clauses)}-l{_pow2ceil(n_literals)}"


def shape_key_of(shape: dict) -> str:
    """Bucket key of an entry's recorded ``shape`` dict.

    Per-class shapes carry ``{"n_classes", "clauses_per_class",
    "n_features"}``; coalesced shapes carry the total pool directly as
    ``"n_clauses"`` (there is no per-class split to multiply out)."""
    n_clauses = shape.get("n_clauses")
    if n_clauses is None:
        n_clauses = shape["n_classes"] * shape["clauses_per_class"]
    return shape_bucket_key(n_clauses, 2 * shape["n_features"])


def register_tuning(name: str, entry: dict,
                    shape_key: Optional[str] = None) -> None:
    """Install (or overwrite) the measured entry for
    ``(backend, shape bucket)``.  ``shape_key`` defaults to the bucket
    of the entry's own recorded ``shape`` (or :data:`REF_SHAPE_KEY` for
    shapeless legacy entries)."""
    _load_tuning_defaults()        # an early register must not shadow the
    if shape_key is None:          # committed entries of OTHER buckets
        shape_key = (shape_key_of(entry["shape"]) if entry.get("shape")
                     else REF_SHAPE_KEY)
    _TUNING.setdefault(name, {})[shape_key] = dict(entry)


def get_tuning(name: str,
               shape_key: Optional[str] = None) -> Optional[dict]:
    """The measured entry for ``(backend, shape bucket)``, or None.

    ``shape_key`` is a :func:`shape_bucket_key` string; None is the
    legacy lookup and means the serve-bench reference bucket
    (:data:`REF_SHAPE_KEY`).  Falls back to the committed default table
    shipped with the package on first lookup of an unknown backend.

    Two withholding rules — a near-miss entry must fall back to
    defaults, never be silently applied:

    * a different **shape bucket** is a different key, so tiles measured
      at the serve-bench shape are never handed to a KWS-shaped engine;
    * an entry whose recorded ``jax_backend`` does not match the runtime
      jax backend is withheld: tiles measured in CPU interpret mode must
      not override the MXU-aligned defaults on a real TPU (re-run
      ``benchmarks/kernel_bench.py`` on the target to tune it).
    """
    if name not in _TUNING:
        _load_tuning_defaults()
    entry = _TUNING.get(name, {}).get(shape_key or REF_SHAPE_KEY)
    if entry is not None and "jax_backend" in entry:
        import jax
        if entry["jax_backend"] != jax.default_backend():
            return None
    return entry


def tuning_snapshot() -> Dict[str, Dict[str, dict]]:
    """A deep copy of the whole loaded table (defaults included) — pair
    with :func:`restore_tuning` around code that mutates it (benchmarks,
    tests).  Deep so that in-place edits of an entry's nested values
    (``tiles``, ``bucket_sizes``) cannot leak through a restore."""
    import copy
    _load_tuning_defaults()
    return {name: {k: copy.deepcopy(e) for k, e in shapes.items()}
            for name, shapes in _TUNING.items()}


def restore_tuning(snapshot: Dict[str, Dict[str, dict]]) -> None:
    """Replace the table with a :func:`tuning_snapshot` copy."""
    import copy
    global _TUNING_DEFAULTS_LOADED
    _TUNING_DEFAULTS_LOADED = True            # snapshot already folded them
    _TUNING.clear()
    for name, shapes in snapshot.items():
        for k, e in shapes.items():
            _TUNING.setdefault(name, {})[k] = copy.deepcopy(e)


def _load_tuning_defaults() -> None:
    global _TUNING_DEFAULTS_LOADED
    if _TUNING_DEFAULTS_LOADED:
        return
    _TUNING_DEFAULTS_LOADED = True
    # Lazy import: no cycle.  normalize_table is the ONE implementation
    # of the pre-ISSUE-5 flat-schema migration (save/merge uses it too).
    from repro.kernels.autotune import load_default_table, normalize_table
    for bname, shapes in normalize_table(load_default_table()).items():
        for skey, entry in shapes.items():
            _TUNING.setdefault(bname, {}).setdefault(skey, entry)


def clear_tuning(name: Optional[str] = None) -> None:
    """Drop one backend's (or every) tuning entry — test hygiene.

    The semantics do not depend on whether a lookup happened first:
    clearing everything empties the table for good (no later lazy load
    resurrects it); clearing one name loads the committed defaults for
    the *other* backends first, then drops that backend's entries for
    ALL shape buckets.
    """
    global _TUNING_DEFAULTS_LOADED
    if name is None:
        _TUNING_DEFAULTS_LOADED = True
        _TUNING.clear()
    else:
        _load_tuning_defaults()
        _TUNING.pop(name, None)
