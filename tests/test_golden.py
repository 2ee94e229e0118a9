"""Golden regression suite: committed class sums, reproduced bit-for-bit.

The backend x state parity matrix (``test_api.py``) pins every backend
to ``tm.forward`` — but if the *reference itself* drifted (a semantics
change in ``core/tm.py``, a jax upgrade changing a kernel's rounding,
all backends drifting together), the matrix would stay green while
every committed result silently changed.  This suite closes that hole:
``tests/golden/backends_v4.json`` carries the class sums + preds of a
fixed seed/model/batch, and EVERY registered backend must reproduce
them bit-for-bit at ``VariationConfig.nominal()``.  v2 (ISSUE 6) adds
the coalesced family (``coalesced-pallas``/``coalesced-pallas-packed``
and the packed coalesced state) and a ``backend_coverage`` map —
{backend name: [golden states it accepts]} — that the registry-coverage
meta-test (``test_registry_coverage.py``) checks against the live
registry, so registering a backend without golden coverage fails CI.
v3 (ISSUE 9) adds the plane-packed states (``*_planes``) and the
``analog-pallas-packed2``/``coalesced-pallas-packed2`` backends that
serve from the LRS/HRS index bitplane.  v4 is v3's schema regenerated
under JAX 0.9's PRNG stream (the partitionable threefry generator), whose
seeded inputs differ from the legacy stream v3 was written under.

The golden inputs (include mask, request batch) are recreated from
seeds and guarded by committed SHA-256 digests, so a failure is
attributable: digest mismatch = the jax PRNG stream changed (regenerate
deliberately); digest match + sum mismatch = an inference backend
really drifted.

Regenerate (deliberately, in a PR that explains why):

  PYTHONPATH=src python tests/test_golden.py --regen

Regeneration recomputes the sums from the seeded model AND rebuilds the
``backend_coverage`` map from the registry at regen time; bump the
filename version (v1 -> v2 -> ...) when the *schema* or the covered
backend set changes, so a stale checkout fails loudly instead of
validating against the wrong bar.
"""

import hashlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.core import tm
from repro.core.coalesced import CoalescedConfig
from repro.core.tm import TMConfig
from repro.core.variations import VariationConfig
from repro.kernels import ops

GOLDEN_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "golden", "backends_v4.json")

# Fixed golden workload.  Changing ANY of these constants invalidates
# the committed file — regenerate in the same commit.
CFG = dict(n_classes=4, clauses_per_class=8, n_features=32, n_states=100)
SEED_INCLUDE, SEED_BATCH, SEED_PROGRAM = 7, 8, 9
INCLUDE_DENSITY = 0.05         # sparse clauses fire often: richer sums
N_BATCH = 16
NOMINAL = VariationConfig.nominal()


def _sha(arr: np.ndarray) -> str:
    return hashlib.sha256(
        np.ascontiguousarray(np.asarray(arr)).tobytes()).hexdigest()


def golden_model():
    """The fixed model + batch, recreated from seeds."""
    cfg = TMConfig(**CFG)
    inc = jax.random.bernoulli(jax.random.PRNGKey(SEED_INCLUDE),
                               INCLUDE_DENSITY,
                               (cfg.n_clauses, cfg.n_literals))
    ta = jnp.where(inc, cfg.n_states + 1, cfg.n_states).astype(
        cfg.state_dtype)
    x = jax.random.bernoulli(jax.random.PRNGKey(SEED_BATCH), 0.4,
                             (N_BATCH, cfg.n_features)).astype(jnp.uint8)
    return cfg, inc, ta, x


def golden_states(cfg, inc, ta):
    """One same-model instance of every registered state type (the
    test_api parity-fixture construction, pinned here by seed)."""
    key = jax.random.PRNGKey(SEED_PROGRAM)
    ccfg = CoalescedConfig(n_classes=cfg.n_classes, n_clauses=cfg.n_clauses,
                           n_features=cfg.n_features, n_states=cfg.n_states)
    w = ops.polarity_matrix(cfg, inc,
                            n_class_pad=cfg.n_classes).astype(jnp.int32)
    states = {
        "digital": api.DigitalState.from_ta(ta, cfg),
        "crossbar": api.CrossbarState.program(inc, key, cfg, NOMINAL),
        "stack": api.ReplicaStackState.program(inc, key, 2, cfg, NOMINAL),
        "coalesced": api.CoalescedState(ta_state=ta, weights=w, cfg=ccfg),
    }
    states["digital_packed"] = states["digital"].pack()
    states["crossbar_packed"] = states["crossbar"].pack()
    states["stack_packed"] = states["stack"].pack()
    states["coalesced_packed"] = states["coalesced"].pack()
    # plane-packed twins (ISSUE 9): same model, resident conductance
    # planes folded into the LRS/HRS index bitplane (+ deviation plane
    # off-nominal — elided here, the golden model is nominal)
    states["crossbar_planes"] = states["crossbar"].pack_planes()
    states["stack_planes"] = states["stack"].pack_planes()
    states["coalesced_planes"] = states["coalesced"].pack_planes()
    return states


def backend_coverage(states):
    """{backend name: sorted golden-state names it accepts} over the
    LIVE registry — committed into the golden file so the coverage
    meta-test can diff it against a future registry."""
    return {b.name: sorted(n for n, s in states.items() if b.accepts(s))
            for b in api.list_backends()}


def compute_golden():
    cfg, inc, ta, x = golden_model()
    sums = np.asarray(tm.forward(ta, x, cfg))
    return {
        "config": dict(CFG),
        "seeds": {"include": SEED_INCLUDE, "batch": SEED_BATCH,
                  "program": SEED_PROGRAM},
        "n_batch": N_BATCH,
        "include_sha256": _sha(np.asarray(inc).astype(np.uint8)),
        "batch_sha256": _sha(np.asarray(x)),
        "class_sums": sums.astype(int).tolist(),
        "preds": np.argmax(sums, axis=-1).astype(int).tolist(),
        "backend_coverage": backend_coverage(golden_states(cfg, inc, ta)),
    }


@pytest.fixture(scope="module")
def golden():
    assert os.path.exists(GOLDEN_PATH), (
        f"missing {GOLDEN_PATH} — regenerate with "
        "`PYTHONPATH=src python tests/test_golden.py --regen`")
    with open(GOLDEN_PATH) as f:
        return json.load(f)


def test_golden_inputs_reproduce(golden):
    """Attribution guard: the seeded include mask and request batch must
    hash to the committed digests.  If THIS fails, the jax PRNG stream
    changed (e.g. an upstream threefry change) — the golden file needs a
    deliberate regeneration; the backends have not necessarily drifted."""
    cfg, inc, ta, x = golden_model()
    assert golden["config"] == dict(CFG)
    assert _sha(np.asarray(inc).astype(np.uint8)) == \
        golden["include_sha256"], "jax PRNG stream changed (include mask)"
    assert _sha(np.asarray(x)) == golden["batch_sha256"], \
        "jax PRNG stream changed (request batch)"


def test_digital_reference_matches_golden(golden):
    """``tm.forward`` itself reproduces the committed sums — the
    reference the whole parity matrix hangs off cannot drift silently."""
    cfg, inc, ta, x = golden_model()
    sums = np.asarray(tm.forward(ta, x, cfg))
    np.testing.assert_array_equal(sums, np.asarray(golden["class_sums"]))
    np.testing.assert_array_equal(np.argmax(sums, axis=-1),
                                  np.asarray(golden["preds"]))


def test_every_registered_backend_reproduces_golden(golden):
    """EVERY registered backend, over every state it accepts (packed
    and unpacked wire formats), reproduces the committed class sums and
    preds bit-for-bit at nominal variation.  Iterates the registry, so
    a newly registered backend is automatically held to the golden
    bar — including backends that might drift *together* with the
    digital reference."""
    cfg, inc, ta, x = golden_model()
    states = golden_states(cfg, inc, ta)
    lits = tm.literals(x)
    litw = ops.pack_literals(lits)
    want_sums = np.asarray(golden["class_sums"])
    want_preds = np.asarray(golden["preds"])
    checked = 0
    for backend in api.list_backends():
        packed_io = api.CAP_PACKED_IO in backend.capabilities
        for name, state in states.items():
            if not backend.accepts(state):
                continue
            wires = (lits, litw) if packed_io else (lits,)
            for wire in wires:
                got = np.asarray(api.class_sums(state, wire,
                                                backend=backend.name))
                stacked = got if got.ndim == 3 else got[None]
                for r in range(stacked.shape[0]):
                    np.testing.assert_array_equal(
                        stacked[r], want_sums,
                        err_msg=f"{backend.name}/{name} drifted from "
                                "the committed golden sums")
                    np.testing.assert_array_equal(
                        np.argmax(stacked[r], axis=-1), want_preds,
                        err_msg=f"{backend.name}/{name}")
            checked += 1
    # digital family 5 + analog family 10 + coalesced family 5 cells,
    # + 12 plane-packed cells (the 3 ``*_planes`` states against every
    # backend that accepts them, incl. the packed2 pair) — see
    # test_api.py's parity-matrix census.
    assert checked >= 32, f"only {checked} (backend, state) cells ran"


def test_predict_entrypoint_matches_golden(golden):
    """The uniform ``api.predict`` entry reproduces the committed preds
    for every state family."""
    cfg, inc, ta, x = golden_model()
    states = golden_states(cfg, inc, ta)
    want = np.asarray(golden["preds"])
    for name in ("digital", "crossbar", "stack", "coalesced",
                 "stack_packed", "coalesced_packed",
                 "stack_planes", "coalesced_planes"):
        got = np.asarray(api.predict(states[name], x))
        np.testing.assert_array_equal(got, want, err_msg=name)


def _regen():
    os.makedirs(os.path.dirname(GOLDEN_PATH), exist_ok=True)
    data = compute_golden()
    with open(GOLDEN_PATH, "w") as f:
        json.dump(data, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"wrote {GOLDEN_PATH}")


if __name__ == "__main__":
    if "--regen" in sys.argv:
        _regen()
    else:
        print(__doc__)
