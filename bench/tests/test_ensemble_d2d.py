"""The ensemble path under device-to-device variation against the plain
reference, on the CPU: the harness's tiny D2D cell widened to a few
hundred clauses and served as R=2 and R=4 ensembles through
``system.build`` and the open loop (Pallas in interpret mode)."""

import json
import os
import sys

import numpy as np
import pytest

from test_bench_harness import BM, ROOT, _tiny, fmnist_d2d

import check  # noqa: E402  (the harness module puts bench/ on the path)
import reference  # noqa: E402

SEED = 2 ** 31 + 5


def _observe(seen):
    """A ``tamper`` that changes nothing: it keeps every request the
    engine is sent and the prediction and class sums handed back."""

    def hook(engine):
        submit, take = engine.submit, engine.take

        def observed_submit(x, **kw):
            rid = submit(x, **kw)
            seen[rid] = {"x": np.array(x, np.uint8)}
            return rid

        def observed_take(rid):
            resp = take(rid)
            if resp is not None:
                seen[rid].update(pred=int(resp.pred),
                                 sums=np.asarray(resp.class_sums))
            return resp

        engine.submit, engine.take = observed_submit, observed_take
        seen["backend"] = engine.backend.name
        seen["dots"] = engine.metrics.crossbar_dots

    return hook


@pytest.mark.parametrize("replicas", [2, 4])
def test_ensemble_d2d_open_loop_matches_the_reference(tmp_path, monkeypatch,
                                                      replicas):
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    import jax.numpy as jnp
    import run
    import work
    from repro.serve.replica import ensemble_vote
    monkeypatch.setitem(work.PEAKS, jax.devices()[0].device_kind,
                        {"flops_per_s": 1e12, "bytes_per_s": 1e11})
    monkeypatch.setattr(run, "CACHE", str(tmp_path / "cache"))
    base = _tiny(tmp_path)
    config = fmnist_d2d()
    config["model"].update(classes=10, clauses_per_class=30, features=256,
                           includes=500)
    config["pool"]["replicas"] = replicas
    config["engine"].update(max_batch=16, buckets=[8, 16])
    config["check"]["sum_gap"] = 0.0
    (tmp_path / "configs" / "ens.json").write_text(json.dumps(config))
    # Sparse images drive whole columns, whose D2D leak band reaches the
    # reference: many clauses then sit within a bfloat16 rounding of it.
    traffic = {"loop": "open", "arrivals": {"process": "poisson",
                                            "rate_per_s": 200,
                                            "shape_seed": 0},
               "payload": {"pool": 64, "density": 0.03}}
    (tmp_path / "traffic" / "sparse.json").write_text(json.dumps(traffic))
    cell = {"name": "c_ens", "config": "ens", "traffic": "sparse",
            "chips": 1}
    seen = {}
    r = run.run_cell(BM, cell, SEED, 0.6, False, jax.devices()[:1],
                     base=base, tamper=_observe(seen))
    assert seen.pop("backend") == "analog-pallas-packed2"
    assert seen.pop("dots") == "bf16x3"
    assert r["correct"] and r["attempted"] > 0
    assert r["check"]["sum_gap"]["value"] == 0.0

    served = [v for v in seen.values() if "pred" in v]
    assert len(served) >= r["attempted"]
    x = np.stack([v["x"] for v in served])
    mu = check._margins(config, SEED, x, jnp.float32)          # [N, R, C]
    per_replica = reference.class_sums(mu < 0, 10)              # [N, R, M]
    assert np.array_equal(np.stack([v["sums"] for v in served]),
                          per_replica.sum(axis=1))
    want = np.asarray(ensemble_vote(jnp.asarray(
        per_replica.transpose(1, 0, 2)), "majority"))
    assert np.array_equal(np.array([v["pred"] for v in served]), want)
    # The test has teeth: many clauses lie within a bfloat16 rounding
    # (2**-9) of the reference, where one-pass dots would flip them.
    assert (np.abs(mu) < 2.0 ** -9).sum() > 20
