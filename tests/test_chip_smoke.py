"""``chip_smoke.py``'s phases at a tiny shape on CPU.

The fused kernels run in interpret mode here (their normal off-TPU
default), so these tests hold the smoke's bit-exactness checks to the
same bar the chip run does, and check that the script refuses to report
success off a TPU.
"""

from __future__ import annotations

import importlib.util
import os
import subprocess
import sys
import textwrap
import warnings
from pathlib import Path

import numpy as np
import pytest

from repro.core.tm import TMConfig
from repro.core.variations import VariationConfig
from repro.serve import EngineConfig

ROOT = Path(__file__).resolve().parents[1]
_spec = importlib.util.spec_from_file_location("chip_smoke",
                                               ROOT / "chip_smoke.py")
smoke = importlib.util.module_from_spec(_spec)
sys.modules["chip_smoke"] = smoke        # dataclasses resolve annotations
_spec.loader.exec_module(smoke)

# Seed 2 at this shape is one where D2D + C2C moves some class sums in
# every noisy leg (phase B's ensemble, the mesh phase's two routings).
TINY = smoke.SmokeConfig(
    tm_cfg=TMConfig(n_classes=3, clauses_per_class=8, n_features=64),
    includes=60, n_replicas=2, n_requests=24, seed=2)


@pytest.fixture(scope="module")
def model():
    ta, x = smoke.make_model(TINY)
    return ta, x, smoke.reference_sums(ta, x, TINY.tm_cfg)


def test_model_has_exact_include_count(model):
    ta, x, ref = model
    cfg = TINY.tm_cfg
    assert int((np.asarray(ta) > cfg.n_states).sum()) == TINY.includes
    assert x.shape == (TINY.n_requests, cfg.n_features)
    assert ref.shape == (TINY.n_requests, cfg.n_classes)
    assert np.abs(ref).sum() > 0          # some clause fires somewhere


def test_phase_a_every_path_equals_reference(model):
    reports = smoke.phase_a(TINY, *model)
    assert [r["variant"] for r in reports] == [p[0] for p in smoke.PHASE_A]
    assert [r["backend"] for r in reports] == [p[3] for p in smoke.PHASE_A]
    assert all(r["rows_equal"] == r["rows"] > 0 for r in reports)


def test_phase_b_fused_kernels_agree_under_noise(model):
    reports = smoke.phase_b(TINY, *model)
    assert [r["backend"] for r in reports] == [
        "analog-pallas-packed2", "analog-pallas-packed", "analog-pallas"]
    assert all(r["rows_equal_across_kernels"] == TINY.n_requests
               for r in reports)
    assert all(r["rows_off_digital"] > 0 for r in reports)


def test_phase_b_fails_when_noise_moves_nothing():
    """Dense requests leave every decision far from the CSA reference:
    the kernels would agree whatever their arithmetic, so phase B must
    refuse to call that agreement a pass."""
    ta, _ = smoke.make_model(TINY)
    rng = np.random.default_rng(TINY.seed)
    x = rng.integers(0, 2, (TINY.n_requests, TINY.tm_cfg.n_features),
                     dtype=np.uint8)
    with pytest.raises(smoke.SmokeFailure, match="cannot fail"):
        smoke.phase_b(TINY, ta, x, smoke.reference_sums(ta, x, TINY.tm_cfg))


def test_check_engine_rejects_a_fallback(model):
    """A csa_offset pool falls back off the fused kernel; the smoke must
    call that a failure, not serve through it."""
    ta, _, _ = model
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        engine = smoke.build(TINY, ta, EngineConfig(),
                             vcfg=VariationConfig())
    with pytest.raises(smoke.SmokeFailure, match="selected analog-jnp"):
        smoke.check_engine(engine, "analog-pallas-packed2", TINY)


def test_main_refuses_off_tpu(capsys):
    assert smoke.main([]) != 0
    out = capsys.readouterr()
    assert '"ok": true' not in out.out
    assert "needs a TPU" in out.err


def test_mesh_phase_on_forced_host_devices():
    """The ``--chips 4`` phase on 4 forced CPU devices (a child process:
    the device count is fixed when JAX starts)."""
    code = textwrap.dedent(f"""
        import importlib.util, sys
        spec = importlib.util.spec_from_file_location(
            "chip_smoke", {str(ROOT / "chip_smoke.py")!r})
        smoke = importlib.util.module_from_spec(spec)
        sys.modules["chip_smoke"] = smoke
        spec.loader.exec_module(smoke)
        from repro.core.tm import TMConfig
        sc = smoke.SmokeConfig(
            tm_cfg=TMConfig(n_classes=3, clauses_per_class=8,
                            n_features=64),
            includes=60, n_replicas=4, n_requests=16, seed=2)
        ta, x = smoke.make_model(sc)
        ref = smoke.reference_sums(ta, x, sc.tm_cfg)
        reports = smoke.phase_mesh(sc, ta, x, ref, 4)
        assert len(reports) == 4, reports
        assert all(r["rows_off_digital"] > 0 for r in reports
                   if r["noise"] != "nominal"), reports
        print("MESH_OK", len(reports))
    """)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = str(ROOT / "src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "MESH_OK 4" in out.stdout
