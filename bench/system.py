"""The system under test, built from a configuration file.

This is the one module of the benchmark that imports the program
(``repro``, under ``src/``).  It builds the engine the configuration
names through the program's own constructors and observes it through
two hooks that change nothing it computes: a ``take`` that keeps the
class sums it hands out, and a record of every dispatch (issue time,
bucket, valid rows) taken where the engine books it.
"""

from __future__ import annotations

import os
import sys

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "src")


def import_program():
    """Put the program on the path; False where the checkout lacks it."""
    if not os.path.isdir(os.path.join(SRC, "repro")):
        return False
    if SRC not in sys.path:
        sys.path.insert(0, SRC)
    return True


def chips(config: dict) -> int:
    """The devices a configuration serves from: the product of its
    ``mesh`` axes (``{"replica": R, "batch": B}``), 1 without a mesh."""
    n = 1
    for size in config.get("mesh", {}).values():
        n *= int(size)
    return n


def replica_mesh(config: dict, devices):
    """The configuration's ``("replica", "batch")`` serving mesh over
    ``devices`` (the program's axis names and rules), or None where the
    configuration has no mesh."""
    if "mesh" not in config:
        return None
    import numpy as np
    from jax.sharding import AxisType, Mesh
    m = config["mesh"]
    shape = (int(m.get("replica", 1)), int(m.get("batch", 1)))
    return Mesh(np.asarray(devices, dtype=object).reshape(shape),
                ("replica", "batch"), axis_types=(AxisType.Auto,) * 2)


def build(config: dict, seed: int, generator, devices):
    """``(engine, tm_cfg)``: the configuration's pool programmed from the
    seeded TA state, behind the configuration's engine class, on
    ``devices`` (sharded over the configuration's mesh where it has
    one).  Raises ValueError where ``devices`` is not what the
    configuration serves from."""
    import jax
    from repro.core.tm import TMConfig
    from repro.core.variations import VariationConfig
    from repro.serve import (AsyncServeEngine, BatcherConfig, EngineConfig,
                             ServeEngine)

    if len(devices) != chips(config):
        raise ValueError(f"the configuration serves from {chips(config)} "
                         f"device(s); the cell gives {len(devices)}")
    mesh = replica_mesh(config, devices)
    m, p, e = config["model"], config["pool"], config["engine"]
    tm_cfg = TMConfig(n_classes=m["classes"],
                      clauses_per_class=m["clauses_per_class"],
                      n_features=m["features"], n_states=m["states"])
    ta = generator.ta_state(generator.model_key(seed, generator.STREAM_WEIGHTS),
                            clauses=tm_cfg.n_clauses,
                            literals=tm_cfg.n_literals,
                            includes=m["includes"], states=m["states"])
    ecfg = EngineConfig(
        batcher=BatcherConfig(max_batch=e["max_batch"],
                              max_wait_s=e["max_wait_s"],
                              bucket_sizes=tuple(e["buckets"])),
        routing=e["routing"], max_in_flight=e["max_in_flight"],
        packed=e["packed"], pack_planes=e["pack_planes"])
    base = {"AsyncServeEngine": AsyncServeEngine,
            "ServeEngine": ServeEngine}[e["class"]]
    engine = recording(base).from_ta_state(
        ta, tm_cfg, n_replicas=p["replicas"],
        key=generator.model_key(seed, generator.STREAM_ENGINE),
        vcfg=VariationConfig(**p["variation"]), ecfg=ecfg, mesh=mesh)
    if (mesh is not None) != engine.state.is_sharded:
        raise RuntimeError(f"the engine's state is sharded: "
                           f"{engine.state.is_sharded}; the configuration's "
                           f"mesh: {config.get('mesh')}")
    if engine.backend.name != e["expect_backend"] or engine.selection.fell_back:
        raise RuntimeError(
            f"engine selected {engine.backend.name} "
            f"({engine.selection.fallback_reason}), the configuration "
            f"expects {e['expect_backend']}")
    watch_dispatches(engine)
    jax.block_until_ready(engine.state)
    return engine, tm_cfg


def recording(base):
    """``base`` with a ``take`` that keeps each handed-out class sum."""

    class Recording(base):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self.taken_sums = {}

        def take(self, rid):
            resp = super().take(rid)
            if resp is not None:
                self.taken_sums[rid] = resp.class_sums
            return resp

    Recording.__name__ = base.__name__
    return Recording


def watch_dispatches(engine) -> None:
    """Append ``(t_issue, bucket, valid_rows)`` to ``engine.dispatches``
    for every batch the engine books."""
    engine.dispatches = []
    book = engine.metrics.record_batch

    def record_batch(records, bucket, nbytes, **kw):
        if records:
            engine.dispatches.append((records[0].t_dispatch, bucket,
                                      len(records)))
        return book(records, bucket, nbytes, **kw)

    engine.metrics.record_batch = record_batch


def stream_server(engine, config: dict, fit_frames):
    """A ``StreamServer`` over ``engine`` with the configuration's
    windowing and a booleanizer fitted to ``fit_frames``."""
    from repro.core.booleanize import fit_quantile
    from repro.serve.stream import StreamConfig, StreamServer
    s = config["stream"]
    booleanizer = fit_quantile(fit_frames, bits=s["bits"])
    return StreamServer(engine, booleanizer,
                        StreamConfig(window=s["window"], hop=s["hop"]))
