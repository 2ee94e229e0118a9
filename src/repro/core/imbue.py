"""IMBUE: the analog Boolean-to-Current crossbar, simulated in JAX.

This is the paper's primary contribution (§II): TM inference computed as
ReRAM column currents instead of digital logic.

Pipeline (mirrors Fig. 2):

  1. **Program**: trained TA actions -> per-cell memristor resistance
     (include -> LRS, exclude -> HRS), with D2D variation draws.
  2. **Drive**: Boolean literals -> read voltages (logic '1' -> 0 V,
     logic '0' -> 0.2 V; inverted so only *violations* conduct).
  3. **KCL**: each partial-clause column of W=32 cells sums its cell
     currents; the 100 Ω divider converts to a column voltage.
  4. **CSA**: the column voltage is compared against ``v_ref`` (placed in
     the sensing margin between the all-exclude leak band and a single
     include violation); output is the Boolean partial-clause value.
  5. **Digital tail**: AND of partial clauses -> full clause; polarity
     up/down counters -> class sums; comparator -> argmax.

Everything is vectorized: column currents are two einsums (on-path and
leak-path), so the ``[B, C, L]`` per-cell current tensor is never
materialized.  Monte-Carlo studies vmap this module over device draws.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import variations as var
from repro.core.mapping import CrossbarMapping, pad_to_columns
from repro.core.tm import TMConfig, class_sums, include_mask, literals

# Nominal single-cell read currents (Table I).
I_INCLUDE_ON = var.V_READ / (var.SERIES_FACTOR * var.LRS_MEAN_OHM)   # ~75.7 uA
I_EXCLUDE_ON = var.V_READ / (var.SERIES_FACTOR * var.HRS_MEAN_OHM)   # ~1.89 uA
# Precision of the column-current einsums (``column_currents_raw``).
DOT_PRECISION = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class IMBUEConfig:
    """Electrical configuration of the crossbar (paper §II/III)."""

    width: int = 32                 # W: TA cells per partial-clause column
    r_divider: float = 100.0        # column divider resistance (Ω)
    v_read: float = var.V_READ      # literal '0' drive voltage (V)
    series_factor: float = var.SERIES_FACTOR
    # Reference current midway between the all-exclude leak band and one
    # include violation (the "careful design choice" of §II-B).
    v_ref: Optional[float] = None   # None -> computed from width

    def reference_voltage(self) -> float:
        if self.v_ref is not None:
            return self.v_ref
        i_leak_band = self.width * I_EXCLUDE_ON
        i_violation = I_INCLUDE_ON
        return self.r_divider * 0.5 * (i_leak_band + i_violation)

    def sensing_margin(self) -> float:
        """Half-width of the [all-exclude, one-include] current band (V)."""
        return self.r_divider * 0.5 * (I_INCLUDE_ON - self.width * I_EXCLUDE_ON)


@dataclasses.dataclass
class ProgrammedCrossbar:
    """A crossbar with TA actions programmed into memristor states."""

    r_mem: jax.Array        # [C, L] programmed memristor resistance (Ω)
    include: jax.Array      # [C, L] bool TA actions
    mapping: CrossbarMapping
    cfg: IMBUEConfig


def program_crossbar(
    ta_include: jax.Array,             # [C, L] bool include mask
    key: jax.Array,
    vcfg: var.VariationConfig = var.VariationConfig(),
    cfg: IMBUEConfig = IMBUEConfig(),
) -> ProgrammedCrossbar:
    """One-time programming (paper Fig. 5): D2D drawn at SET/RESET time."""
    c, l = ta_include.shape
    r_mem = var.sample_device_resistance(key, ta_include, vcfg)
    return ProgrammedCrossbar(
        r_mem=r_mem, include=ta_include,
        mapping=CrossbarMapping(n_clauses=c, n_literals=l, width=cfg.width),
        cfg=cfg)


def conductances(
    r_mem: jax.Array,                 # [..., C, L] programmed resistance (Ω)
    include: jax.Array,               # [C, L] bool TA actions
    cfg: IMBUEConfig,
    key: Optional[jax.Array] = None,
    vcfg: var.VariationConfig = var.VariationConfig(),
):
    """Per-cell on-path conductance and leak current for one read cycle.

    Array-level twin of :func:`cell_conductances` so replica stacks
    ``[R, C, L]`` can vmap over device draws without materializing one
    ``ProgrammedCrossbar`` per replica.
    """
    r = r_mem
    if key is not None:
        r = var.apply_c2c(key, r, include, vcfg)
    g_on = 1.0 / (cfg.series_factor * r)                    # [..., C, L] S
    # Leak at literal '1' scales with 1/R around the Table I operating point.
    i_leak_nom = jnp.where(include, var.I_LEAK_INCLUDE,
                           var.I_LEAK_EXCLUDE)
    r_nom = jnp.where(include, var.LRS_MEAN_OHM, var.HRS_MEAN_OHM)
    i_leak = i_leak_nom * (r_nom / r)
    return g_on, i_leak


def cell_conductances(xbar: ProgrammedCrossbar, key: Optional[jax.Array],
                      vcfg: var.VariationConfig):
    """Per-cell on-path conductance and leak current for this read cycle."""
    return conductances(xbar.r_mem, xbar.include, xbar.cfg, key, vcfg)


def column_currents_raw(
    g_on: jax.Array,                  # [C, L] on-path conductance (S)
    i_leak: jax.Array,                # [C, L] leak current (A)
    lits: jax.Array,                  # [B, L] uint8
    mapping: CrossbarMapping,
    cfg: IMBUEConfig,
) -> jax.Array:
    """KCL column currents ``[B, C, columns_per_clause]`` (amps)."""
    lit0 = pad_to_columns((1 - lits).astype(jnp.float32) * cfg.v_read,
                          mapping)                            # [B, K, W] volts
    lit1 = pad_to_columns(lits.astype(jnp.float32), mapping)  # [B, K, W]
    g_on_f = pad_to_columns(g_on, mapping)                    # [C, K, W]
    i_leak_f = pad_to_columns(i_leak, mapping)
    # f32 products with f32 accumulation on every device.  At a TPU's
    # default precision both operands are rounded to bfloat16 (8
    # significant bits: up to 0.2% each, 0.1% for the 0.2 V drive),
    # which flips clauses whose column current lies that close to the
    # reference, as D2D variation puts some.
    on = jnp.einsum("bkw,ckw->bck", lit0, g_on_f, precision=DOT_PRECISION)
    leak = jnp.einsum("bkw,ckw->bck", lit1, i_leak_f,
                      precision=DOT_PRECISION)
    return on + leak


def column_currents(
    xbar: ProgrammedCrossbar,
    lits: jax.Array,                  # [B, L] uint8
    key: Optional[jax.Array] = None,
    vcfg: var.VariationConfig = var.VariationConfig(),
) -> jax.Array:
    """KCL column currents ``[B, C, columns_per_clause]`` (amps)."""
    g_on, i_leak = cell_conductances(xbar, key, vcfg)
    return column_currents_raw(g_on, i_leak, lits, xbar.mapping, xbar.cfg)


def csa_sense(
    i_col: jax.Array,                 # [..., columns] column currents
    cfg: IMBUEConfig,
    key: Optional[jax.Array] = None,
    vcfg: var.VariationConfig = var.VariationConfig(),
) -> jax.Array:
    """CSA compare (Fig. 4a): partial clause = 1 iff V_col < V_ref+offset."""
    v_col = i_col * cfg.r_divider
    v_ref = cfg.reference_voltage()
    off = (var.csa_offset(key, i_col.shape, vcfg)
           if key is not None else 0.0)
    return (v_col < v_ref + off).astype(jnp.uint8)


def analog_clause_outputs_raw(
    r_mem: jax.Array,                 # [C, L] programmed resistance (Ω)
    include: jax.Array,               # [C, L] bool
    lits: jax.Array,                  # [B, L]
    mapping: CrossbarMapping,
    cfg: IMBUEConfig,
    key: Optional[jax.Array] = None,
    vcfg: var.VariationConfig = var.VariationConfig(),
) -> jax.Array:
    """Clause outputs ``[B, C]`` from raw device arrays (vmap-friendly)."""
    if key is not None:
        k_c2c, k_csa = jax.random.split(key)
    else:
        k_c2c = k_csa = None
    g_on, i_leak = conductances(r_mem, include, cfg, k_c2c, vcfg)
    i_col = column_currents_raw(g_on, i_leak, lits, mapping, cfg)
    partial = csa_sense(i_col, cfg, k_csa, vcfg)              # [B, C, K]
    return jnp.min(partial, axis=-1)                          # AND over cols


def analog_clause_outputs(
    xbar: ProgrammedCrossbar,
    lits: jax.Array,                  # [B, L]
    key: Optional[jax.Array] = None,
    vcfg: var.VariationConfig = var.VariationConfig(),
) -> jax.Array:
    """Full clause outputs ``[B, C]`` via partial-clause AND (Fig. 4b)."""
    return analog_clause_outputs_raw(xbar.r_mem, xbar.include, lits,
                                     xbar.mapping, xbar.cfg, key, vcfg)


def analog_forward(
    xbar: ProgrammedCrossbar,
    x: jax.Array,                     # [B, F] raw Boolean features
    tm_cfg: TMConfig,
    key: Optional[jax.Array] = None,
    vcfg: var.VariationConfig = var.VariationConfig(),
) -> jax.Array:
    """Class sums ``[B, M]`` from the analog crossbar."""
    lits = literals(x)
    cls = analog_clause_outputs(xbar, lits, key, vcfg)
    # Digital tail: the control unit masks empty clauses at inference.
    nonempty = xbar.include.any(axis=-1)
    cls = cls * nonempty[None, :].astype(cls.dtype)
    return class_sums(cls, tm_cfg)


def analog_predict(xbar, x, tm_cfg, key=None,
                   vcfg: var.VariationConfig = var.VariationConfig()):
    return jnp.argmax(analog_forward(xbar, x, tm_cfg, key, vcfg), axis=-1)


# --------------------------------------------------------------------------
# Replica stacks (multi-chip deployments / ensemble serving)
# --------------------------------------------------------------------------

def program_replica_stack(
    ta_include: jax.Array,             # [C, L] bool include mask
    key: jax.Array,
    n_replicas: int,
    vcfg: var.VariationConfig = var.VariationConfig(),
) -> jax.Array:
    """Program ``R`` independent chips: stacked resistances ``[R, C, L]``.

    Each replica gets its own D2D draw — the physical model of programming
    the same trained TM into R distinct crossbars (one per serving chip).
    """
    keys = jax.random.split(key, n_replicas)
    return jax.vmap(
        lambda k: var.sample_device_resistance(k, ta_include, vcfg))(keys)


@partial(jax.jit, static_argnames=("tm_cfg", "vcfg", "cfg"))
def stacked_clause_outputs(
    r_stack: jax.Array,                # [R, C, L] per-replica resistance
    include: jax.Array,                # [C, L] bool (shared TA actions)
    lits: jax.Array,                   # [B, L]
    tm_cfg: TMConfig,
    key: Optional[jax.Array] = None,
    vcfg: var.VariationConfig = var.VariationConfig(),
    cfg: IMBUEConfig = IMBUEConfig(),
) -> jax.Array:
    """Clause outputs ``[R, B, C]``, fresh C2C+CSA noise per replica."""
    c, l = include.shape
    mapping = CrossbarMapping(n_clauses=c, n_literals=l, width=cfg.width)
    if key is None:
        return jax.vmap(lambda r: analog_clause_outputs_raw(
            r, include, lits, mapping, cfg, None, vcfg))(r_stack)
    keys = jax.random.split(key, r_stack.shape[0])
    return jax.vmap(lambda r, k: analog_clause_outputs_raw(
        r, include, lits, mapping, cfg, k, vcfg))(r_stack, keys)


@partial(jax.jit, static_argnames=("tm_cfg", "vcfg", "cfg"))
def stacked_class_sums(
    r_stack: jax.Array,                # [R, C, L]
    include: jax.Array,                # [C, L] bool
    x: jax.Array,                      # [B, F] raw Boolean features
    tm_cfg: TMConfig,
    key: Optional[jax.Array] = None,
    vcfg: var.VariationConfig = var.VariationConfig(),
    cfg: IMBUEConfig = IMBUEConfig(),
) -> jax.Array:
    """Per-replica class sums ``[R, B, M]`` (the stacked analog forward)."""
    lits = literals(x)
    cls = stacked_clause_outputs(r_stack, include, lits, tm_cfg, key,
                                 vcfg, cfg)                    # [R, B, C]
    nonempty = include.any(axis=-1)                            # [C]
    cls = cls * nonempty[None, None, :].astype(cls.dtype)
    return class_sums(cls, tm_cfg)


# --------------------------------------------------------------------------
# Monte-Carlo variation studies (paper §III-C / Fig. 7)
# --------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("tm_cfg", "vcfg", "draws"))
def monte_carlo_accuracy(
    ta_state: jax.Array,
    x: jax.Array,
    y: jax.Array,
    key: jax.Array,
    tm_cfg: TMConfig,
    vcfg: var.VariationConfig = var.VariationConfig(),
    draws: int = 16,
) -> jax.Array:
    """Accuracy distribution over independent device/cycle draws ``[draws]``.

    Each draw programs a fresh crossbar (D2D), then evaluates the batch
    under fresh C2C + CSA-offset noise — i.e. one manufactured chip and one
    read cycle per draw.
    """
    inc = include_mask(ta_state, tm_cfg)

    def one(k):
        k_prog, k_read = jax.random.split(k)
        xbar = program_crossbar(inc, k_prog, vcfg)
        pred = analog_predict(xbar, x, tm_cfg, k_read, vcfg)
        return (pred == y).mean()

    return jax.vmap(one)(jax.random.split(key, draws))


@partial(jax.jit, static_argnames=("tm_cfg", "vcfg", "draws"))
def clause_error_rate(
    ta_state: jax.Array,
    x: jax.Array,
    key: jax.Array,
    tm_cfg: TMConfig,
    vcfg: var.VariationConfig = var.VariationConfig(),
    draws: int = 16,
) -> jax.Array:
    """Fraction of (datapoint, clause) cells where the analog readout
    disagrees with the digital oracle, per draw."""
    from repro.core.tm import clause_outputs  # local to avoid cycle
    inc = include_mask(ta_state, tm_cfg)
    lits = literals(x)
    oracle = clause_outputs(ta_state, lits, tm_cfg, training=True)

    def one(k):
        k_prog, k_read = jax.random.split(k)
        xbar = program_crossbar(inc, k_prog, vcfg)
        got = analog_clause_outputs(xbar, lits, k_read, vcfg)
        return (got != oracle).mean()

    return jax.vmap(one)(jax.random.split(key, draws))
