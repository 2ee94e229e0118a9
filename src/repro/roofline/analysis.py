"""Roofline analysis from compiled HLO.

XLA's ``compiled.cost_analysis()`` counts a while (scan) body ONCE —
verified empirically (tests/test_roofline.py) — and our stacks scan over
layers, so raw numbers undercount by ~n_layers.  This module therefore
walks the *optimized, SPMD-partitioned* HLO text itself:

* **flops**: every ``dot`` (2 x prod(result dims) x prod(lhs contracting
  dims)), recursing into fusion/call/while computations, with while-body
  costs multiplied by the loop trip count parsed from the loop condition
  (jax scans lower to counted loops — the condition compares the
  induction variable against a constant).
* **bytes**: per instruction at fusion granularity (operand + result
  buffer sizes of compute ops) — a post-fusion proxy for HBM traffic.
* **collective_bytes**: operand bytes of all-reduce / all-gather /
  reduce-scatter / all-to-all / collective-permute (x2 algorithmic factor
  for all-reduce), same while scaling.

Because the module is already partitioned, all shapes are per-device:
``compute_s = flops / peak_flops`` directly (no further /chips).

Hardware peaks come from :data:`PEAKS`, keyed by JAX's ``device_kind``
with their published source; a device that is not in the table is an
error, never a silent v5e default.  Code that runs beside a chip keys
by that chip's ``device_kind``; only host-side models with no chip
attached (the dry-run, the kernel bench's analytic roofline off a TPU)
name their target chip, :data:`TARGET_KIND`.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

@dataclasses.dataclass(frozen=True)
class Peaks:
    """Published per-chip peaks of one TPU generation."""

    bf16_flops: float        # FLOP/s
    hbm_bw: float            # bytes/s
    ici_bw: float            # bytes/s per link (one link per direction)
    source: str


PEAKS: Dict[str, Peaks] = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM
    # at 819 GB/s, 1,600 Gbit/s ICI per chip over 4 links (50 GB/s each).
    "TPU v5 lite": Peaks(bf16_flops=197e12, hbm_bw=819e9, ici_bw=50e9,
                         source="Google Cloud documentation, TPU v5e"),
}
TARGET_KIND = "TPU v5 lite"      # the deployment chip the dry-run models


def peaks(device_kind: str) -> Peaks:
    """Peaks of ``device_kind`` (``jax.devices()[0].device_kind``)."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; add it "
            f"to repro.roofline.analysis.PEAKS with its source "
            f"(known: {sorted(PEAKS)})") from None

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")


def _shape_bytes(dtype: str, dims: str) -> int:
    bpe = _DTYPE_BYTES.get(dtype)
    if bpe is None:
        return 0
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * bpe


def _all_shape_bytes(text: str) -> int:
    return sum(_shape_bytes(d, s) for d, s in _SHAPE_RE.findall(text))


@dataclasses.dataclass
class _Instr:
    name: str
    op: str
    result: str          # result type text
    args: str            # text inside the op's parentheses
    attrs: str           # text after the closing paren


_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*((?:\([^()]*\)|[a-z][a-z0-9]*\["
    r"[0-9,]*\](?:\{[^}]*\})?))\s+([\w\-]+)\((.*?)\)(.*)$")


def parse_computations(hlo: str) -> Tuple[Dict[str, List[_Instr]],
                                          Dict[str, Dict[str, str]]]:
    """computation name -> instruction list (entry as '@entry'), plus a
    per-computation map of instruction name -> result type text (modern
    HLO references operands by name without inline shapes)."""
    comps: Dict[str, List[_Instr]] = {}
    types: Dict[str, Dict[str, str]] = {}
    cur: Optional[str] = None
    for line in hlo.splitlines():
        header = re.match(r"^(ENTRY\s+)?%?([\w\.\-]+)\s*\(.*\)\s*->.*\{",
                          line)
        if header:
            cur = "@entry" if header.group(1) else header.group(2)
            comps[cur] = []
            types[cur] = {}
            continue
        if line.startswith("}"):
            cur = None
            continue
        if cur is None:
            continue
        m = _INSTR_RE.match(line)
        if m:
            ins = _Instr(name=m.group(1), result=m.group(2), op=m.group(3),
                         args=m.group(4), attrs=m.group(5))
            comps[cur].append(ins)
            types[cur][ins.name] = ins.result
    return comps, types


_NAME_RE = re.compile(r"%([\w\.\-]+)")


def _operand_types(ins: _Instr, comp_types: Dict[str, str]) -> List[str]:
    """Result-type texts of an instruction's operands (resolved by name,
    falling back to inline shapes for older HLO dumps)."""
    out = []
    for tok in ins.args.split(","):
        tok = tok.strip()
        if not tok:
            continue
        inline = _SHAPE_RE.findall(tok.split("%")[0])
        nm = _NAME_RE.search(tok)
        if nm and nm.group(1) in comp_types:
            out.append(comp_types[nm.group(1)])
        elif inline:
            out.append(tok)
    return out


def _trip_count(cond_instrs: List[_Instr]) -> int:
    """Trip count of a counted loop: the largest integer constant compared
    against in the condition computation (jax scans compare the induction
    variable to the length)."""
    best = 1
    for ins in cond_instrs:
        if ins.op == "constant":
            m = re.search(r"constant\((\d+)\)", f"constant({ins.args})")
            if m:
                best = max(best, int(m.group(1)))
        for m in re.finditer(r"constant\((\d+)\)", ins.args + ins.attrs):
            best = max(best, int(m.group(1)))
    return best


def _dot_flops(ins: _Instr, comp_types: Dict[str, str]) -> float:
    res = _SHAPE_RE.findall(ins.result)
    if not res:
        return 0.0
    _, rdims = res[0]
    rprod = 1
    for d in rdims.split(","):
        if d:
            rprod *= int(d)
    ops = _operand_types(ins, comp_types)
    if not ops:
        return 0.0
    lhs = _SHAPE_RE.findall(ops[0])
    if not lhs:
        return 0.0
    lhs_dims = [int(d) for d in lhs[0][1].split(",") if d]
    m = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", ins.attrs)
    cprod = 1
    if m and m.group(1):
        for ix in m.group(1).split(","):
            i = int(ix)
            if i < len(lhs_dims):
                cprod *= lhs_dims[i]
    return 2.0 * rprod * cprod


_SKIP_BYTES_OPS = {
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "while", "conditional", "call", "after-all", "partition-id",
    "replica-id", "copy-start", "copy-done", "opt-barrier", "domain",
    "get-dimension-size",
}


class HloCost:
    def __init__(self, hlo: str):
        self.comps, self.types = parse_computations(hlo)
        self.flops = 0.0
        self.bytes = 0.0
        self.collective_bytes = 0.0
        self.collective_detail: Dict[str, float] = {}
        self.loops: List[Tuple[str, int]] = []
        if "@entry" in self.comps:
            self._walk("@entry", 1.0, count_bytes=True)

    def _callee(self, attrs: str, key: str) -> Optional[str]:
        m = re.search(key + r"=%?([\w\.\-]+)", attrs)
        return m.group(1) if m else None

    def _io_bytes(self, ins: _Instr, comp: str) -> float:
        """HBM-traffic proxy for one instruction: result + operand bytes,
        with slice-aware corrections:

        * dynamic-slice / slice / gather read only the slice (2x result),
          not the sliced-into buffer (scan reads a [L, ...] weight stack
          one layer at a time — counting the stack per iteration would
          overcount L x);
        * dynamic-update-slice writes only the update (in-place aliasing
          inside loops), so 2 x update-operand bytes;
        * fusion operands > 8x the result are treated as slice-reads of a
          stack/cache and skipped (the slicing happens inside the fusion).
        """
        rb = _all_shape_bytes(ins.result)
        if ins.op in ("dynamic-slice", "slice", "gather"):
            return 2.0 * rb
        ops = _operand_types(ins, self.types.get(comp, {}))
        if ins.op == "dynamic-update-slice":
            upd = _all_shape_bytes(ops[1]) if len(ops) > 1 else rb
            return 2.0 * upd
        if ins.op == "fusion" and "dynamic-update-slice" in ins.name:
            # DUS-rooted fusion: writes only the update slice (the result
            # buffer is aliased in-place) — count the slice-sized
            # operands, not the stack-sized result.
            small = [b for t in ops
                     if (b := _all_shape_bytes(t)) < rb]
            return 2.0 * (sum(small) if small else rb)
        ob = 0.0
        for t in ops:
            b = _all_shape_bytes(t)
            if ins.op == "fusion" and b > 8.0 * max(rb, 1.0):
                continue
            ob += b
        return rb + ob

    def _operand_bytes(self, ins: _Instr, comp: str) -> float:
        ops = _operand_types(ins, self.types.get(comp, {}))
        return sum(_all_shape_bytes(t) for t in ops)

    def _walk(self, comp: str, mult: float, count_bytes: bool):
        for ins in self.comps.get(comp, []):
            op = ins.op
            if op == "while":
                cond = self._callee(ins.attrs, "condition")
                body = self._callee(ins.attrs, "body")
                trip = _trip_count(self.comps.get(cond, [])) if cond else 1
                self.loops.append((body or "?", trip))
                if body:
                    self._walk(body, mult * trip, count_bytes)
                continue
            if op == "conditional":
                for m in re.finditer(r"%?([\w\.\-]+)", ins.attrs):
                    if m.group(1) in self.comps and \
                            "branch" in ins.attrs[:m.start(1)][-40:]:
                        self._walk(m.group(1), mult, count_bytes)
                continue
            if op == "call":
                callee = self._callee(ins.attrs, "to_apply")
                if callee:
                    self._walk(callee, mult, count_bytes)
                continue
            if op == "fusion":
                callee = self._callee(ins.attrs, "calls")
                if callee:
                    self._walk(callee, mult, count_bytes=False)
                if count_bytes:
                    self.bytes += mult * self._io_bytes(ins, comp)
                continue
            base = op[:-6] if op.endswith("-start") else op
            if base in _COLLECTIVES and not op.endswith("-done"):
                factor = 2.0 if base == "all-reduce" else 1.0
                nbytes = mult * factor * self._operand_bytes(ins, comp)
                self.collective_bytes += nbytes
                self.collective_detail[base] = \
                    self.collective_detail.get(base, 0.0) + nbytes
                if count_bytes:
                    self.bytes += mult * self._io_bytes(ins, comp)
                continue
            if op == "dot":
                self.flops += mult * _dot_flops(ins,
                                                self.types.get(comp, {}))
            if count_bytes and op not in _SKIP_BYTES_OPS:
                self.bytes += mult * self._io_bytes(ins, comp)


def collective_bytes(hlo: str) -> Dict[str, float]:
    cost = HloCost(hlo)
    return {"total": cost.collective_bytes, **cost.collective_detail}


def model_flops_per_step(cfg, params_abs, kind: str, global_batch: int,
                         seq: int) -> float:
    """Analytic MODEL_FLOPS: 6*N*D (train) / 2*N_active*D (fwd-only),
    N = active non-embedding params."""
    total = 0
    expert_total = 0
    import jax
    from repro.distributed.sharding import _path_str

    def visit(path, leaf):
        nonlocal total, expert_total
        p = _path_str(path)
        n = 1
        for d in leaf.shape:
            n *= d
        if "embeddings" in p:
            return
        total += n
        if "experts_" in p:
            expert_total += n
    jax.tree_util.tree_map_with_path(visit, params_abs)
    active = total
    if cfg.moe is not None:
        frac = cfg.moe.top_k / cfg.moe.n_experts
        active = total - expert_total + expert_total * frac
    tokens = global_batch * (seq if kind in ("train", "prefill") else 1)
    mult = 6.0 if kind == "train" else 2.0
    return mult * active * tokens


def analyze_compiled(arch, shape, mesh, cfg, compiled, cost, mem, coll,
                     params_abs=None) -> dict:
    """One §Roofline record (all quantities PER DEVICE) for the dry-run's
    target chip, :data:`TARGET_KIND`."""
    from repro.launch.shapes import SHAPES
    spec = SHAPES[shape]
    hlo_cost = HloCost(compiled.as_text())
    n_dev = 1
    for v in mesh.shape.values():
        n_dev *= v
    if params_abs is None:
        from repro.launch.shapes import abstract_params
        params_abs = abstract_params(cfg)
    mflops = model_flops_per_step(cfg, params_abs, spec.kind,
                                  spec.global_batch, spec.seq)
    flops_dev = hlo_cost.flops
    bytes_dev = hlo_cost.bytes
    coll_dev = hlo_cost.collective_bytes
    pk = peaks(TARGET_KIND)
    compute_s = flops_dev / pk.bf16_flops
    memory_s = bytes_dev / pk.hbm_bw
    coll_s = coll_dev / pk.ici_bw
    dominant = max((("compute", compute_s), ("memory", memory_s),
                    ("collective", coll_s)), key=lambda kv: kv[1])[0]
    mesh_name = "x".join(f"{k}={v}" for k, v in mesh.shape.items())
    return {
        "arch": arch, "shape": shape, "mesh": mesh_name, "devices": n_dev,
        "kind": spec.kind,
        "hlo_flops": flops_dev * n_dev,          # global
        "hlo_bytes": bytes_dev * n_dev,
        "collective_bytes": coll_dev * n_dev,
        "per_device": {"flops": flops_dev, "bytes": bytes_dev,
                       "collective_bytes": coll_dev},
        "compute_s": compute_s, "memory_s": memory_s,
        "collective_s": coll_s, "dominant": dominant,
        "model_flops": mflops,
        "useful_ratio": mflops / max(flops_dev * n_dev, 1.0),
        "collective_ops": hlo_cost.collective_detail,
        "loops": hlo_cost.loops[:20],
        "xla_cost_analysis": {k: cost.get(k) for k in
                              ("flops", "bytes accessed")} if cost else {},
        "memory_analysis": str(mem)[:400],
    }
