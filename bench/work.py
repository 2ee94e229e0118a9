"""The yardstick's work count: operations and bytes of a served decision.

The count is the paper's dense crossbar read, whatever implements it:
every cell of every 32-cell column is sensed, so a decision on one
replica costs two multiply-adds per cell (the on-path current and the
leak current, ``4 * L * C`` operations) plus the polarity tail
(``2 * C * M``).  An ensemble reads every replica.  A dispatch moves the
resident model (the include bitplane, ``L * C / 8`` bytes, plus one f32
deviation plane per replica when the pool carries device-to-device
variation), the request literals as bits and the outputs (``M`` class
sums and one prediction per row, int32).

A change that skips columns or cells does less work than this count;
such a change needs a revised count in the benchmark before it can be
measured against it.

``PEAKS`` holds the published peaks per ``device_kind``; an unknown kind
is an error, never a default.
"""

from __future__ import annotations

import dataclasses

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
    # 393 TOP/s int8, 16 GB HBM at 819 GB/s.
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peak(device_kind: str) -> dict:
    """The peak table entry of ``device_kind``; KeyError if unknown."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to bench/work.py")
    return PEAKS[device_kind]


@dataclasses.dataclass(frozen=True)
class Shape:
    """The sizes the count needs, taken from a configuration file."""

    classes: int
    clauses_per_class: int
    features: int
    replicas_read: int          # chips read per decision (R for ensemble)
    deviation_planes: int       # f32 [C, L] planes resident (0 at nominal)

    @property
    def literals(self) -> int:
        return 2 * self.features

    @property
    def clauses(self) -> int:
        return self.classes * self.clauses_per_class

    @classmethod
    def of(cls, config: dict) -> "Shape":
        model, pool = config["model"], config["pool"]
        ensemble = config["engine"]["routing"] == "ensemble"
        r = pool["replicas"]
        return cls(classes=model["classes"],
                   clauses_per_class=model["clauses_per_class"],
                   features=model["features"],
                   replicas_read=r if ensemble else 1,
                   deviation_planes=r if pool["variation"]["d2d"] else 0)


def ops_per_decision(s: Shape) -> int:
    """Operations of one decision: ``R_read * (4 L C + 2 C M)``."""
    return s.replicas_read * (4 * s.literals * s.clauses
                              + 2 * s.clauses * s.classes)


def resident_bytes(s: Shape) -> int:
    """Model bytes one dispatch reads: the include bitplane plus the
    deviation planes the pool holds."""
    return (s.literals * s.clauses // 8
            + s.deviation_planes * s.literals * s.clauses * 4)


def dispatch_bytes(s: Shape, rows: int) -> int:
    """Bytes of a dispatch of ``rows`` requests: resident model, literal
    bits in, class sums and predictions out."""
    return (resident_bytes(s) + rows * s.literals // 8
            + rows * (s.classes + 1) * 4)


def least_time_s(s: Shape, rows: int, pk: dict, chips: int = 1) -> float:
    """The least time the chips could take for one dispatch: the larger
    of its operations over peak FLOP/s and its bytes over peak B/s."""
    return max(rows * ops_per_decision(s) / (chips * pk["flops_per_s"]),
               dispatch_bytes(s, rows) / (chips * pk["bytes_per_s"]))
