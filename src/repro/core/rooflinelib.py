"""Roofline analysis from compiled HLO — the dry-run "profiler".

This container has no TPU, so the profile is structural (per the brief):

  compute term    = HLO_FLOPs   / (chips × peak_FLOP/s)
  memory term     = HLO_bytes   / (chips × HBM_bw)
  collective term = coll_bytes  / (chips × link_bw)

FLOPs/bytes come from ``compiled.cost_analysis()``; collective bytes are
parsed out of the HLO text (cost_analysis does not attribute them).

The machine-balance framing mirrors the paper's Sec. 2.1/Table 1: TPU
v5e-class constants give balance = 197e12 / 819e9 ≈ 240 bf16 FLOP per
byte — stencil kernels sit far below it (memory-bound), dense matmul
training sits near or above it (compute-bound), which is exactly the
regime split the paper studies on GPUs.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any

# --- hardware constants (brief-specified, TPU v5e class) -------------------


@dataclasses.dataclass(frozen=True)
class HardwareSpec:
    name: str
    peak_flops_bf16: float  # FLOP/s per chip
    peak_flops_f32: float
    hbm_bw: float  # bytes/s per chip
    ici_bw: float  # bytes/s per link (per direction)
    vmem_bytes: int
    hbm_bytes: int
    tdp_watts: float

    def machine_balance(self, dtype_bytes: int = 2) -> float:
        peak = self.peak_flops_bf16 if dtype_bytes == 2 else self.peak_flops_f32
        return peak / self.hbm_bw


TPU_V5E = HardwareSpec(
    name="tpu-v5e",
    peak_flops_bf16=197e12,
    peak_flops_f32=98.5e12,  # half-rate fp32 on the MXU
    hbm_bw=819e9,
    ici_bw=50e9,
    vmem_bytes=128 * 1024 * 1024,
    hbm_bytes=16 * 1024 * 1024 * 1024,
    tdp_watts=200.0,
)

# --- HLO collective parsing -------------------------------------------------

_COLLECTIVES = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{(\{[^}]*\}(?:,\{[^}]*\})*)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(type_str: str) -> int:
    """Total bytes of a (possibly tuple) HLO type string."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def _group_size(line: str) -> int | None:
    m = _GROUPS_IOTA_RE.search(line)
    if m:  # replica_groups=[G,S] iota form: G groups of size S
        return int(m.group(2))
    m = _GROUPS_RE.search(line)
    if m:
        first = m.group(1).split("},")[0].strip("{}")
        if first:
            return len(first.split(","))
    return None


@dataclasses.dataclass
class CollectiveStats:
    """Per-collective byte totals parsed from an HLO module."""

    result_bytes: dict[str, int]
    wire_bytes: dict[str, int]  # ring-model per-chip wire traffic
    counts: dict[str, int]

    @property
    def total_result_bytes(self) -> int:
        return sum(self.result_bytes.values())

    @property
    def total_wire_bytes(self) -> int:
        return sum(self.wire_bytes.values())


def parse_collectives(hlo_text: str) -> CollectiveStats:
    """Sum collective operand/result sizes from HLO text.

    Wire model (per participating chip, bidirectional ring):
      all-gather:        out × (g-1)/g        (out = gathered result)
      reduce-scatter:    in  × (g-1)/g  =  out × (g-1)
      all-reduce:        2 × size × (g-1)/g
      all-to-all:        size × (g-1)/g
      collective-permute: size
    """
    result_bytes: dict[str, int] = {c: 0 for c in _COLLECTIVES}
    wire_bytes: dict[str, int] = {c: 0 for c in _COLLECTIVES}
    counts: dict[str, int] = {c: 0 for c in _COLLECTIVES}
    for line in hlo_text.splitlines():
        line = line.strip()
        if " = " not in line:
            continue
        lhs, rhs = line.split(" = ", 1)
        del lhs
        m = re.match(r"((?:\([^)]*\)|\S+))\s+([\w-]+)", rhs)
        if not m:
            continue
        type_str, opname = m.group(1), m.group(2)
        op = None
        for c in _COLLECTIVES:
            if opname == c or opname == c + "-start" or opname.startswith(c + "."):
                op = c
                break
        if op is None:
            continue
        nbytes = _shape_bytes(type_str)
        g = _group_size(line) or 1
        counts[op] += 1
        result_bytes[op] += nbytes
        if op == "all-gather":
            wire_bytes[op] += int(nbytes * (g - 1) / max(g, 1))
        elif op == "reduce-scatter":
            wire_bytes[op] += int(nbytes * (g - 1))
        elif op == "all-reduce":
            wire_bytes[op] += int(2 * nbytes * (g - 1) / max(g, 1))
        elif op == "all-to-all":
            wire_bytes[op] += int(nbytes * (g - 1) / max(g, 1))
        else:  # collective-permute
            wire_bytes[op] += nbytes
    return CollectiveStats(result_bytes, wire_bytes, counts)


# --- roofline terms ----------------------------------------------------------


@dataclasses.dataclass
class Roofline:
    flops: float  # per-device HLO FLOPs (SPMD program)
    hbm_bytes: float  # per-device HLO bytes accessed
    collective_result_bytes: float
    collective_wire_bytes: float
    chips: int
    hw: HardwareSpec
    dtype_bytes: int = 2

    @property
    def compute_s(self) -> float:
        peak = (
            self.hw.peak_flops_bf16
            if self.dtype_bytes == 2
            else self.hw.peak_flops_f32
        )
        return self.flops / peak

    @property
    def memory_s(self) -> float:
        return self.hbm_bytes / self.hw.hbm_bw

    @property
    def collective_s(self) -> float:
        # Brief formula: collective_bytes / (chips × link_bw), evaluated
        # with the per-chip wire model (each chip drives its own links;
        # per-chip wire bytes over per-link bandwidth).
        return self.collective_wire_bytes / self.hw.ici_bw

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }
        return max(terms, key=terms.get)

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    def useful_flops_fraction(self, model_flops_per_chip: float) -> float:
        """MODEL_FLOPS / HLO_FLOPs — catches remat/redundancy waste."""
        return model_flops_per_chip / max(self.flops, 1.0)

    def roofline_fraction(self, model_flops_per_chip: float) -> float:
        """Useful-FLOP throughput vs peak if the step ran at its bound:
        (model FLOPs / bound-time) / peak — the MFU-style score."""
        peak = (
            self.hw.peak_flops_bf16
            if self.dtype_bytes == 2
            else self.hw.peak_flops_f32
        )
        return model_flops_per_chip / (self.bound_s * peak)

    def summary(self) -> dict[str, Any]:
        return {
            "flops": self.flops,
            "hbm_bytes": self.hbm_bytes,
            "coll_result_bytes": self.collective_result_bytes,
            "coll_wire_bytes": self.collective_wire_bytes,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "dominant": self.dominant,
        }


def from_compiled(
    compiled,
    hlo_text: str,
    *,
    chips: int,
    hw: HardwareSpec = TPU_V5E,
    dtype_bytes: int = 2,
) -> Roofline:
    """Build roofline terms from a compiled executable + its HLO text."""
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    flops = float(ca.get("flops", 0.0))
    nbytes = float(ca.get("bytes accessed", 0.0))
    coll = parse_collectives(hlo_text)
    return Roofline(
        flops=flops,
        hbm_bytes=nbytes,
        collective_result_bytes=float(coll.total_result_bytes),
        collective_wire_bytes=float(coll.total_wire_bytes),
        chips=chips,
        hw=hw,
        dtype_bytes=dtype_bytes,
    )


def stencil_ideal_bytes(
    n_points: float, n_f: int, n_out: int, dtype_bytes: int
) -> float:
    """The paper's 'ideal performance' bound (Sec. 5.4): the domain is
    read and written exactly once at peak bandwidth."""
    return n_points * (n_f + n_out) * dtype_bytes


def stencil_mxu_roof_s(
    flops: float, dtype_bytes: int = 4, hw: HardwareSpec = TPU_V5E
) -> float:
    """Compute roof next to the bandwidth roof: seconds the strategy
    ``"tc"`` matmul lowering needs at peak MXU rate for ``flops``
    contraction FLOPs (``stencil_mxu_flops_per_step``). bf16 inputs run
    the MXU at double the f32-accumulate rate, mirroring
    ``trafficmodel.peak_mxu_flops``."""
    peak = hw.peak_flops_bf16 if dtype_bytes == 2 else hw.peak_flops_f32
    return flops / peak
