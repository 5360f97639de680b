"""Three-term roofline from a compiled dry-run artifact.

  compute    = HLO_FLOPs / (chips * peak_FLOP/s)
  memory     = HLO_bytes / (chips * HBM_bw)
  collective = collective_bytes / (chips * ICI_bw)

cost_analysis() reports per-device numbers for SPMD modules; collective bytes
are parsed from the optimized HLO text (all-gather / all-reduce /
reduce-scatter / all-to-all / collective-permute operand sizes).

Peaks are looked up by `jax.Device.device_kind` in `PEAKS`, never assumed:
a device that is not in the table raises.
"""
from __future__ import annotations

import dataclasses
import re


@dataclasses.dataclass(frozen=True)
class Peaks:
    """Published per-chip peaks."""

    flops_bf16: float            # FLOP/s
    hbm_bw: float                # bytes/s
    ici_bw: float                # bytes/s, all chip-to-chip links together
    source: str


# Keyed by `jax.Device.device_kind` (a v5e chip reports "TPU v5 lite").
PEAKS = {
    "TPU v5 lite": Peaks(
        flops_bf16=197e12, hbm_bw=819e9, ici_bw=1600e9 / 8,
        source='Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
               '819 GB/s HBM, 1,600 Gbit/s chip-to-chip interconnect'),
}


def peaks(device_kind: str) -> Peaks:
    """The published peaks of one chip of `device_kind`."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r};"
                       f" known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
}
_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    """'bf16[8,128]{1,0}' -> byte size; tuples handled by caller."""
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dtype, dims = m.groups()
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Sum of *output* shape bytes per collective kind in the optimized HLO.

    Output bytes are the tensor sizes the collectives materialize; for
    all-reduce in/out match, for all-gather the output is the gathered size
    (an upper bound on per-device link traffic; consistent across variants).
    """
    out: dict[str, int] = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        s = line.strip()
        # Match 'x = TYPE[...] all-reduce(...)' & fused variants ('-start').
        m = re.match(r"^[%\w.\-]+\s*=\s*(\(?[\w\[\],{}\s]*\)?)\s*([\w-]+)", s)
        if not m:
            continue
        shape_str, op = m.groups()
        base = next((c for c in _COLLECTIVES if op == c or op == c + "-start"),
                    None)
        if base is None:
            continue
        out[base] += _shape_bytes(shape_str)
    return out


@dataclasses.dataclass
class Roofline:
    flops_per_device: float
    bytes_per_device: float
    coll_bytes_per_device: float
    coll_breakdown: dict[str, int]
    chips: int
    peak: Peaks

    @property
    def t_compute(self) -> float:
        return self.flops_per_device / self.peak.flops_bf16

    @property
    def t_memory(self) -> float:
        return self.bytes_per_device / self.peak.hbm_bw

    @property
    def t_collective(self) -> float:
        return self.coll_bytes_per_device / self.peak.ici_bw

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def step_time(self) -> float:
        """Roofline step time (no overlap assumed = worst case ... the
        overlap-optimistic bound is max(); we report both)."""
        return max(self.t_compute, self.t_memory, self.t_collective)

    def compute_fraction(self, model_flops_per_device: float) -> float:
        """MODEL_FLOPS / (step_time * peak): the roofline fraction score."""
        if self.step_time == 0:
            return 0.0
        return model_flops_per_device / (self.step_time
                                         * self.peak.flops_bf16)

    def as_dict(self) -> dict:
        return {
            "flops_per_device": self.flops_per_device,
            "bytes_per_device": self.bytes_per_device,
            "coll_bytes_per_device": self.coll_bytes_per_device,
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck,
            "coll_breakdown": {k: v for k, v in self.coll_breakdown.items() if v},
        }


def from_compiled(compiled, chips: int, device_kind: str) -> Roofline:
    """Trip-aware terms from the optimized HLO (see hlo_analysis.py: XLA's
    cost_analysis counts scan bodies once, 24-62x off for deep stacks)."""
    from .hlo_analysis import analyze
    cost = analyze(compiled.as_text())
    breakdown = {k: int(v) for k, v in cost.coll_breakdown.items()}
    return Roofline(cost.flops, cost.bytes_accessed, cost.collective_bytes,
                    breakdown, chips, peaks(device_kind))


def from_compiled_xla(compiled, chips: int, device_kind: str) -> Roofline:
    """The raw (trip-blind) XLA numbers - kept for comparison/debugging."""
    ca = compiled.cost_analysis()
    flops = float(ca.get("flops", 0.0))
    byts = float(ca.get("bytes accessed", 0.0))
    breakdown = collective_bytes(compiled.as_text())
    coll = float(sum(breakdown.values()))
    return Roofline(flops, byts, coll, breakdown, chips, peaks(device_kind))
