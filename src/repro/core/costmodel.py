"""Analytic execution-time source: roofline terms from compiled dry-runs.

On a TPU-less container the profiler cannot wall-clock at-scale workloads, so
this module turns a compiled XLA artifact into the three roofline terms the
grading methodology specifies (all per-device, post-SPMD — ``cost_analysis``
reports the per-device program after partitioning):

    compute    = HLO_flops / peak_flops            (s)
    memory     = HLO_bytes / hbm_bandwidth         (s)
    collective = collective_bytes / ici_bandwidth  (s)

``collective_bytes`` is not in cost_analysis; we parse the compiled HLO text
and sum the *output* operand sizes of every collective op (all-gather,
all-reduce, reduce-scatter, all-to-all, collective-permute).  The estimated
step time is max(compute, memory) + collective when overlap is off, and
max(compute, memory, collective) under perfect overlap — both are reported.

This is also the ``AnalyticTimer`` backend for the paper's profiling phase at
scale: time(config) := estimated step time of the config's compiled artifact.
"""

from __future__ import annotations

import dataclasses
import math
import re


# TPU v5e hardware constants (per brief).
PEAK_FLOPS_BF16 = 197e12       # FLOP/s per chip
HBM_BW = 819e9                 # bytes/s per chip
ICI_BW = 50e9                  # bytes/s per link (approx, per brief)
HBM_BYTES = 16 * 1024**3       # 16 GiB per chip

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1,
    "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1, "f8e3m4": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

# e.g. "bf16[256,4096,1024]{2,1,0}" or "f32[]" — capture dtype + dims.
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLLECTIVE_KINDS = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)
# Matches `  %x = TYPE all-gather(...)` / `ROOT %y = (..) all-reduce-start(`
_COLLECTIVE_LINE_RE = re.compile(
    r"=\s+(?P<shape>\([^)]*\)|\S+)\s+"
    r"(?P<kind>all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?P<start>-start)?\("
)


def _shape_bytes(shape_str: str) -> int:
    """Bytes of one HLO shape string (tuple shapes -> sum of elements)."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue  # e.g. token[] / opaque
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


@dataclasses.dataclass
class CollectiveStats:
    """Per-kind byte totals parsed from compiled (post-SPMD) HLO."""

    bytes_by_kind: dict[str, int]
    count_by_kind: dict[str, int]

    @property
    def total_bytes(self) -> int:
        return sum(self.bytes_by_kind.values())

    @property
    def total_count(self) -> int:
        return sum(self.count_by_kind.values())


def parse_collectives(hlo_text: str) -> CollectiveStats:
    """Sum output-operand bytes of every collective in compiled HLO text.

    ``-start`` variants are counted once (their paired ``-done`` line has no
    own shape production matched by the regex since it's `<kind>-done(` which
    doesn't match our kind group followed by `(` — it does! guard explicitly).
    """
    bytes_by_kind: dict[str, int] = {k: 0 for k in _COLLECTIVE_KINDS}
    count_by_kind: dict[str, int] = {k: 0 for k in _COLLECTIVE_KINDS}
    for line in hlo_text.splitlines():
        if "-done(" in line:
            continue  # async completion: already counted at -start
        m = _COLLECTIVE_LINE_RE.search(line)
        if not m:
            continue
        kind = m.group("kind")
        nbytes = _shape_bytes(m.group("shape"))
        bytes_by_kind[kind] += nbytes
        count_by_kind[kind] += 1
    return CollectiveStats(bytes_by_kind=bytes_by_kind, count_by_kind=count_by_kind)


@dataclasses.dataclass
class RooflineReport:
    """The §Roofline record for one (arch, shape, mesh) cell."""

    flops: float                  # per-device HLO flops
    hbm_bytes: float              # per-device HLO bytes accessed
    collective_bytes: float       # per-device collective bytes (HLO output sums)
    compute_s: float
    memory_s: float
    collective_s: float
    peak_hbm_bytes: float         # memory_analysis peak (args+temp) per device
    dominant: str
    # Usefulness accounting
    model_flops: float | None = None   # 6*N*D (train) / 2*N*D-style (serve), GLOBAL
    useful_ratio: float | None = None  # model_flops / (flops * n_devices)
    collectives: CollectiveStats | None = None

    @property
    def step_time_no_overlap(self) -> float:
        return max(self.compute_s, self.memory_s) + self.collective_s

    @property
    def step_time_overlap(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def roofline_fraction(self) -> float:
        """useful model FLOP/s achieved ÷ peak, at the no-overlap step time.

        This is the score-bearing number: it charges every inefficiency
        (redundant compute, memory stalls, exposed collectives) against the
        machine's peak.
        """
        if not self.model_flops:
            return float("nan")
        return self.flops_fraction_of_peak

    @property
    def flops_fraction_of_peak(self) -> float:
        if not self.model_flops or self.n_devices is None:
            return float("nan")
        per_dev_useful = self.model_flops / self.n_devices
        t = self.step_time_no_overlap
        return (per_dev_useful / t) / PEAK_FLOPS_BF16 if t > 0 else float("nan")

    n_devices: int | None = None

    def to_dict(self) -> dict:
        d = {
            "flops": self.flops,
            "hbm_bytes": self.hbm_bytes,
            "collective_bytes": self.collective_bytes,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "peak_hbm_bytes": self.peak_hbm_bytes,
            "dominant": self.dominant,
            "model_flops": self.model_flops,
            "useful_ratio": self.useful_ratio,
            "n_devices": self.n_devices,
            "step_time_no_overlap": self.step_time_no_overlap,
            "step_time_overlap": self.step_time_overlap,
            "roofline_fraction": self.flops_fraction_of_peak,
        }
        if self.collectives is not None:
            d["collective_bytes_by_kind"] = self.collectives.bytes_by_kind
            d["collective_count_by_kind"] = self.collectives.count_by_kind
        return d


def roofline_from_compiled(
    compiled,
    *,
    n_devices: int,
    model_flops: float | None = None,
    hlo_text: str | None = None,
) -> RooflineReport:
    """Derive the three roofline terms from a jax Compiled object."""
    cost = compiled.cost_analysis()
    # cost_analysis is per-device for SPMD-partitioned modules.
    flops = float(cost.get("flops", 0.0))
    hbm_bytes = float(cost.get("bytes accessed", 0.0))
    text = hlo_text if hlo_text is not None else compiled.as_text()
    coll = parse_collectives(text)
    collective_bytes = float(coll.total_bytes)
    compute_s = flops / PEAK_FLOPS_BF16
    memory_s = hbm_bytes / HBM_BW
    collective_s = collective_bytes / ICI_BW
    terms = {
        "compute": compute_s,
        "memory": memory_s,
        "collective": collective_s,
    }
    dominant = max(terms, key=terms.get)
    mem = compiled.memory_analysis()
    peak = float(
        getattr(mem, "argument_size_in_bytes", 0)
        + getattr(mem, "temp_size_in_bytes", 0)
        + getattr(mem, "output_size_in_bytes", 0)
        - getattr(mem, "alias_size_in_bytes", 0)
    )
    useful = None
    if model_flops is not None and flops > 0:
        useful = model_flops / (flops * n_devices)
    return RooflineReport(
        flops=flops,
        hbm_bytes=hbm_bytes,
        collective_bytes=collective_bytes,
        compute_s=compute_s,
        memory_s=memory_s,
        collective_s=collective_s,
        peak_hbm_bytes=peak,
        dominant=dominant,
        model_flops=model_flops,
        useful_ratio=useful,
        collectives=coll,
        n_devices=n_devices,
    )


def format_seconds(s: float) -> str:
    if s == 0 or math.isnan(s):
        return f"{s:.3g}s"
    if s < 1e-3:
        return f"{s * 1e6:.1f}us"
    if s < 1:
        return f"{s * 1e3:.2f}ms"
    return f"{s:.3f}s"
