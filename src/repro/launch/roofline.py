"""Roofline term derivation from compiled dry-run artifacts.

Three terms per (arch x shape x mesh), in seconds, from the peaks of the
chip's ``device_kind`` (:data:`DEVICE_PEAKS`):

    compute    = HLO_FLOPs_per_chip / peak bf16 FLOP/s
    memory     = HLO_bytes_per_chip / HBM bandwidth
    collective = collective_bytes_per_chip / per-link ICI bandwidth

``cost_analysis()`` supplies per-chip FLOPs/bytes (the compiled module is
the per-device SPMD program).  Collective bytes are NOT in cost_analysis —
they are parsed from the compiled HLO text with ring-algorithm per-chip
costs:  all-gather R*(g-1)/g, reduce-scatter R*(g-1), all-reduce
2*R*(g-1)/g, all-to-all R*(g-1)/g, collective-permute R   (R = result
bytes, g = replica-group size).

MODEL_FLOPS uses 6*N_active*tokens (train) / 2*N_active*tokens (inference)
plus the exact attention term; the ratio MODEL_FLOPS / (HLO_FLOPs * chips)
exposes remat/causal-overcount waste.
"""
from __future__ import annotations

import dataclasses
import re

from repro.configs.shapes import ShapeSpec
from repro.models.config import ModelConfig



@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    flops: float        # bf16 FLOP/s per chip
    hbm_bw: float       # HBM bytes/s per chip
    ici_bw: float       # interconnect bytes/s per link


#: Published per-chip peaks keyed by ``jax.Device.device_kind``.
#: TPU v5e: Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16,
#: 819 GB/s HBM, 1,600 Gbit/s interconnect over 4 links (50 GB/s each).
DEVICE_PEAKS = {
    "TPU v5 lite": DevicePeaks(flops=197e12, hbm_bw=819e9, ici_bw=50e9),
}


def device_peaks(device_kind: str) -> DevicePeaks:
    """Peaks of ``device_kind``; an unknown kind is an error."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(DEVICE_PEAKS)}"
                         ) from None


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"\b(" + "|".join(_DTYPE_BYTES) + r")\[([0-9,]*)\]")
_GROUPS_EXPL_RE = re.compile(r"replica_groups=\{\{([0-9,\s]*)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[([0-9,]+)\]<=\[")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def _group_size(line: str, default: int) -> int:
    m = _GROUPS_EXPL_RE.search(line)
    if m:
        body = m.group(1).strip()
        return len(body.split(",")) if body else 1
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        dims = [int(x) for x in m.group(1).split(",")]
        return dims[-1]
    return default


def _result_bytes(line: str, op: str) -> int:
    """Sum of result-type shape bytes (everything left of the op token)."""
    head = line.split(f" {op}(")[0]
    if "=" in head:
        head = head.split("=", 1)[1]
    return sum(_shape_bytes(d, s) for d, s in _SHAPE_RE.findall(head))


@dataclasses.dataclass
class CollectiveStats:
    counts: dict
    bytes_per_chip: float
    total_result_bytes: float

    def asdict(self):
        return {"counts": self.counts, "bytes_per_chip": self.bytes_per_chip,
                "total_result_bytes": self.total_result_bytes}


def parse_collectives(hlo_text: str, n_devices: int) -> CollectiveStats:
    counts: dict[str, int] = {}
    per_chip = 0.0
    total = 0.0
    for line in hlo_text.splitlines():
        for op in _COLLECTIVES:
            token = f" {op}("
            start_token = f" {op}-start("
            if start_token in line:
                use = op + "-start"
            elif token in line:
                use = op
            else:
                continue
            if f"{op}-done" in line:
                continue
            r = _result_bytes(line, use)
            g = _group_size(line, n_devices)
            if op == "all-gather":
                cost = r * (g - 1) / max(g, 1)
            elif op == "reduce-scatter":
                cost = r * (g - 1)
            elif op == "all-reduce":
                cost = 2 * r * (g - 1) / max(g, 1)
            elif op == "all-to-all":
                cost = r * (g - 1) / max(g, 1)
            else:                      # collective-permute
                cost = r
            counts[op] = counts.get(op, 0) + 1
            per_chip += cost
            total += r
            break
    return CollectiveStats(counts=counts, bytes_per_chip=per_chip,
                           total_result_bytes=total)


# --------------------------------------------------------------------- #
# MODEL_FLOPS (the "useful work" yardstick)
# --------------------------------------------------------------------- #


def model_flops(cfg: ModelConfig, shape: ShapeSpec) -> float:
    """6*N_active*D (train) / 2*N_active*D (inference) + exact attention."""
    counts = cfg.param_counts()
    n_active = counts["active"]
    n_attn_layers = sum(
        1 for i in range(cfg.n_layers)
        if cfg.pattern[i % len(cfg.pattern)].mixer == "attn"
    )
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        base = 6.0 * n_active * tokens
        # causal attention: fwd 2*2*S^2/2*d_attn per layer, x3 with backward
        attn = (3 * 2 * 2 * 0.5 * shape.seq_len ** 2 * cfg.q_dim
                * n_attn_layers * shape.global_batch)
        return base + attn
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        base = 2.0 * n_active * tokens
        attn = (2 * 2 * 0.5 * shape.seq_len ** 2 * cfg.q_dim
                * n_attn_layers * shape.global_batch)
        return base + attn
    # decode: one token per sequence, attention reads the whole cache
    tokens = shape.global_batch
    base = 2.0 * n_active * tokens
    attn = (2 * 2 * shape.seq_len * cfg.q_dim * n_attn_layers * tokens)
    return base + attn


@dataclasses.dataclass
class Roofline:
    compute_s: float
    memory_s: float
    collective_s: float
    flops_per_chip: float
    bytes_per_chip: float
    coll_bytes_per_chip: float
    n_devices: int
    model_flops_total: float
    peak_flops: float

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def bound_time_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / (HLO_FLOPs * chips) — remat/redundancy waste."""
        hlo_total = self.flops_per_chip * self.n_devices
        return self.model_flops_total / hlo_total if hlo_total else 0.0

    @property
    def roofline_fraction(self) -> float:
        """Useful-compute time / bottleneck time — the score.

        1.0 means the step time is fully explained by MODEL_FLOPS at peak
        MXU throughput; less means the dominant term (or wasted FLOPs) is
        costing wall-clock."""
        ideal = self.model_flops_total / (self.n_devices * self.peak_flops)
        return ideal / self.bound_time_s if self.bound_time_s else 0.0

    def asdict(self):
        return {
            "compute_s": self.compute_s, "memory_s": self.memory_s,
            "collective_s": self.collective_s, "dominant": self.dominant,
            "flops_per_chip": self.flops_per_chip,
            "bytes_per_chip": self.bytes_per_chip,
            "coll_bytes_per_chip": self.coll_bytes_per_chip,
            "n_devices": self.n_devices,
            "model_flops_total": self.model_flops_total,
            "useful_flops_ratio": self.useful_flops_ratio,
            "roofline_fraction": self.roofline_fraction,
        }


def derive(cost: dict, coll: CollectiveStats, n_devices: int,
           model_flops_total: float, device_kind: str) -> Roofline:
    peaks = device_peaks(device_kind)
    flops = float(cost.get("flops", 0.0))
    byts = float(cost.get("bytes accessed", 0.0))
    return Roofline(
        compute_s=flops / peaks.flops,
        memory_s=byts / peaks.hbm_bw,
        collective_s=coll.bytes_per_chip / peaks.ici_bw,
        flops_per_chip=flops,
        bytes_per_chip=byts,
        coll_bytes_per_chip=coll.bytes_per_chip,
        n_devices=n_devices,
        model_flops_total=model_flops_total,
        peak_flops=peaks.flops,
    )


def derive_from_hlo_cost(hlo_cost, n_devices: int, model_flops_total: float,
                         device_kind: str) -> Roofline:
    """Roofline terms from the loop-aware HLO walker (the accurate path —
    raw cost_analysis counts while-loop bodies once; see hlo_analysis.py)."""
    peaks = device_peaks(device_kind)
    return Roofline(
        compute_s=hlo_cost.flops / peaks.flops,
        memory_s=hlo_cost.bytes_accessed / peaks.hbm_bw,
        collective_s=hlo_cost.collective_bytes / peaks.ici_bw,
        flops_per_chip=hlo_cost.flops,
        bytes_per_chip=hlo_cost.bytes_accessed,
        coll_bytes_per_chip=hlo_cost.collective_bytes,
        n_devices=n_devices,
        model_flops_total=model_flops_total,
        peak_flops=peaks.flops,
    )
