"""Roofline-term derivation from compiled dry-run artifacts.

Terms (per DESIGN.md §8 — cost_analysis on this JAX build reports PER-DEVICE
flops/bytes, verified empirically):

    compute_term    = flops_per_device / peak FLOP/s
    memory_term     = bytes_per_device / HBM bytes/s
    collective_term = link_bytes_per_device / ICI bytes/s per link

with the peaks of the chip the program was compiled for (``PEAKS``).

collective bytes are parsed from the optimized HLO text with ring-model
factors: all-gather / reduce-scatter x(n-1)/n, all-reduce x2(n-1)/n,
all-to-all x(n-1)/n, collective-permute x1, with n = replica-group size.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

import numpy as np



@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Published per-chip peaks that price a roofline."""
    flops: float       # bf16 FLOP/s
    hbm_bw: float      # HBM bytes/s
    ici_bw: float      # interconnect bytes/s per link


# Keyed by ``jax.Device.device_kind``.  TPU v5e: 197 TFLOP/s bf16, 16 GB
# of HBM at 819 GB/s, 1,600 Gbit/s of interconnect over 4 links (Google
# Cloud documentation, "TPU v5e").  A chip that is not here is an error.
PEAKS: Dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(flops=197e12, hbm_bw=819e9, ici_bw=50e9),
}
# The chip a CPU compile (dry runs, tests) is priced as.
MODELED_KIND = "TPU v5 lite"


class UnknownChipError(KeyError):
    """A TPU whose ``device_kind`` has no entry in ``PEAKS``."""


def chip_peaks(device_kind: str) -> ChipPeaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownChipError(
            f"no published peaks for device kind {device_kind!r} "
            f"(known: {sorted(PEAKS)}); add it to launch/roofline.PEAKS "
            "with its source") from None


def priced_kind(device) -> str:
    """The ``PEAKS`` key that prices a program compiled for ``device``:
    a TPU's own kind, or ``MODELED_KIND`` for the CPU backend."""
    return MODELED_KIND if device.platform == "cpu" else device.device_kind

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1, "s4": 1, "u4": 1,
}

# JAX dtype-name spellings of the HLO shorthands above
_DTYPE_ALIASES = {"bfloat16": "bf16", "float16": "f16", "float32": "f32",
                  "float64": "f64", "int8": "s8", "int32": "s32",
                  "float8_e4m3fn": "f8e4m3fn", "float8_e5m2": "f8e5m2"}


def dtype_bytes(dtype: str) -> int:
    """Bytes per element of an HLO or JAX dtype name.

    The single sizing convention for modeled byte surfaces — the HLO
    walker and the traffic engine's ``LayerStack`` lowering
    (``core.traffic``) both size tensors through it.
    """
    key = _DTYPE_ALIASES.get(dtype, dtype)
    if key not in _DTYPE_BYTES:
        raise KeyError(f"unknown dtype {dtype!r}")
    return _DTYPE_BYTES[key]


_COLL_RE = re.compile(
    r"(?P<outshape>[\w\[\],{}\s()]*?)"
    r"\b(?P<op>all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start)?\(")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{([^}]*)\}")
_SRC_TGT_RE = re.compile(r"source_target_pairs=\{")


def _shape_bytes(txt: str) -> float:
    """Sum byte sizes of all 'dtype[a,b,c]' shapes in a fragment."""
    total = 0.0
    for m in _SHAPE_RE.finditer(txt):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclasses.dataclass
class CollectiveStats:
    op_counts: Dict[str, int]
    op_bytes: Dict[str, float]        # ring-model per-device link bytes
    raw_bytes: Dict[str, float]       # payload bytes (no ring factor)

    @property
    def total_bytes(self) -> float:
        return sum(self.op_bytes.values())


def parse_collectives(hlo_text: str) -> CollectiveStats:
    counts: Dict[str, int] = {}
    link_bytes: Dict[str, float] = {}
    raw: Dict[str, float] = {}
    for line in hlo_text.splitlines():
        line = line.strip()
        m = _COLL_RE.search(line)
        if not m or "-done" in line.split("=")[0]:
            continue
        op = m.group("op")
        # replica group size n
        n = 1
        g = _GROUPS_RE.search(line)
        if g:
            n = int(g.group(2))
        else:
            gl = _GROUPS_LIST_RE.search(line)
            if gl:
                first = gl.group(1).split("}")[0].split("{")[-1]
                n = max(1, len([x for x in first.split(",") if x.strip()]))
        # output shape: LHS of '='; for -start ops it's a tuple incl. inputs
        lhs = line.split("=", 1)[0]
        rhs_shapes = line.split("=", 1)[1] if "=" in line else ""
        out_bytes = _shape_bytes(lhs)
        if op == "all-reduce":
            payload = out_bytes
            factor = 2.0 * (n - 1) / max(n, 1)
        elif op == "all-gather":
            # LHS is the gathered (full) shape; ring moves (n-1)/n of it
            payload = out_bytes
            factor = (n - 1) / max(n, 1)
        elif op == "reduce-scatter":
            # LHS is the scattered shard; ring moves (n-1)*shard per device
            payload = out_bytes * n
            factor = (n - 1) / max(n, 1)
        elif op == "all-to-all":
            payload = out_bytes
            factor = (n - 1) / max(n, 1)
        else:  # collective-permute
            payload = out_bytes
            factor = 1.0
            if _SRC_TGT_RE.search(line):
                n = 2  # point-to-point
        counts[op] = counts.get(op, 0) + 1
        link_bytes[op] = link_bytes.get(op, 0.0) + payload * factor
        raw[op] = raw.get(op, 0.0) + payload
    return CollectiveStats(counts, link_bytes, raw)


@dataclasses.dataclass
class Roofline:
    flops_per_device: float
    bytes_per_device: float
    collective_bytes: float
    collectives: Dict[str, float]
    collective_counts: Dict[str, int]
    temp_bytes: float
    arg_bytes: float
    device_kind: str         # PEAKS key of the chip the program targets
    xla_flops: float = 0.0   # raw cost_analysis (while bodies counted once)
    xla_bytes: float = 0.0
    bytes_by_scope: Dict[str, float] = None
    flops_by_scope: Dict[str, float] = None

    @property
    def peaks(self) -> ChipPeaks:
        """Raises ``UnknownChipError`` for a chip missing from PEAKS."""
        return chip_peaks(self.device_kind)

    @property
    def compute_s(self) -> float:
        return self.flops_per_device / self.peaks.flops

    @property
    def memory_s(self) -> float:
        return self.bytes_per_device / self.peaks.hbm_bw

    @property
    def collective_s(self) -> float:
        return self.collective_bytes / self.peaks.ici_bw

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    def model_flops_util(self, model_flops_per_device: float) -> float:
        """MODEL_FLOPS fraction of the roofline bound (MFU-like)."""
        if self.bound_s <= 0:
            return 0.0
        return model_flops_per_device / self.peaks.flops / self.bound_s

    def to_dict(self) -> dict:
        return {
            "flops_per_device": self.flops_per_device,
            "bytes_per_device": self.bytes_per_device,
            "collective_bytes": self.collective_bytes,
            "collectives": self.collectives,
            "collective_counts": self.collective_counts,
            "temp_bytes": self.temp_bytes,
            "arg_bytes": self.arg_bytes,
            "device_kind": self.device_kind,
            "xla_flops": self.xla_flops,
            "xla_bytes": self.xla_bytes,
            "bytes_by_scope": self.bytes_by_scope,
            "flops_by_scope": self.flops_by_scope,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "dominant": self.dominant,
        }


def analyze(compiled) -> Roofline:
    """Derive roofline terms from a compiled executable.

    FLOPs / HBM bytes / collective link bytes come from the while-aware HLO
    walker (repro.launch.hlo_analysis) because XLA's HloCostAnalysis counts
    while bodies once instead of x trip_count. The raw cost_analysis values
    are kept as reference fields.
    """
    from repro.launch.hlo_analysis import analyze_hlo

    import jax

    shardings = jax.tree.leaves(compiled.input_shardings)
    device = (min(shardings[0].device_set, key=lambda d: d.id) if shardings
              else jax.devices()[0])
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    xla_flops = float(ca.get("flops", 0.0))
    xla_bytes = float(ca.get("bytes accessed", 0.0))
    stats = analyze_hlo(compiled.as_text())
    temp = arg = 0.0
    try:
        ma = compiled.memory_analysis()
        temp = float(getattr(ma, "temp_size_in_bytes", 0.0))
        arg = float(getattr(ma, "argument_size_in_bytes", 0.0))
    except Exception:
        pass
    return Roofline(
        flops_per_device=stats.flops,
        bytes_per_device=stats.bytes,
        collective_bytes=stats.collective_link_bytes,
        collectives=stats.collective_bytes_by_op,
        collective_counts=stats.collective_counts,
        temp_bytes=temp,
        arg_bytes=arg,
        device_kind=priced_kind(device),
        xla_flops=xla_flops,
        xla_bytes=xla_bytes,
        bytes_by_scope=stats.bytes_by_scope,
        flops_by_scope=stats.flops_by_scope,
    )


def model_flops(cfg, shape, chips: int) -> float:
    """Per-device MODEL_FLOPS: 6·N·D train, 2·N·tokens serve (N = active)."""
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        total = 6.0 * n_active * shape.seq_len * shape.global_batch
    elif shape.kind == "prefill":
        total = 2.0 * n_active * shape.seq_len * shape.global_batch
    else:  # decode: one token per sequence
        total = 2.0 * n_active * shape.global_batch
    return total / chips
