"""Roofline-term extraction from compiled dry-run artifacts (deliverable g).

    compute term    = HLO_FLOPs / (chips x peak_FLOP/s)
    memory term     = HLO_bytes / (chips x HBM_bw)
    collective term = collective_bytes / (chips x link_bw)

Sources: ``compiled.cost_analysis()`` for FLOPs/bytes; ``compiled.as_text()``
parsed for all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute operand bytes (collective bytes are NOT in
cost_analysis).  Hardware peaks come from :data:`PEAKS`, keyed by
``jax.Device.device_kind``.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, Optional, Tuple

__all__ = ["Peaks", "PEAKS", "peaks_for", "RooflineTerms",
           "analyze_compiled", "collective_bytes"]


@dataclass(frozen=True)
class Peaks:
    """Published per-chip peaks of one accelerator kind."""

    flops_bf16: float             # FLOP/s
    hbm_bw: float                 # bytes/s
    ici_link_bw: float            # bytes/s per chip-to-chip link


#: per-chip peaks keyed by ``device_kind``.  TPU v5e: Google Cloud
#: documentation, "TPU v5e" — 197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s
#: of chip-to-chip interconnect over 4 links (50 GB/s each).
PEAKS: Dict[str, Peaks] = {
    "TPU v5 lite": Peaks(flops_bf16=197e12, hbm_bw=819e9, ici_link_bw=50e9),
}


def peaks_for(device_kind: str) -> Peaks:
    """The peaks of ``device_kind``; a kind not in :data:`PEAKS` is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; known "
            f"kinds: {sorted(PEAKS)}") from None


_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "f16": 2, "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
    "f64": 8, "c64": 8, "c128": 16, "token": 0,
}

# one HLO value definition: %name = type[dims]{layout} opcode(...)
_DEF_RE = re.compile(
    r"%?([\w\.\-]+)\s*=\s*\(?\s*([a-z0-9]+)\[([\d,]*)\]")
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")
_COLL_RE = re.compile(
    r"=\s*(.+?)\s+(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start|-done)?\(([^)]*)\)")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims.strip():
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum operand bytes per collective kind from optimized HLO text."""
    # table of every defined value's shape
    shapes: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _DEF_RE.search(line)
        if m:
            name, dt, dims = m.groups()
            shapes[name] = _shape_bytes(dt, dims)

    out = {k: 0 for k in _COLLECTIVES}
    out["count"] = 0
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        result_types, kind, operands = m.groups()
        if "-done" in line.split("=")[1][:60]:
            continue  # avoid double counting async pairs
        # operand bytes: resolve %names; fall back to inline shapes
        total = 0
        names = re.findall(r"%?([\w\.\-]+)", operands)
        for nm in names:
            if nm in shapes:
                total += shapes[nm]
        if total == 0:
            for dt, dims in _SHAPE_RE.findall(result_types):
                total += _shape_bytes(dt, dims)
        out[kind] += total
        out["count"] += 1
    return out


@dataclass
class RooflineTerms:
    arch: str
    cell: str
    mesh: str
    chips: int
    hlo_flops: float
    hlo_bytes: float
    coll_bytes: float
    coll_breakdown: Dict[str, int]
    model_flops: float
    peaks: Peaks
    bytes_per_device: float = 0.0
    peak_memory_per_device: float = 0.0

    @property
    def t_compute(self) -> float:
        return self.hlo_flops / (self.chips * self.peaks.flops_bf16)

    @property
    def t_memory(self) -> float:
        return self.hlo_bytes / (self.chips * self.peaks.hbm_bw)

    @property
    def t_collective(self) -> float:
        return self.coll_bytes / (self.chips * self.peaks.ici_link_bw)

    @property
    def dominant(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def useful_flops_ratio(self) -> float:
        return self.model_flops / self.hlo_flops if self.hlo_flops else 0.0

    @property
    def roofline_fraction(self) -> float:
        """Fraction of the binding roofline the useful work achieves:
        t_model_compute / max(all terms) — 1.0 means the dominant term is
        exactly the useful compute."""
        t_model = self.model_flops / (self.chips * self.peaks.flops_bf16)
        bound = max(self.t_compute, self.t_memory, self.t_collective)
        return t_model / bound if bound else 0.0

    def as_dict(self) -> Dict:
        return {
            "arch": self.arch, "cell": self.cell, "mesh": self.mesh,
            "chips": self.chips,
            "hlo_flops": self.hlo_flops, "hlo_bytes": self.hlo_bytes,
            "coll_bytes": self.coll_bytes,
            "coll_breakdown": self.coll_breakdown,
            "model_flops": self.model_flops,
            "t_compute_s": self.t_compute, "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "dominant": self.dominant,
            "useful_flops_ratio": self.useful_flops_ratio,
            "roofline_fraction": self.roofline_fraction,
            "bytes_per_device": self.bytes_per_device,
            "peak_memory_per_device": self.peak_memory_per_device,
        }


def analyze_compiled(compiled, *, arch: str, cell: str, mesh_name: str,
                     chips: int, model_flops: float,
                     device_kind: str) -> RooflineTerms:
    """Roofline terms from the compiled artifact, against the peaks of
    ``device_kind`` (the chip the program is compiled for).

    Primary source: our trip-count-aware HLO walk (hlo_cost.py) — XLA's
    cost_analysis counts while bodies once, which under-reports scanned
    models by ~n_layers x.  The per-device totals are scaled to global by
    the chip count so the spec formulas (X / (chips·peak)) apply.
    """
    try:
        hlo = compiled.as_text()
    except Exception:
        hlo = ""
    from .hlo_cost import analyze_hlo_text
    per_dev = analyze_hlo_text(hlo) if hlo else None
    xla_cost = compiled.cost_analysis()
    if isinstance(xla_cost, list):
        xla_cost = xla_cost[0] if xla_cost else {}
    if per_dev is not None and per_dev.flops > 0:
        flops = per_dev.flops * chips
        bts = per_dev.bytes * chips
        coll = {k: v * chips for k, v in per_dev.coll.items()}
        coll["count"] = per_dev.coll_count
        total_coll = float(per_dev.coll_bytes * chips)
    else:  # fallback: XLA's own (loop-undercounting) analysis
        flops = float(xla_cost.get("flops", 0.0))
        bts = float(xla_cost.get("bytes accessed", 0.0))
        coll = collective_bytes(hlo)
        total_coll = float(sum(v for k, v in coll.items() if k != "count"))
    mem = {}
    try:
        ma = compiled.memory_analysis()
        mem = {
            "argument_size": getattr(ma, "argument_size_in_bytes", 0),
            "output_size": getattr(ma, "output_size_in_bytes", 0),
            "temp_size": getattr(ma, "temp_size_in_bytes", 0),
        }
    except Exception:
        pass
    per_dev = (mem.get("argument_size", 0) + mem.get("temp_size", 0))
    return RooflineTerms(
        arch=arch, cell=cell, mesh=mesh_name, chips=chips,
        hlo_flops=flops, hlo_bytes=bts, coll_bytes=total_coll,
        coll_breakdown=coll, model_flops=model_flops,
        peaks=peaks_for(device_kind),
        bytes_per_device=per_dev,
        peak_memory_per_device=per_dev,
    )
