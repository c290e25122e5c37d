"""Roofline-term extraction from compiled dry-run artifacts.

Sources (per the assignment):
  * ``compiled.cost_analysis()`` → HLO FLOPs and bytes accessed. For an
    SPMD-partitioned executable these are **per-device** numbers.
  * ``compiled.as_text()`` → the partitioned HLO; we parse every
    all-gather / all-reduce / reduce-scatter / all-to-all /
    collective-permute and sum operand sizes.

Hardware model: per-chip peaks from :data:`PEAKS`, keyed by the
``device_kind`` JAX reports. A device missing from the table is an error,
never a default.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, List, Optional, Tuple

import numpy as np


@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    """Published per-chip peaks of one accelerator generation."""
    flops_bf16: float        # FLOP/s
    hbm_bw: float            # bytes/s
    ici_bw: float            # bytes/s per link
    hbm_bytes: float
    source: str


#: Keyed by ``jax.devices()[0].device_kind``.
PEAKS: Dict[str, DevicePeaks] = {
    "TPU v5 lite": DevicePeaks(
        flops_bf16=197e12, hbm_bw=819e9, ici_bw=50e9, hbm_bytes=16e9,
        source="Google Cloud TPU documentation, 'TPU v5e': 197 TFLOP/s "
               "bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI per chip "
               "(4 links, ~50 GB/s each)"),
}

#: The chip this repo's projections (dry runs, analytic benches) target.
PROJECTION_DEVICE = "TPU v5 lite"


def device_peaks(device_kind: str) -> DevicePeaks:
    """The peak table's row for ``device_kind``; raises for a device the
    table does not hold."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

_DTYPE_BYTES = {
    "f64": 8, "s64": 8, "u64": 8, "c64": 8,
    "f32": 4, "s32": 4, "u32": 4,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
    "f8e4m3fn": 1, "f8e5m2": 1, "s8": 1, "u8": 1, "pred": 1,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(m: re.Match) -> int:
    dtype, dims = m.group(1), m.group(2)
    bs = _DTYPE_BYTES.get(dtype)
    if bs is None:
        return 0
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * bs


def parse_collectives(hlo_text: str) -> Dict[str, Dict[str, float]]:
    """Per collective kind: op count, total operand bytes, total result bytes,
    and modeled wire bytes per device (ring algorithms)."""
    out = {k: {"count": 0, "operand_bytes": 0.0, "result_bytes": 0.0,
               "wire_bytes": 0.0} for k in COLLECTIVES}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        kind = None
        for k in COLLECTIVES:
            token = f" {k}(" if f" {k}(" in stripped else (
                f" {k}-start(" if f" {k}-start(" in stripped else None)
            if token is not None:
                kind = k
                break
        if kind is None:
            continue
        # result shape(s): everything before ` = ` is the name; after it the
        # result shape, then `op(<operands>)`.
        try:
            lhs, rhs = stripped.split(" = ", 1)
        except ValueError:
            continue
        op_idx = rhs.find(kind)
        result_part = rhs[:op_idx]
        operand_part = rhs[op_idx:]
        res_bytes = sum(_shape_bytes(m) for m in _SHAPE_RE.finditer(result_part))
        opd_bytes = sum(_shape_bytes(m) for m in
                        _SHAPE_RE.finditer(operand_part.split("),", 1)[0]))
        if opd_bytes == 0:
            opd_bytes = res_bytes
        rec = out[kind]
        rec["count"] += 1
        rec["operand_bytes"] += opd_bytes
        rec["result_bytes"] += res_bytes
        # modeled bytes-on-wire per device (ring):
        if kind == "all-gather":
            rec["wire_bytes"] += max(res_bytes - opd_bytes, opd_bytes)
        elif kind == "all-reduce":
            rec["wire_bytes"] += 2 * opd_bytes
        else:
            rec["wire_bytes"] += opd_bytes
    return out


def roofline_terms(cost: Dict[str, float], collectives: Dict[str, Dict],
                   n_devices: int, model_flops_global: Optional[float] = None,
                   *, device_kind: str = PROJECTION_DEVICE
                   ) -> Dict[str, Any]:
    peaks = device_peaks(device_kind)
    flops_dev = float(cost.get("flops", 0.0) or 0.0)
    bytes_dev = float(cost.get("bytes accessed", 0.0) or 0.0)
    coll_operand = sum(v["operand_bytes"] for v in collectives.values())
    coll_wire = sum(v["wire_bytes"] for v in collectives.values())
    t_compute = flops_dev / peaks.flops_bf16
    t_memory = bytes_dev / peaks.hbm_bw
    t_coll = coll_wire / peaks.ici_bw
    terms = {"compute_s": t_compute, "memory_s": t_memory,
             "collective_s": t_coll,
             "hlo_flops_per_device": flops_dev,
             "hlo_bytes_per_device": bytes_dev,
             "collective_operand_bytes": coll_operand,
             "collective_wire_bytes": coll_wire}
    dom = max(("compute_s", "memory_s", "collective_s"),
              key=lambda k: terms[k])
    terms["dominant"] = dom.replace("_s", "")
    bound = max(terms["compute_s"], terms["memory_s"], terms["collective_s"])
    terms["roofline_fraction"] = (terms["compute_s"] / bound) if bound > 0 else 0.0
    if model_flops_global:
        terms["model_flops_global"] = model_flops_global
        hlo_global = flops_dev * n_devices
        terms["useful_flops_ratio"] = (model_flops_global / hlo_global
                                       if hlo_global else 0.0)
    return terms


# ---------------------------------------------------------------------------
# Analytic MODEL_FLOPS (6·N·D train / 2·N·D inference; active params for MoE)


def model_flops(cfg, shape_kind: str, tokens: int) -> float:
    n = cfg.active_params()
    if shape_kind == "train":
        return 6.0 * n * tokens
    return 2.0 * n * tokens
