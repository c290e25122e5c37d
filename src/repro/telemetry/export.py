"""Metrics exporter (DESIGN.md §telemetry).

Renders ``ServingMetrics`` summaries (the engine's ``MetricsLedger``),
pipeline compile counters, span-ring counters and tap aggregates as the
structured log line (``metrics_line``) — the ``--metrics-interval``
one-liner: ``[metrics] k=v ...`` with nested dicts flattened into
suffixed names (NaNs dropped) and a stable key order.

Everything here is duck-typed over plain dicts — the engine imports
telemetry, so telemetry must never import the engine.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Mapping, Optional


def _flatten(prefix: str, node: Any, out: Dict[str, float]) -> None:
    if isinstance(node, Mapping):
        for k, v in node.items():
            key = f"{prefix}_{k}" if prefix else str(k)
            _flatten(_sanitize(key), v, out)
        return
    if isinstance(node, bool):
        out[prefix] = float(node)
        return
    if isinstance(node, (int, float)):
        v = float(node)
        if not math.isnan(v):
            out[prefix] = v


def _sanitize(name: str) -> str:
    return "".join(c if c.isalnum() or c == "_" else "_" for c in name)


#: metrics_line key order — SLA signals first, then throughput, then
#: device-side health; anything else appends alphabetically
_LINE_ORDER = ("served", "p50", "p99", "deadline_hit_rate", "tokens_per_s",
               "packing_efficiency", "cache_hit_rate",
               "attn_block_skip_rate", "drift_mean", "drift_max",
               "eps_norm_mean", "compiled", "span_dropped",
               "span_occupancy")


def metrics_line(summary: Mapping[str, Any],
                 taps: Optional[Mapping[str, Any]] = None,
                 compile_stats: Optional[Mapping[str, Any]] = None,
                 spans: Optional[Mapping[str, Any]] = None,
                 tag: str = "metrics") -> str:
    """The periodic structured log line: ``[metrics] served=12 ...``."""
    flat: Dict[str, float] = {}
    _flatten("", dict(summary), flat)
    if taps:
        for k in ("drift", "eps_norm"):
            sub = taps.get(k)
            if isinstance(sub, Mapping):
                for stat in ("mean", "max"):
                    if stat in sub:
                        flat[f"{k}_{stat}"] = float(sub[stat])
    if compile_stats and "compiled" in compile_stats:
        flat["compiled"] = float(compile_stats["compiled"])
    if spans:
        if "events_dropped" in spans:
            flat["span_dropped"] = float(spans["events_dropped"])
        if "occupancy" in spans:
            flat["span_occupancy"] = float(spans["occupancy"])
    keys = [k for k in _LINE_ORDER if k in flat]
    keys += sorted(k for k in flat if k not in _LINE_ORDER)
    body = " ".join(f"{k}={flat[k]:.4g}" for k in keys)
    return f"[{tag}] {body}"
