"""Reduction of a profiler trace to device busy time, kernel time, the
device operations that took most time, and idle gaps labelled by what the
harness was doing on the host.

A trace is first normalised to plain lists (:func:`normalise`), so that
the reduction (:func:`reduce`) can be checked on a small recorded trace:

``device_ops``   ``[[op, start_ns, dur_ns], ...]`` of one device's op line;
                 ``op`` is the HLO instruction's name (``flash_attention.3``
                 from ``%flash_attention.3 = bf16[...] custom-call(...)``)
``host_spans``   ``[[name, start_ns, dur_ns], ...]`` of the harness's own
                 ``jax.profiler.TraceAnnotation`` spans (``chipbench.*``)
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

SPAN_PREFIX = "chipbench."
WINDOW_SPAN = "chipbench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"
_SUFFIX = re.compile(r"[._]\d+$")


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {len(paths)}")
    return paths[0]


def normalise(xplane_path: str, device_id: int = 0) -> Dict:
    """Device ``device_id``'s op events and the harness's host spans."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(xplane_path)
    ops: List[list] = []
    spans: List[list] = []
    found = False
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) == device_id:
            for line in plane.lines:
                if line.name == OP_LINE:
                    found = True
                    ops.extend([op_name(e.name), int(e.start_ns),
                                int(e.duration_ns)] for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend([e.name, int(e.start_ns), int(e.duration_ns)]
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    if not found:
        raise RuntimeError(f"no {OP_LINE!r} line on /device:TPU:{device_id} "
                           f"in {xplane_path}")
    ops.sort(key=lambda e: e[1])
    spans.sort(key=lambda e: e[1])
    return {"device_ops": ops, "host_spans": spans}


def op_name(event_name: str) -> str:
    """``%name = <shape> <op>(...)`` -> ``name``; other names unchanged."""
    head = event_name.split(" = ", 1)[0]
    return head[1:] if head.startswith("%") else head


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def op_group(name: str) -> str:
    """An op's name without the numeric suffix XLA gives each instance."""
    return _SUFFIX.sub("", name)


def reduce(trace: Dict, kernels: Tuple[str, ...] = ("flash_attention",),
           top: int = 10) -> Dict:
    """Busy and kernel seconds inside the harness's window span, the ops
    that took most device time of their own, and the idle gaps by host
    activity."""
    win = [s for s in trace["host_spans"] if s[0] == WINDOW_SPAN]
    if len(win) != 1:
        raise RuntimeError(f"expected one {WINDOW_SPAN} span, found "
                           f"{len(win)}")
    w0, w1 = win[0][1], win[0][1] + win[0][2]
    clipped = []
    for name, s, d in trace["device_ops"]:
        a, b = max(s, w0), min(s + d, w1)
        if b > a:
            clipped.append((name, a, b))
    busy = _union([(a, b) for _n, a, b in clipped])
    busy_ns = sum(b - a for a, b in busy)
    by_op: Dict[str, int] = collections.Counter()
    for name, own in _self_times(clipped):
        by_op[op_group(name)] += own
    kernel_ns = {k: 0 for k in kernels}
    for name, a, b in clipped:
        if op_group(name) in kernel_ns:
            kernel_ns[op_group(name)] += b - a
    gaps = []
    prev = w0
    for a, b in busy:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    if w1 > prev:
        gaps.append((prev, w1))
    spans = [(n, s, s + d) for n, s, d in trace["host_spans"]
             if n != WINDOW_SPAN]
    starts = [s for _n, s, _e in spans]
    by_host: Dict[str, int] = collections.Counter()
    for a, b in gaps:
        by_host[_label(spans, starts, a, b)] += b - a
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": busy_ns / 1e9,
        "kernel_s": {k: v / 1e9 for k, v in kernel_ns.items()},
        "device_ops": [[n, v / 1e9] for n, v in by_op.most_common(top)],
        "idle_gaps": [[n, v / 1e9] for n, v in by_host.most_common(top)],
    }


def _self_times(ops: List[Tuple[str, int, int]]) -> List[Tuple[str, int]]:
    """Each op's time less that of the ops nested in it. Ops on one line
    nest like calls: a ``while`` loop's event spans its body's ops, so
    ranking by whole durations would count the body twice."""
    out: List[list] = []
    stack: List[list] = []
    for name, a, b in sorted(ops, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][2] <= a:
            stack.pop()
        if stack:
            stack[-1][3] -= min(b, stack[-1][2]) - a
        entry = [name, a, b, b - a]
        out.append(entry)
        stack.append(entry)
    return [(e[0], e[3]) for e in out]


def _label(spans: List[Tuple[str, int, int]], starts: List[int], a: int,
           b: int) -> str:
    """The harness span that overlaps the gap [a, b) most. The harness's
    spans follow one another on one thread, so they do not overlap and
    the walk back from the last one starting before b stops early."""
    best: Optional[str] = None
    best_ov = 0
    j = bisect.bisect_left(starts, b) - 1
    while j >= 0 and spans[j][2] > a:
        name, s, e = spans[j]
        ov = min(e, b) - max(s, a)
        if ov > best_ov:
            best, best_ov = name, ov
        j -= 1
    return best or "host:unannotated"
