"""Reduction of a profiler trace to device busy time, kernel time, the
device operations that took most time, and idle gaps labelled by what the
harness was doing on the host.

A trace is first normalised to plain lists
(:func:`chipbench.enginetrace.normalise`), so that the reduction
(:func:`reduce`) can be checked on a small recorded trace:

``device_ops``   ``[[op, start_ns, dur_ns], ...]`` of one device's op line;
                 ``op`` is the HLO instruction's name (``flash_attention.3``
                 from ``%flash_attention.3 = bf16[...] custom-call(...)``)
``host_spans``   ``[[name, start_ns, dur_ns], ...]`` of the harness's own
                 ``jax.profiler.TraceAnnotation`` spans (``chipbench.*``)
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import functools
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

import numpy as np

SPAN_PREFIX = "chipbench."
WINDOW_SPAN = "chipbench.window"
OP_LINE = "XLA Ops"
_SUFFIX = re.compile(r"[._]\d+$")


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {len(paths)}")
    return paths[0]


def op_name(event_name: str) -> str:
    """``%name = <shape> <op>(...)`` -> ``name``; other names unchanged."""
    head = event_name.split(" = ", 1)[0]
    return head[1:] if head.startswith("%") else head


@functools.lru_cache(maxsize=None)
def op_group(name: str) -> str:
    """An op's name without the numeric suffix XLA gives each instance."""
    return _SUFFIX.sub("", name)


def window(trace: Dict) -> Tuple[int, int]:
    """Start and end, in ns, of the harness's window span."""
    win = [s for s in trace["host_spans"] if s[0] == WINDOW_SPAN]
    if len(win) != 1:
        raise RuntimeError(f"expected one {WINDOW_SPAN} span, found "
                           f"{len(win)}")
    return win[0][1], win[0][1] + win[0][2]


@dataclasses.dataclass
class WindowOps:
    """The device ops inside the window, clipped to it, in start order
    (the longer first on a tie): ``index`` into ``device_ops``, start
    ``a`` and end ``b`` in ns, and ``own``, each op's time less that of
    the ops nested in it; ``busy``, the union of their intervals, and
    ``gaps``, the window's time outside that union."""
    w0: int
    w1: int
    index: List[int]
    a: List[int]
    b: List[int]
    own: List[int]
    busy: List[Tuple[int, int]]
    gaps: List[Tuple[int, int]]


def window_ops(trace: Dict) -> WindowOps:
    """:class:`WindowOps` of the trace's harness window."""
    w0, w1 = window(trace)
    ops = trace["device_ops"]
    start = np.fromiter((o[1] for o in ops), np.int64, len(ops))
    dur = np.fromiter((o[2] for o in ops), np.int64, len(ops))
    a = np.maximum(start, w0)
    b = np.minimum(start + dur, w1)
    index = np.flatnonzero(b > a)
    a, b = a[index], b[index]
    order = np.lexsort((-b, a))
    index, a, b = index[order], a[order], b[order]
    busy = []
    if len(a):
        ends = np.maximum.accumulate(b)
        first = np.flatnonzero(np.r_[True, a[1:] > ends[:-1]])
        last = np.r_[first[1:] - 1, len(a) - 1]
        busy = list(zip(a[first].tolist(), ends[last].tolist()))
    gaps, prev = [], w0
    for s, e in busy:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    if w1 > prev:
        gaps.append((prev, w1))
    a, b = a.tolist(), b.tolist()
    return WindowOps(w0, w1, index.tolist(), a, b, _own_times(a, b), busy,
                     gaps)


def reduce(trace: Dict, kernels: Tuple[str, ...] = ("flash_attention",),
           top: int = 10) -> Dict:
    """Busy and kernel seconds inside the harness's window span, the ops
    that took most device time of their own, and the idle gaps by host
    activity."""
    w = window_ops(trace)
    ops = trace["device_ops"]
    busy_ns = sum(e - s for s, e in w.busy)
    by_op: Dict[str, int] = collections.Counter()
    kernel_ns = {k: 0 for k in kernels}
    for i, a, b, own in zip(w.index, w.a, w.b, w.own):
        group = op_group(ops[i][0])
        by_op[group] += own
        if group in kernel_ns:
            kernel_ns[group] += b - a
    spans = [(n, s, s + d) for n, s, d in trace["host_spans"]
             if n != WINDOW_SPAN]
    starts = [s for _n, s, _e in spans]
    by_host: Dict[str, int] = collections.Counter()
    for a, b in w.gaps:
        by_host[_label(spans, starts, a, b)] += b - a
    return {
        "window_s": (w.w1 - w.w0) / 1e9,
        "busy_s": busy_ns / 1e9,
        "kernel_s": {k: v / 1e9 for k, v in kernel_ns.items()},
        "device_ops": [[n, v / 1e9] for n, v in by_op.most_common(top)],
        "idle_gaps": [[n, v / 1e9] for n, v in by_host.most_common(top)],
    }


def _own_times(a: List[int], b: List[int]) -> List[int]:
    """Each op's time less that of the ops nested in it, for ops in
    start order (the longer first on a tie). Ops on one line nest like
    calls: a ``while`` loop's event spans its body's ops, so ranking by
    whole durations would count the body twice."""
    own = [e - s for s, e in zip(a, b)]
    stack: List[int] = []       # indices of the ops still open
    ends: List[int] = []        # and their ends
    for i, (s, e) in enumerate(zip(a, b)):
        while ends and ends[-1] <= s:
            ends.pop()
            stack.pop()
        if ends:
            own[stack[-1]] -= min(e, ends[-1]) - s
        stack.append(i)
        ends.append(e)
    return own


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
