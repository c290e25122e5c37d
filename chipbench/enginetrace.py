"""The program's own spans and scopes in a profiler trace, and the
reduction that says why the device idles and where its time goes.

The engine stamps each phase of ``ServingEngine.step`` as a host span
``engine.<phase>`` (``step`` holding ``admit``, ``plan``, ``pack``,
``compile``, ``dispatch``, ``materialize``, ``retire``); the packed step
names its parts with ``jax.named_scope`` (``embed``, ``pack_rows``,
``dit_block`` holding ``adaln``, ``attn`` and ``mlp``, ``final``,
``guidance_solver``), which reach each device op's HLO ``op_name``
metadata. :func:`normalise` reads both besides what
:func:`chipbench.devtrace.normalise` reads, in the same lists:

``device_ops``    as ``devtrace``'s, in the same order
``host_spans``    as ``devtrace``'s (``chipbench.*``)
``engine_spans``  ``[[name, start_ns, dur_ns], ...]`` of ``engine.*``
``op_names``      one HLO ``op_name`` per device op (``""`` where the
                  trace gives none), in ``device_ops`` order

Where the program has no such spans or scopes, the lists are empty and
:func:`reduce` says so, and the readers built on it return nothing.
"""
from __future__ import annotations

import collections
from typing import Dict, Iterable, List, Tuple

from chipbench import devtrace

ENGINE_PREFIX = "engine."
SYNC_SPAN = "engine.materialize"
#: the packed step's ``jax.named_scope`` names
SCOPES = ("embed", "pack_rows", "dit_block", "adaln", "attn", "mlp",
          "final", "guidance_solver")
BLOCK_SCOPE = "dit_block"
#: the device op stat that holds the HLO instruction's ``op_name``
OP_NAME_STAT = "tf_op"
OUTSIDE = "outside engine"      # idle under no engine span
UNSCOPED = "unscoped"           # device time under no scope


def normalise(xplane_path: str, device_id: int = 0) -> Dict:
    """``devtrace.normalise``'s lists, the engine's spans and each
    device op's ``op_name`` (:func:`op_name_table`)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(xplane_path)
    table = op_name_table(xplane_path, f"/device:TPU:{device_id}")
    ops: List[list] = []
    spans: List[list] = []
    engine: List[list] = []
    found = False
    for plane in pd.planes:
        m = devtrace.DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) == device_id:
            for line in plane.lines:
                if line.name == devtrace.OP_LINE:
                    found = True
                    ops.extend([devtrace.op_name(e.name), int(e.start_ns),
                                int(e.duration_ns), table.get(e.name, "")]
                               for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(devtrace.SPAN_PREFIX):
                        spans.append([e.name, int(e.start_ns),
                                      int(e.duration_ns)])
                    elif e.name.startswith(ENGINE_PREFIX):
                        engine.append([e.name, int(e.start_ns),
                                       int(e.duration_ns)])
    if not found:
        raise RuntimeError(f"no {devtrace.OP_LINE!r} line on "
                           f"/device:TPU:{device_id} in {xplane_path}")
    ops.sort(key=lambda e: e[1])
    spans.sort(key=lambda e: e[1])
    engine.sort(key=lambda e: (e[1], -e[2]))
    return {"device_ops": [o[:3] for o in ops], "host_spans": spans,
            "engine_spans": engine, "op_names": [o[3] for o in ops]}


def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, start: int, end: int):
    """``(field number, value)`` of the protobuf message in
    ``buf[start:end]``: an int for a varint, ``(start, end)`` of the
    bytes of a length-delimited field; fixed-width fields are skipped."""
    i = start
    while i < end:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(buf, i)
            yield key >> 3, v
        elif kind == 2:
            n, i = _varint(buf, i)
            yield key >> 3, (i, i + n)
            i += n
        elif kind in (1, 5):
            i += 8 if kind == 1 else 4
        else:
            raise ValueError(f"protobuf wire type {kind} at byte {i}")


def op_name_table(xplane_path: str, plane_name: str) -> Dict[str, str]:
    """Event name -> ``tf_op`` stat of the events' metadata on one plane
    of an ``.xplane.pb`` (an ``XSpace``). A TPU's op events carry their
    HLO ``op_name`` there, not on the event, and
    ``jax.profiler.ProfileData`` does not show it; this reads just the plane's metadata maps off the wire
    (XSpace.planes = 1; XPlane: name = 2, event_metadata = 4,
    stat_metadata = 5; XEventMetadata: name = 2, stats = 5; XStat:
    metadata_id = 1, str_value = 5, ref_value = 7)."""
    with open(xplane_path, "rb") as f:
        buf = memoryview(f.read())

    def text(span) -> str:
        return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")

    for fno, plane in _fields(buf, 0, len(buf)):
        if fno != 1:
            continue
        fields = list(_fields(buf, *plane))
        if not any(k == 2 and text(v) == plane_name for k, v in fields):
            continue
        stat_names: Dict[int, str] = {}
        events = []
        for k, v in fields:
            if k not in (4, 5):
                continue
            entry = dict(_fields(buf, *v))
            if 2 not in entry:
                continue
            if k == 5:
                meta = dict(_fields(buf, *entry[2]))
                stat_names[meta.get(1, 0)] = text(meta.get(2, (0, 0)))
            else:
                events.append(entry[2])
        out: Dict[str, str] = {}
        for ev in events:
            name, stats = None, []
            for k, v in _fields(buf, *ev):
                if k == 2:
                    name = text(v)
                elif k == 5:
                    stats.append(dict(_fields(buf, *v)))
            for st in stats:
                if stat_names.get(st.get(1)) != OP_NAME_STAT:
                    continue
                if 5 in st:
                    out[name] = text(st[5])
                elif 7 in st:
                    out[name] = stat_names.get(st[7], "")
        return out
    return {}


def scope_path(op_name: str) -> str:
    """The packed step's scopes in an HLO ``op_name``, outermost first:
    ``jit(step)/while/body/dit_block/attn/dot_general`` ->
    ``dit_block/attn``; ``""`` where it holds none."""
    return "/".join(p for p in op_name.split("/") if p in SCOPES)


def _window(trace: Dict) -> Tuple[int, int]:
    win = [s for s in trace["host_spans"] if s[0] == devtrace.WINDOW_SPAN]
    if len(win) != 1:
        raise RuntimeError(f"expected one {devtrace.WINDOW_SPAN} span, "
                           f"found {len(win)}")
    return win[0][1], win[0][1] + win[0][2]


def innermost(spans: Iterable[Tuple[str, int, int]]
              ) -> List[Tuple[int, int, str]]:
    """Cut time into pieces, each labelled by the innermost span that
    covers it: of the spans open there, the one opened last (the
    shorter on a tie). Time that no span covers is left out."""
    spans = list(spans)
    bounds = sorted({t for _n, a, b in spans for t in (a, b)})
    starts = collections.defaultdict(list)
    ends = collections.defaultdict(list)
    for i, (_n, a, b) in enumerate(spans):
        if b > a:
            starts[a].append(i)
            ends[b].append(i)
    open_: Dict[int, Tuple[int, int]] = {}
    out: List[Tuple[int, int, str]] = []
    for t0, t1 in zip(bounds, bounds[1:]):
        for i in ends.get(t0, ()):
            open_.pop(i, None)
        for i in starts.get(t0, ()):
            open_[i] = (spans[i][1], spans[i][1] - spans[i][2])
        if open_:
            i = max(open_, key=open_.get)
            if out and out[-1][2] == spans[i][0] and out[-1][1] == t0:
                out[-1] = (out[-1][0], t1, spans[i][0])
            else:
                out.append((t0, t1, spans[i][0]))
    return out


def _overlap(pieces: List[Tuple[int, int, str]],
             gaps: List[Tuple[int, int]]) -> Dict[str, int]:
    """Nanoseconds of ``gaps`` under each label of ``pieces``; both
    lists sorted and each free of overlaps."""
    out: Dict[str, int] = collections.Counter()
    j = 0
    for a, b in gaps:
        while j < len(pieces) and pieces[j][1] <= a:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < b:
            ov = min(b, pieces[k][1]) - max(a, pieces[k][0])
            if ov > 0:
                out[pieces[k][2]] += ov
            k += 1
    return out


def reduce(trace: Dict) -> Dict:
    """Inside the harness's window: the device's idle time by the
    innermost ``engine.*`` span over it (``engine.step`` keeps its own
    time, outside its phases, as its own label), host time in
    ``engine.materialize``, and device time of the ops' own by the
    scope they ran under. ``engine_spans`` 0 means the program stamped
    none, and ``scoped_ops`` 0 that its ops carry no scope."""
    w0, w1 = _window(trace)
    ops = trace["device_ops"]
    metas = trace.get("op_names") or [""] * len(ops)
    kept = []
    for (name, s, d), meta in zip(ops, metas):
        a, b = max(s, w0), min(s + d, w1)
        if b > a:
            kept.append((name, a, b, meta))
    # in the order devtrace._self_times walks them, so its list lines up
    kept.sort(key=lambda e: (e[1], -e[2]))
    clipped = [e[:3] for e in kept]
    busy = devtrace._union([(a, b) for _n, a, b in clipped])
    gaps, prev = [], w0
    for a, b in busy:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    if w1 > prev:
        gaps.append((prev, w1))
    eng = [(n, max(s, w0), min(s + d, w1))
           for n, s, d in trace.get("engine_spans", ())
           if min(s + d, w1) > max(s, w0)]
    idle = dict(_overlap(innermost(eng), gaps))
    idle_ns = sum(b - a for a, b in gaps)
    idle[OUTSIDE] = idle_ns - sum(idle.values())
    sync_ns = sum(b - a for n, a, b in eng if n == SYNC_SPAN)

    by_scope: Dict[str, int] = collections.Counter()
    block_ns = 0
    scoped = 0
    paths: Dict[str, str] = {}
    for (name, _a, _b, meta), (_n, own) in zip(
            kept, devtrace._self_times(clipped)):
        path = paths.get(meta)
        if path is None:
            path = paths[meta] = scope_path(meta)
        scoped += bool(path)
        by_scope[path or UNSCOPED] += own
        if path.split("/")[0] == BLOCK_SCOPE and \
                devtrace.op_group(name) != "flash_attention":
            block_ns += own
    return {
        "engine_spans": len(eng),
        "scoped_ops": scoped,
        "idle_s": {k: v / 1e9 for k, v in sorted(
            idle.items(), key=lambda kv: -kv[1])},
        "idle_in_engine_s": (idle_ns - idle[OUTSIDE]) / 1e9,
        "sync_wait_s": sync_ns / 1e9,
        "device_s_by_scope": {k: v / 1e9 for k, v in sorted(
            by_scope.items(), key=lambda kv: -kv[1])},
        "block_s": block_ns / 1e9,
    }


def by_top_scope(device_s_by_scope: Dict[str, float]) -> Dict[str, float]:
    """Device seconds by outermost scope (``dit_block/attn`` counts
    under ``dit_block``)."""
    out: Dict[str, float] = collections.Counter()
    for path, s in device_s_by_scope.items():
        out[path.split("/")[0]] += s
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def engine_of(run: Dict, scoped: bool = False):
    """The run's engine reduction (``run["trace"]["engine"]``), or None
    where it has none, where the program stamped no ``engine.*`` span,
    or, with ``scoped``, where its device ops carry no scope."""
    tr = run.get("trace")
    eng = tr.get("engine") if tr else None
    if not eng or not eng["engine_spans"] or tr["window_s"] <= 0:
        return None
    if scoped and not eng["scoped_ops"]:
        return None
    return eng


def engine_idle_share(run: Dict):
    """Idle time under an ``engine.*`` span over the window, in %."""
    eng = engine_of(run)
    if eng is None:
        return None
    return 100.0 * eng["idle_in_engine_s"] / run["trace"]["window_s"]
