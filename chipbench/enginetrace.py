"""The program's own spans and scopes in a profiler trace, and the
reduction that says why the device idles and where its time goes.

The engine stamps each phase of ``ServingEngine.step`` as a host span
``engine.<phase>`` (``step`` holding ``admit``, ``plan``, ``pack``,
``compile``, ``dispatch``, ``materialize``, ``retire``); the packed step
names its parts with ``jax.named_scope`` (``embed``, ``pack_rows``,
``dit_block`` holding ``adaln``, ``attn`` and ``mlp``, ``final``,
``guidance_solver``), which reach each device op's HLO ``op_name``
metadata. :func:`normalise` reads both beside the lists that
:func:`chipbench.devtrace.reduce` reads:

``device_ops``    as ``devtrace`` describes them, sorted by start
``host_spans``    as ``devtrace`` describes them (``chipbench.*``)
``engine_spans``  ``[[name, start_ns, dur_ns], ...]`` of ``engine.*``
``op_names``      one HLO ``op_name`` per device op (``""`` where the
                  trace gives none), in ``device_ops`` order

Where the program has no such spans or scopes, the lists are empty and
:func:`reduce` says so, and the readers built on it return nothing.
"""
from __future__ import annotations

import collections
from typing import Dict, Iterable, List, Tuple

from chipbench import devtrace, xspace

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
    """Device ``device_id``'s op events with each op's ``op_name``, the
    harness's ``chipbench.*`` spans and the engine's ``engine.*`` spans.
    Only the device plane's op line and the host planes' events named
    so are read event by event."""
    device = f"/device:TPU:{device_id}"
    ops: List[list] = []
    spans: List[list] = []
    engine: List[list] = []
    found = False
    for plane in xspace.read(xplane_path).planes:
        if plane.name == device:
            meta = op_metadata(plane)
            for line in plane.lines:
                if line.name != devtrace.OP_LINE:
                    continue
                found = True
                ts = line.timestamp_ns
                ops.extend([*meta.get(e.metadata_id, ("", "")),
                            ts + e.offset_ps // 1000, e.duration_ps // 1000]
                           for e in line.events)
        elif plane.name.startswith("/host:"):
            names = {m.key: m.value.name for m in plane.event_metadata
                     if m.value.name.startswith((devtrace.SPAN_PREFIX,
                                                 ENGINE_PREFIX))}
            for line in plane.lines:
                for ev in xspace.events(line, names):
                    (engine if ev[0].startswith(ENGINE_PREFIX)
                     else spans).append(ev)
    if not found:
        raise RuntimeError(f"no {devtrace.OP_LINE!r} line on {device} in "
                           f"{xplane_path}")
    ops.sort(key=lambda e: e[2])
    spans.sort(key=lambda e: e[1])
    engine.sort(key=lambda e: (e[1], -e[2]))
    return {"device_ops": [[o[0], o[2], o[3]] for o in ops],
            "host_spans": spans, "engine_spans": engine,
            "op_names": [o[1] for o in ops]}


def op_metadata(plane) -> Dict[int, Tuple[str, str]]:
    """Metadata id -> (op, ``tf_op`` stat) on a device plane: ``op`` as
    :func:`chipbench.devtrace.op_name` gives it, the stat as a string or
    the name of the stat it refers to (``""`` where there is none). A
    TPU keeps each op's HLO ``op_name`` there, on the metadata, not on
    the event."""
    stat_names = {m.key: m.value.name for m in plane.stat_metadata}
    out: Dict[int, Tuple[str, str]] = {}
    for m in plane.event_metadata:
        tf_op = ""
        for st in m.value.stats:
            if stat_names.get(st.metadata_id) == OP_NAME_STAT:
                tf_op = st.str_value or stat_names.get(st.ref_value, "")
        out[m.key] = (devtrace.op_name(m.value.name), tf_op)
    return out


def scope_path(op_name: str) -> str:
    """The packed step's scopes in an HLO ``op_name``, outermost first:
    ``jit(step)/while/body/dit_block/attn/dot_general`` ->
    ``dit_block/attn``; ``""`` where it holds none."""
    return "/".join(p for p in op_name.split("/") if p in SCOPES)


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
    w = devtrace.window_ops(trace)
    w0, w1 = w.w0, w.w1
    ops = trace["device_ops"]
    metas = trace.get("op_names") or [""] * len(ops)
    eng = [(n, max(s, w0), min(s + d, w1))
           for n, s, d in trace.get("engine_spans", ())
           if min(s + d, w1) > max(s, w0)]
    idle = dict(_overlap(innermost(eng), w.gaps))
    idle_ns = sum(b - a for a, b in w.gaps)
    idle[OUTSIDE] = idle_ns - sum(idle.values())
    sync_ns = sum(b - a for n, a, b in eng if n == SYNC_SPAN)

    by_scope: Dict[str, int] = collections.Counter()
    block_ns = 0
    scoped = 0
    paths: Dict[str, Tuple[str, bool]] = {}
    for i, own in zip(w.index, w.own):
        meta = metas[i]
        known = paths.get(meta)
        if known is None:
            path = scope_path(meta)
            known = paths[meta] = (path,
                                   path.split("/")[0] == BLOCK_SCOPE)
        path, in_block = known
        scoped += bool(path)
        by_scope[path or UNSCOPED] += own
        if in_block and devtrace.op_group(ops[i][0]) != "flash_attention":
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
