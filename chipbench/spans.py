"""The program's own spans and scopes, read from a traced run's profile.

The program marks its host stages with ``jax.profiler.TraceAnnotation``
spans (``pipeline.sample``, ``pipeline.gather``, ``engine.layout``,
``engine.place``; each tagged ``batch``, the place span also ``bytes``) and
its device layers with ``jax.named_scope`` (``combine``,
``mirror_backward``).  :func:`load` reads both out of an ``.xplane.pb``:

* ``host``: ``(thread, name, start_ns, end_ns, stats)`` for every event on
  a host plane, ``thread`` the index of its line (one line per thread;
  :class:`jax.profiler.ProfileData` names them all ``python``), ``stats``
  the span's arguments;
* ``tf_op``: per device plane, each operation's name (as the trace's ops
  line names it, see ``chipbench/trace.py``) mapped to its ``tf_op`` stat,
  the HLO ``op_name`` path that carries the named scopes
  (``jit(step)/transpose(jvp(combine))/dot_general``).

``ProfileData`` gives no event metadata's stats, so ``tf_op`` comes from
walking the serialized ``XSpace`` (``xplane.proto``: ``XSpace.planes`` 1;
``XPlane.name`` 2, ``event_metadata`` 4, ``stat_metadata`` 5, both maps of
id to value; ``XEventMetadata.name`` 2, ``stats`` 5; ``XStatMetadata.id``
1, ``name`` 2; ``XStat.metadata_id`` 1, ``str_value`` 5, ``ref_value`` 7)
with the standard library alone.  A metric reads the run's profile through
:func:`program`, which decodes it once per run.
"""
from __future__ import annotations

import os
import re
from typing import Dict, Iterator, List, Optional, Tuple

from chipbench import trace

#: where ``chipbench/run.py`` writes a traced run's profile
TRACE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), ".chipbench_out", "trace")
#: the stat that carries an operation's name-stack path
TF_OP = "tf_op"


def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        byte = buf[i]
        i += 1
        out |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return out, i


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """``(field number, value)`` of one protobuf message: an int for a
    varint, a memoryview for a length-delimited field, raw bytes for the
    fixed-width ones."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            val, i = bytes(buf[i:i + size]), i + size
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, val


def _map_values(entries) -> Iterator[memoryview]:
    """The values of a protobuf map's entries (key 1, value 2)."""
    for entry in entries:
        for num, val in _fields(entry):
            if num == 2:
                yield val


def tf_ops(xspace: bytes) -> Dict[str, Dict[str, str]]:
    """``{device plane: {operation name: tf_op path}}`` from a serialized
    ``XSpace``.  An operation name met with two different paths keeps the
    first (its text includes its shapes and operands, so a clash means two
    programs with one instruction alike)."""
    out: Dict[str, Dict[str, str]] = {}
    for num, plane in _fields(memoryview(xspace)):
        if num != 1:
            continue
        name, events, stat_meta = "", [], []
        for pnum, val in _fields(plane):
            if pnum == 2:
                name = bytes(val).decode()
            elif pnum == 4:
                events.append(val)
            elif pnum == 5:
                stat_meta.append(val)
        if not name.startswith("/device:"):
            continue
        stat_names = {}
        for meta in _map_values(stat_meta):
            fields = dict(_fields(meta))
            stat_names[fields.get(1, 0)] = bytes(fields.get(2, b"")).decode()
        tf_id = next((k for k, v in stat_names.items() if v == TF_OP), None)
        paths: Dict[str, str] = {}
        for meta in _map_values(events):
            op, path = None, None
            for fnum, val in _fields(meta):
                if fnum == 2:
                    op = bytes(val).decode()
                elif fnum == 5 and tf_id is not None:
                    stat = dict(_fields(val))
                    if stat.get(1) != tf_id:
                        continue
                    if 5 in stat:
                        path = bytes(stat[5]).decode()
                    elif 7 in stat:
                        path = stat_names.get(stat[7])
            if op is not None and path is not None:
                paths.setdefault(op, path)
        out[name] = paths
    return out


def host_spans(pd) -> List[tuple]:
    """``(thread, name, start_ns, end_ns, stats)`` for every event on the
    host planes of a :class:`jax.profiler.ProfileData`."""
    spans, thread = [], 0
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans += [(thread, e.name, e.start_ns, e.start_ns + e.duration_ns,
                       dict(e.stats)) for e in line.events]
            thread += 1
    return spans


def load(path: str) -> dict:
    """``{"host": host spans, "tf_op": per-plane paths}`` of one
    ``.xplane.pb``."""
    import jax

    with open(path, "rb") as f:
        raw = f.read()
    pd = jax.profiler.ProfileData.from_serialized_xspace(raw)
    return {"host": host_spans(pd), "tf_op": tf_ops(raw)}


def program(ctx: dict) -> Optional[dict]:
    """The run's :func:`load`, decoded on the first call and kept in
    ``ctx``; None for a run without a trace."""
    if not ctx.get("trace"):
        return None
    if "program" not in ctx:
        ctx["program"] = load(trace.find(TRACE_DIR))
    return ctx["program"]


def window_spans(ctx: dict, name: str) -> List[tuple]:
    """``(start_ns, end_ns, stats)`` of the host spans called ``name`` that
    start inside the traced window."""
    prog = program(ctx)
    if prog is None:
        return []
    lo, hi = ctx["trace_window"]
    return [(a, b, st) for _, n, a, b, st in prog["host"]
            if n == name and lo <= a < hi]


def mean_span_ms(ctx: dict, name: str) -> Optional[float]:
    """Mean duration of the window's ``name`` spans in ms; None if none."""
    spans = window_spans(ctx, name)
    if not spans:
        return None
    return sum(b - a for a, b, _ in spans) / len(spans) / 1e6


_WRAPPED = re.compile(r"^[\w.-]+\((.*)\)$")


def in_scope(path: str, scope: str) -> bool:
    """Whether a ``tf_op`` path has the segment ``scope``, bare or inside
    transformation wrappers (``transpose(jvp(combine))``)."""
    for seg in path.split("/"):
        seg = seg.rstrip(":")
        while True:
            m = _WRAPPED.match(seg)
            if not m:
                break
            seg = m.group(1)
        if seg == scope:
            return True
    return False


def scoped_ms(ctx: dict, scope: str) -> Optional[float]:
    """Device ms per window step, per chip, of the operations whose
    ``tf_op`` path has the segment ``scope``; None where none ran."""
    prog = program(ctx)
    if prog is None or not ctx["trace"]["devices"]:
        return None
    lo, hi = ctx["trace_window"]
    devices = ctx["trace"]["devices"]
    ns = 0.0
    for plane, ops in devices.items():
        paths = prog["tf_op"].get(plane, {})
        hit = {n for n in {o[0] for o in ops}
               if in_scope(paths.get(n, ""), scope)}
        ns += trace.op_ns([o for o in ops if o[0] in hit], "", lo, hi)
    ns /= len(devices)
    return ns / 1e6 / ctx["steps"] if ns else None
