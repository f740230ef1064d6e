"""Reduce a JAX profiler trace to the numbers the benchmark reports.

:func:`load` reads an ``.xplane.pb`` into plain tuples: per device, the
operations that ran on it, and per host thread, its spans.  The rest works
on those tuples and on one window ``(lo, hi)`` in the trace's nanoseconds:

* :func:`busy_ns` — the union of a device's operation intervals;
* :func:`op_ns` — time of the operations whose name matches a pattern;
* :func:`exposed_ns` — time of matching operations during which no other
  operation runs on that device (a collective's exposed part);
* :func:`breakdown` — the operations that took the most device time and
  the longest idle gaps, each gap named by the host span that overlaps it
  most.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Tuple

Interval = Tuple[float, float]
#: the line of a device plane that holds one event per operation
OPS_LINE = "XLA Ops"


def find(trace_dir: str) -> str:
    """The newest ``.xplane.pb`` under ``trace_dir``."""
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def from_profile(pd) -> dict:
    """``{"devices": {plane: [(name, start, end)]}, "host": [(thread,
    name, start, end)]}`` from a :class:`jax.profiler.ProfileData`."""
    devices: Dict[str, list] = {}
    host: List[tuple] = []
    for plane in pd.planes:
        lines = [line for line in plane.lines if line.name == OPS_LINE]
        if plane.name.startswith("/device:") and lines:
            ops = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for line in lines for e in line.events]
            devices[plane.name] = sorted(ops, key=lambda o: o[1])
        elif plane.name.startswith("/host:"):
            host += [(line.name, e.name, e.start_ns,
                      e.start_ns + e.duration_ns)
                     for line in plane.lines for e in line.events]
    return {"devices": devices, "host": host}


def load(path: str) -> dict:
    import jax

    return from_profile(jax.profiler.ProfileData.from_file(path))


def _clip(ivals, lo, hi) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in ivals if b > lo and a < hi]


def union(ivals) -> List[Interval]:
    out: List[list] = []
    for a, b in sorted(ivals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _total(ivals) -> float:
    return float(sum(b - a for a, b in ivals))


def busy_ns(ops, lo: float, hi: float) -> float:
    """Nanoseconds in ``[lo, hi]`` in which some operation ran."""
    return _total(union(_clip([(a, b) for _, a, b in ops], lo, hi)))


def op_ns(ops, pattern: str, lo: float, hi: float) -> float:
    """Summed durations, clipped to the window, of operations whose name
    matches ``pattern``."""
    rx = re.compile(pattern)
    return _total(_clip([(a, b) for n, a, b in ops if rx.search(n)],
                        lo, hi))


def _subtract(ivals, cover) -> List[Interval]:
    """Parts of the (sorted, disjoint) ``ivals`` outside the (sorted,
    disjoint) ``cover``."""
    out, j = [], 0
    for a, b in ivals:
        while j < len(cover) and cover[j][1] <= a:
            j += 1
        k, cur = j, a
        while k < len(cover) and cover[k][0] < b:
            if cover[k][0] > cur:
                out.append((cur, cover[k][0]))
            cur = max(cur, cover[k][1])
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


def exposed_ns(ops, pattern: str, lo: float, hi: float) -> float:
    """Time of matching operations in ``[lo, hi]`` during which no
    non-matching operation runs on the device."""
    rx = re.compile(pattern)
    hit = union(_clip([(a, b) for n, a, b in ops if rx.search(n)], lo, hi))
    rest = union(_clip([(a, b) for n, a, b in ops if not rx.search(n)],
                       lo, hi))
    return _total(_subtract(hit, rest))


def _family(name: str) -> str:
    """An operation's family: a TPU trace names each operation by its HLO
    text (``%fusion.40 = f32[...] fusion(...)``); keep the instruction's
    name, without its ``%`` and numeric instance suffix."""
    short = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"(\.\d+)+$", "", short)


def breakdown(tr: dict, lo: float, hi: float, skip_host: str = "",
              n: int = 10) -> dict:
    """``{"device_ops": [[name, s]], "idle_gaps": [[label, s]]}``: the
    operation families that took the most device time (seconds, averaged
    over devices), and the longest gaps between operations on the first
    device, each labelled with the host span that overlaps it most (spans
    whose name matches ``skip_host``, such as the window's own step spans,
    are passed over)."""
    devs = tr["devices"]
    per: Dict[str, float] = {}
    for ops in devs.values():
        for name, a, b in ops:
            c = _clip([(a, b)], lo, hi)
            if c:
                per[_family(name)] = per.get(_family(name), 0.0) + _total(c)
    n_dev = max(len(devs), 1)
    top = sorted(per.items(), key=lambda kv: -kv[1])[:n]
    device_ops = [[k, v / n_dev / 1e9] for k, v in top]
    gaps: List[list] = []
    if devs:
        first = devs[sorted(devs)[0]]
        busy = union(_clip([(a, b) for _, a, b in first], lo, hi))
        idle = _subtract([(lo, hi)], busy)
        rx = re.compile(skip_host) if skip_host else None
        spans = [(nm, a, b) for _, nm, a, b in tr["host"]
                 if not (rx and rx.search(nm))]
        for a, b in sorted(idle, key=lambda g: g[0] - g[1])[:n]:
            best, label = 0.0, "no host span"
            for nm, sa, sb in spans:
                ov = min(b, sb) - max(a, sa)
                if ov > best:
                    best, label = ov, nm
            gaps.append([label, (b - a) / 1e9])
    return {"device_ops": device_ops, "idle_gaps": gaps}
