"""Reduce a profiler trace to device busy time, op categories and idle gaps.

Events are ``Event(plane, line, name, start_ns, dur_ns, stats)``, one per
profiler event, on the one clock the profiler puts every plane on.  Device
planes are those named ``/device:<accelerator>:<n>``; their ``XLA Ops`` line
holds one event per executed HLO op (a loop's op spans the ops of its
body), their ``XLA Modules`` line one event per executable run.  Host
planes (``/host:...``) hold the benchmark's own spans and JAX's.

The window is the host span named ``window`` (the benchmark wraps its
measured calls in it).  In it:

- busy: the union of a device's op intervals, averaged over the devices
  that ran any op;
- op categories: each op's self time (its span less the ops nested in it),
  summed by category: the op's HLO instruction name without its number,
  its fusion kind and the type of its (first) result, without layout
  (``%fusion.178 = s32[63945]{0:T(1024)} fusion(...), kind=kCustom`` ->
  ``fusion kCustom s32[63945]``), so that categories survive renumbering;
- module time: the summed spans of the executables whose name contains a
  given key;
- idle gaps: the stretches of the window in which the first device ran no
  op.  A gap inside a run of an executable (between two of its ops) is
  labelled ``in <executable>``; any other by the innermost host span
  covering its middle, which says what the host was doing.
"""
from __future__ import annotations

import bisect
import collections
import re
from typing import NamedTuple


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    stats: dict


OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def events_from_file(path: str) -> list[Event]:
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if not (plane.name.startswith("/device:")
                or plane.name.startswith("/host:")):
            continue
        for line in plane.lines:
            for ev in line.events:
                out.append(Event(plane.name, line.name, ev.name,
                                 ev.start_ns, ev.duration_ns,
                                 dict(ev.stats)))
    return out


def _is_device(plane: str) -> bool:
    return plane.startswith("/device:") and "CPU" not in plane


_HLO = re.compile(r"%?([\w.-]+?)(?:\.\d+)? = \(?(\w+\[[\d,]*\])")
_KIND = re.compile(r"kind=(k\w+)")


def category(ev: Event) -> str:
    m = _HLO.match(ev.name)
    if not m:
        return re.sub(r"\.\d+$", "", ev.name)
    kind = _KIND.search(ev.name)
    return " ".join([m.group(1)] + ([kind.group(1)] if kind else [])
                    + [m.group(2)])


def _clip(ev: Event, w0: float, w1: float) -> tuple[float, float]:
    return max(ev.start_ns, w0), min(ev.start_ns + ev.dur_ns, w1)


def _union(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _self_times(ops: list[Event]) -> dict[str, float]:
    """Per-category self time of one line's ops, nesting by containment."""
    out: dict[str, float] = collections.defaultdict(float)
    stack: list[list] = []   # [end, category, self time left]
    for ev in sorted(ops, key=lambda e: (e.start_ns, -e.dur_ns)):
        end = ev.start_ns + ev.dur_ns
        while stack and stack[-1][0] <= ev.start_ns:
            _, cat, t = stack.pop()
            out[cat] += t
        if stack:
            stack[-1][2] -= ev.dur_ns
        stack.append([end, category(ev), ev.dur_ns])
    for _, cat, t in stack:
        out[cat] += t
    return out


def reduce(events: list[Event], *, window: str, module_key: str) -> dict:
    spans = [e for e in events if e.plane.startswith("/host:")
             and e.name == window]
    if not spans:
        raise ValueError(f"the trace has no host span named {window!r}")
    w0 = min(e.start_ns for e in spans)
    w1 = max(e.start_ns + e.dur_ns for e in spans)
    by_plane: dict[str, list[Event]] = collections.defaultdict(list)
    runs: dict[str, list[Event]] = collections.defaultdict(list)
    modules = collections.defaultdict(float)
    for e in events:
        if not _is_device(e.plane):
            continue
        a, b = _clip(e, w0, w1)
        if b <= a:
            continue
        if e.line == OPS_LINE:
            by_plane[e.plane].append(e)
        elif e.line == MODULES_LINE:
            runs[e.plane].append(e)
            if module_key in e.name:
                modules[e.plane] += b - a
    busy = {p: _union(_clip(e, w0, w1) for e in ops)
            for p, ops in by_plane.items()}
    n_dev = len(busy)
    cats: dict[str, float] = collections.defaultdict(float)
    for ops in by_plane.values():
        for c, t in _self_times(ops).items():
            cats[c] += t / n_dev
    gaps: dict[str, float] = collections.defaultdict(float)
    if busy:
        plane = sorted(busy)[0]
        first = busy[plane]
        edges = [w0] + [x for iv in first for x in iv] + [w1]
        mods = sorted(runs[plane], key=lambda e: e.start_ns)
        starts = [e.start_ns for e in mods]
        host = [e for e in events if e.plane.startswith("/host:")
                and e.dur_ns > 0 and e.name != window]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps[_label(host, mods, starts, (a + b) / 2)] += (b - a) / 1e9
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": (sum(sum(b - a for a, b in iv) for iv in busy.values())
                   / n_dev / 1e9) if n_dev else 0.0,
        "devices": n_dev,
        "module_s": (sum(modules.values()) / len(modules) / 1e9
                     if modules else 0.0),
        "op_self_s": {c: t / 1e9 for c, t in cats.items()},
        "idle_gaps_s": dict(gaps),
    }


def _label(host: list[Event], mods: list[Event], starts: list[float],
           t: float) -> str:
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and t < mods[i].start_ns + mods[i].dur_ns:
        return f"in {mods[i].name}"
    inside = [e for e in host if e.start_ns <= t < e.start_ns + e.dur_ns]
    if not inside:
        return "no host span"
    return min(inside, key=lambda e: e.dur_ns).name


def top(d: dict[str, float], n: int = 10) -> list[list]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]
