"""Device time by step stage, and idle time by program span, from a trace.

The program names its step's stages with ``jax.named_scope``
(``cycle.route``, ``cycle.arbitrate``, ``cycle.fault``, ``cycle.move``,
``cycle.inject``, ``cycle.count``, ``cycle.phase``, and ``point.traffic``
for the traffic drawn before the cycles), and its host layers with
``repro.*`` spans (``repro.obs``).  Events are ``trace_reduce.Event``s.

- ``stage_self_s``: the self time of every op that runs inside an
  executable whose name holds ``module_key``, summed by stage.  An op's
  stage is the scope in its op-name metadata: a stat of the op's event
  (``tf_op`` and the like), or else the ``metadata={op_name=...}`` of the
  instruction of that name in the compiled executable's text.  Ops with
  neither go under ``unscoped``.
- ``idle_by_span``: the window's idle stretches on the first device,
  split by the innermost ``repro.*`` host span over each part; a stretch
  inside a run of an executable reads ``in <executable>``.
"""
from __future__ import annotations

import bisect
import collections
import re

from ringbench import trace_reduce as tr

UNSCOPED = "unscoped"
SPAN_PREFIX = "repro."
NO_SPAN = "no repro span"

_STAGE = re.compile(r"(?<![\w.])(point\.traffic|cycle\.[a-z]+)(?![\w])")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.-]+) = .*?"
                    r"metadata=\{[^}]*op_name=\"([^\"]*)\"", re.M)
_NAME = re.compile(r"^%?([\w.-]+)")


def stage_of(op_name: str) -> str | None:
    """The stage scope an op name lies under, if any."""
    m = _STAGE.findall(op_name)
    return m[-1] if m else None


def hlo_op_names(hlo_text: str) -> dict[str, str]:
    """Instruction name -> op-name metadata, from compiled HLO text."""
    return {m.group(1): m.group(2) for m in _INSTR.finditer(hlo_text)}


def _stage(ev: tr.Event, by_instr: dict[str, str]) -> str:
    for v in ev.stats.values():
        if isinstance(v, str):
            s = stage_of(v)
            if s:
                return s
    m = _NAME.match(ev.name)
    s = stage_of(by_instr.get(m.group(1), "")) if m else None
    return s or UNSCOPED


def _window(events: list[tr.Event], window: str) -> tuple[float, float]:
    spans = [e for e in events if e.plane.startswith("/host:")
             and e.name == window]
    if not spans:
        raise ValueError(f"the trace has no host span named {window!r}")
    return (min(e.start_ns for e in spans),
            max(e.start_ns + e.dur_ns for e in spans))


def _run_at(t: float, runs: list[tr.Event], starts: list[float]
            ) -> tr.Event | None:
    """The executable run (sorted by start) under time ``t``, if any."""
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and t < runs[i].start_ns + runs[i].dur_ns:
        return runs[i]
    return None


def stage_self_s(events: list[tr.Event], *, window: str, module_key: str,
                 hlo_text: str = "") -> dict[str, float]:
    """Seconds of op self time by stage, in the window, inside runs of the
    executables named by ``module_key``, averaged over the devices."""
    w0, w1 = _window(events, window)
    by_instr = hlo_op_names(hlo_text)
    ops, runs = collections.defaultdict(list), collections.defaultdict(list)
    for e in events:
        if not tr._is_device(e.plane) or e.start_ns + e.dur_ns <= w0 \
                or e.start_ns >= w1:
            continue
        if e.line == tr.OPS_LINE:
            ops[e.plane].append(e)
        elif e.line == tr.MODULES_LINE and module_key in e.name:
            runs[e.plane].append(e)
    out: dict[str, float] = collections.defaultdict(float)
    planes = [p for p in ops if runs[p]]
    for p in planes:
        mods = sorted(runs[p], key=lambda e: e.start_ns)
        starts = [e.start_ns for e in mods]
        mine = [e for e in ops[p]
                if _run_at(e.start_ns + e.dur_ns / 2, mods, starts)]
        stack: list[list] = []   # [end, stage, self time left]
        for ev in sorted(mine, key=lambda e: (e.start_ns, -e.dur_ns)):
            while stack and stack[-1][0] <= ev.start_ns:
                _, s, t = stack.pop()
                out[s] += t
            if stack:
                stack[-1][2] -= ev.dur_ns
            stack.append([ev.start_ns + ev.dur_ns, _stage(ev, by_instr),
                          ev.dur_ns])
        for _, s, t in stack:
            out[s] += t
    return {s: t / len(planes) / 1e9 for s, t in out.items()}


def idle_by_span(events: list[tr.Event], *, window: str
                 ) -> dict[str, float]:
    """Seconds of the window in which the first device ran no op, by the
    innermost ``repro.*`` span over them (``in <executable>`` inside a
    run of one, ``no repro span`` where none is open)."""
    w0, w1 = _window(events, window)
    dev = sorted({e.plane for e in events if tr._is_device(e.plane)
                  and e.line == tr.OPS_LINE})
    if not dev:
        return {}
    plane = dev[0]
    busy = tr._union(tr._clip(e, w0, w1) for e in events
                     if e.plane == plane and e.line == tr.OPS_LINE)
    mods = sorted((e for e in events if e.plane == plane
                   and e.line == tr.MODULES_LINE), key=lambda e: e.start_ns)
    starts = [e.start_ns for e in mods]
    spans = [e for e in events if e.plane.startswith("/host:")
             and e.name.startswith(SPAN_PREFIX) and e.dur_ns > 0]
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    out: dict[str, float] = collections.defaultdict(float)
    for a, b in zip(edges[::2], edges[1::2]):
        if b <= a:
            continue
        cuts = sorted({a, b} | {x for e in spans
                                for x in (e.start_ns, e.start_ns + e.dur_ns)
                                if a < x < b})
        for lo, hi in zip(cuts, cuts[1:]):
            out[_label(lo, hi, mods, starts, spans)] += (hi - lo) / 1e9
    return dict(out)


def _label(lo: float, hi: float, mods: list[tr.Event], starts: list[float],
           spans: list[tr.Event]) -> str:
    t = (lo + hi) / 2
    run = _run_at(t, mods, starts)
    if run is not None:
        return f"in {run.name}"
    inside = [e for e in spans if e.start_ns <= t < e.start_ns + e.dur_ns]
    if not inside:
        return NO_SPAN
    return min(inside, key=lambda e: e.dur_ns).name


def us_per_point_cycle(ctx: dict, stage: str) -> float | None:
    """A metric reader's value: microseconds of the step's device self
    time in ``stage`` per simulated point-cycle of the traced window, or
    None where the trace names no such stage."""
    t, pc = ctx["stages"].get(stage), ctx["window"]["point_cycles"]
    return None if t is None or not pc else 1e6 * t / pc
