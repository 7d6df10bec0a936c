"""Collective schedules as phase-gated trace traffic.

A schedule is a census of collective bytes by kind, in execution order
(``{"bytes_by_kind": {"all-reduce": 7.3e9, ...}}``).  Each collective over
groups of ``g`` PEs becomes its textbook steps: ``halving_doubling`` does
log2(g) exchanges with the partner at local index ``i XOR dist``, ``ring``
does g - 1 shifts to the next member.  With a pod size, all-reduce runs
across pods (a group per local index, stride ``pod_size``) and the other
kinds inside contiguous pods; without one, every collective is global.
Bytes become single-flit packets of ``flit_bytes``, after dividing by a
scale chosen so that the largest per-PE burst of any phase is
``normalize_flits`` flits; any positive volume is at least one flit.

Each phase is a ``(dst, flits)`` pair of ``[P]`` arrays: PE ``s`` sends
``flits[s]`` packets to ``dst[s]`` (0 flits: idle).
"""
from __future__ import annotations

import math

import numpy as np

KINDS = ("reduce-scatter", "all-gather", "all-reduce")


def _groups(kind: str, n_pes: int, pod_size: int | None):
    if pod_size is None:
        return [list(range(n_pes))]
    if kind == "all-reduce":
        return [list(range(i, n_pes, pod_size)) for i in range(pod_size)]
    return [list(range(b, b + pod_size)) for b in range(0, n_pes, pod_size)]


def _step(groups, partner, nbytes):
    return [(g[i], g[partner(i, len(g))], nbytes)
            for g in groups for i in range(len(g))]


def _phases(kind: str, groups, nbytes: float, algorithm: str):
    g = len(groups[0])
    if algorithm == "ring":
        shift = [_step(groups, lambda i, n: (i + 1) % n, nbytes / g)
                 for _ in range(g - 1)]
        return shift + shift if kind == "all-reduce" else shift
    bits = g.bit_length() - 1
    if 1 << bits != g:
        raise ValueError(f"halving_doubling needs a power-of-two group, "
                         f"got {g}")
    scatter = [_step(groups, lambda i, n, k=k: i ^ (g >> k),
                     nbytes / (1 << k)) for k in range(1, bits + 1)]
    gather = [_step(groups, lambda i, n, k=k: i ^ (1 << (k - 1)),
                    nbytes / (1 << (bits - k + 1)))
              for k in range(1, bits + 1)]
    return {"reduce-scatter": scatter, "all-gather": gather,
            "all-reduce": scatter + gather}[kind]


def schedule_phases(census: dict, n_pes: int, *, algorithm: str,
                    pod_size: int | None, normalize_flits: int,
                    flit_bytes: int) -> tuple[list, float]:
    """``([(dst, flits), ...], scale)`` of one schedule."""
    byte_phases = []
    for kind, nbytes in census["bytes_by_kind"].items():
        if kind not in KINDS:
            raise ValueError(f"unknown collective kind {kind!r}")
        if nbytes > 0:
            byte_phases += _phases(kind, _groups(kind, n_pes, pod_size),
                                   nbytes, algorithm)
    peak = max(b for ph in byte_phases for _, _, b in ph)
    scale = max(1.0, peak / (flit_bytes * normalize_flits))
    out = []
    for ph in byte_phases:
        dst = np.zeros(n_pes, np.int32)
        flits = np.zeros(n_pes, np.int32)
        for s, d, b in ph:
            dst[s] = d
            flits[s] = max(1, math.ceil(b / (flit_bytes * scale))) if b else 0
        out.append((dst, flits))
    return out, scale
