"""Batched sweep engine: one XLA compilation per (geometry, cycle budget).

The paper's evaluation (Figs. 9-17) is a grid of simulations over injection
rates x traffic patterns x seeds x locality regimes.  Running each point as
its own dispatch pays per-point Python/host-sync overhead and — in the seed
implementation — recompiled whenever a pattern mode changed.  Here the grid
is batched instead: every per-point parameter is a traced ``SweepPoint``
field (``core.sim``), so a whole grid ``jax.vmap``s through a single
compiled program and returns all results from one device execution.

Compile-cache key (DESIGN.md §4): array *shapes* only — (n_links, n_phys,
n_pes, queue depth, fan-in and fan-out widths) from the geometry, the
batch size (which also picks the arbitration lookups, ``arb_fanout``), the
batch's trace phase count and lowered fault-entry count, and the static
ints (cycles, warmup, starvation_limit, trace-barrier semantics).  Rates,
seeds, localities, destination maps and fault drop masks are data.
``sweep()`` groups its configs by the static ints and trace-ness, so
mixed-budget batches still compile once per distinct budget; points of a
group with fewer trace phases or a smaller fault bucket than the group's
largest are padded to it with entries that never act (DESIGN.md §12-13),
so one call of mixed trace lengths or of healthy and faulted points is
one dispatch.  Results always come back in input order.

    topo = topology.build_ring_mesh(256)
    cfgs = sweep.grid(inj_rates=(0.25, 0.5, 1.0),
                      patterns=sim.PATTERNS, seeds=(0, 1), cycles=900)
    results = sweep.sweep(topo, cfgs)       # one compile, one dispatch

``compile_stats()`` exposes the jit cache sizes so benchmarks can assert
the one-compile-per-geometry property (logged into BENCH_noc.json).

Each sweep times its stages as ``repro.obs`` host spans
(``repro.sweep.prepare``, ``.lower``, ``.compile``, ``.wait``,
``.to_result``; every span of one group carries ``group=<k>``), and
counts the arbitration passes its dispatches ran and needed
(``sweep.arb_passes_run``, ``sweep.arb_passes_needed``) beside the
point-cycles they simulated (``sweep.point_cycles``), of which those of
dispatches that ran the fan-out lookups (``arb_fanout``) are
``sweep.arb_fanout_point_cycles``.  It counts its dispatches
(``sweep.dispatches``) and the points among them whose phase tables or
fault entries were padded (``sweep.padded_points``).
"""
from __future__ import annotations

import contextvars
import dataclasses
import functools
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Iterable, Sequence

import jax
import numpy as np

from repro import obs
from repro.core import sim
from repro.core import topology as topo_mod
from repro.core import traffic


_AXIS = "points"   # the vmap axis of a batch

# Smallest batch whose arbitration fixpoint reads each queue's target
# through the static fan-out table (``cycle_step``'s ``arb_fanout``).  A
# gather whose index differs per point costs about B x L one-element
# fetches a pass; the fan-out form gathers L x Fo static indices, each
# fetching a B-wide row (1-D, so dearer per index, at B = 1).  Measured
# on a TPU v5e with ``bench/stage_split.py`` on the last B points of the
# figs 15-17 grid at 1024 PEs, ``cycle.arbitrate`` us per point-cycle,
# direct -> fan-out: ring_mesh B=1 5614 -> 15730, B=2 2325 -> 2568,
# B=3 2059 -> 1817, B=4 1948 -> 1413; flat_mesh B=2 5513 -> 5355,
# B=3 5066 -> 3849.  Ring-mesh loses at 2 points, both win from 3.
ARB_FANOUT_MIN_BATCH = 3


def arb_fanout(batch: int, backend: str) -> bool:
    """Whether a ``batch``-point dispatch runs the fan-out lookups: XLA
    backend batches of at least ``ARB_FANOUT_MIN_BATCH`` points."""
    return backend == "xla" and batch >= ARB_FANOUT_MIN_BATCH


@functools.partial(
    jax.jit, static_argnames=("cycles", "warmup", "starvation_limit",
                              "backend", "arb_iters", "strict_barrier",
                              "watchdog"))
def _run_batch(geom: sim.Geometry, points: sim.SweepPoint, *, cycles: int,
               warmup: int, starvation_limit: int, backend: str = "xla",
               strict_barrier: bool = False, watchdog: int = 0,
               arb_iters: int = sim.ARB_ITERS) -> sim.Metrics:
    """vmap of the simulator core over a stacked SweepPoint batch; the
    geometry is broadcast (in_axes=None) so it is uploaded once.  Both
    backends vmap — the fused pallas kernel batches its traffic streams
    against the broadcast geometry.  The XLA scan learns the vmap axis,
    so that it counts the arbitration passes the batch ran, and takes the
    fan-out lookups when the batch is large enough (``arb_fanout``)."""
    run = functools.partial(sim._run_core, cycles=cycles, warmup=warmup,
                            starvation_limit=starvation_limit,
                            backend=backend, arb_iters=arb_iters,
                            strict_barrier=strict_barrier, watchdog=watchdog,
                            batch_axis=_AXIS if backend == "xla" else None,
                            arb_fanout=arb_fanout(points.seed.shape[0],
                                                  backend))
    return jax.vmap(run, in_axes=(None, 0), axis_name=_AXIS)(geom, points)


# AOT executable cache.  jit's own cache would work, but holding the
# compiled objects ourselves lets ``precompile`` build them from worker
# threads (XLA compilation releases the GIL, so compiles for different
# geometries overlap each other and any python-side work) and gives the
# benchmarks an exact compile counter to log (``sweep.batch_xla_compiles``
# in ``repro.obs``).
_AOT: dict[tuple, object] = {}
_AOT_LOCK = threading.Lock()


def _static_key(geom: sim.Geometry, batch: int, trace_shape: tuple,
                fault_shape: tuple, cycles: int, warmup: int, starv: int,
                backend: str, strict_barrier: bool, watchdog: int,
                arb_iters: int) -> tuple:
    return (geom.n_links, geom.n_phys, geom.n_pes, geom.depth,
            geom.cand.shape, geom.intab.shape, geom.outtab.shape, batch,
            trace_shape, fault_shape, cycles, warmup, starv, backend,
            strict_barrier, watchdog, arb_iters)


def _executable(geom: sim.Geometry, points: sim.SweepPoint, cycles: int,
                warmup: int, starv: int, backend: str = "xla",
                strict_barrier: bool = False, watchdog: int = 0,
                arb_iters: int = sim.ARB_ITERS):
    key = _static_key(geom, points.seed.shape[0],
                      tuple(points.ph_dst.shape),
                      tuple(points.fault_links.shape), cycles, warmup, starv,
                      backend, strict_barrier, watchdog, arb_iters)
    with _AOT_LOCK:
        exe = _AOT.get(key)
    if exe is None:
        with obs.span("repro.sweep.lower"):
            lowered = _run_batch.lower(
                geom, points, cycles=cycles, warmup=warmup,
                starvation_limit=starv, backend=backend,
                strict_barrier=strict_barrier, watchdog=watchdog,
                arb_iters=arb_iters)
        # XLA compilation, or the executable's load from the persistent
        # compile cache.
        with obs.span("repro.sweep.compile"):
            exe = lowered.compile()
        with _AOT_LOCK:
            if key in _AOT:          # lost a compile race: keep the winner
                exe = _AOT[key]      # (counter stays exact either way)
            else:
                _AOT[key] = exe
                obs.add("sweep.batch_xla_compiles")
    return exe


def _stack_points(cfgs: Sequence[sim.SimConfig],
                  topo: topo_mod.Topology) -> sim.SweepPoint:
    """The points of ``cfgs`` stacked on a leading batch axis.  Phase
    tables get trailing rows of zero flits and fault entries trailing
    ``(n_links, 0.0, 0)`` entries, up to the group's largest: a zero-flit
    phase completes the cycle after the one before it, and a pad entry
    never fires, so every counter of a padded point is its own."""
    pts = [sim.make_point(c, topo.n_pes, topo) for c in cfgs]
    n_ph = max(p.ph_dst.shape[0] for p in pts)
    n_f = max(p.fault_links.shape[0] for p in pts)

    def pad(x, n, value):
        if x.shape[0] == n:
            return x
        return np.pad(x, [(0, n - x.shape[0])] + [(0, 0)] * (x.ndim - 1),
                      constant_values=value)

    pts = [dataclasses.replace(
        p, ph_dst=pad(p.ph_dst, n_ph, 0), ph_flits=pad(p.ph_flits, n_ph, 0),
        fault_links=pad(p.fault_links, n_f, topo.n_links),
        fault_drop_p=pad(p.fault_drop_p, n_f, 0.0),
        fault_onset=pad(p.fault_onset, n_f, 0)) for p in pts]
    return jax.tree.map(lambda *xs: np.stack(xs), *pts)


def _own_shape(cfg: sim.SimConfig, topo: topo_mod.Topology
               ) -> tuple[int, int]:
    """A point's own (trace phase count, lowered fault count), before the
    group pads it."""
    return (traffic.resolve(cfg.pattern).n_trace_phases,
            cfg.faults.n_lowered(topo) if cfg.faults else 0)


# How many leading entries of a group key are _executable statics; the
# last (trace-ness) only gates which points may stack together.
_N_EXE_STATICS = 6


def _grouped(topo: topo_mod.Topology, cfgs: Sequence[sim.SimConfig]):
    """(geometry, [(static key, config indexes, stacked points), ...])."""
    with obs.span("repro.sweep.prepare"):
        geom = sim.build_geometry(topo)
        groups: dict[tuple, list[int]] = {}
        for i, c in enumerate(cfgs):
            # Trace-ness is a static branch of the step, so statistical
            # and trace points never share a dispatch.  The phase count
            # and the lowered fault count are array shapes too, but
            # ``_stack_points`` pads them to the group's largest, so they
            # do not split a group.
            groups.setdefault((c.cycles, c.warmup, c.starvation_limit,
                               c.backend, c.strict_barrier, c.watchdog,
                               traffic.resolve(c.pattern).is_trace),
                              []).append(i)
        return geom, [(key[:_N_EXE_STATICS], idxs,
                       _stack_points([cfgs[i] for i in idxs], topo))
                      for key, idxs in groups.items()]


def _dispatch(topo, cfgs, geom, idxs, points, exe, out):
    with obs.span("repro.sweep.wait"):
        metrics = jax.tree.map(np.asarray, exe(geom, points))
    with obs.span("repro.sweep.to_result"):
        for b, i in enumerate(idxs):
            m_i = jax.tree.map(lambda x: x[b], metrics)
            out[i] = sim._to_result(topo, cfgs[i], m_i)
    obs.add("sweep.dispatches")
    shape = (points.ph_dst.shape[1], points.fault_links.shape[1])
    obs.add("sweep.padded_points",
            sum(_own_shape(cfgs[i], topo) != shape for i in idxs))
    obs.add("sweep.arb_passes_run", int(metrics.arb_passes_run.sum()))
    obs.add("sweep.arb_passes_needed", int(metrics.arb_passes.sum()))
    point_cycles = cfgs[idxs[0]].cycles * len(idxs)
    obs.add("sweep.point_cycles", point_cycles)
    # Added (0 too) on every dispatch: the counter's presence says the
    # program can run the fan-out lookups.
    obs.add("sweep.arb_fanout_point_cycles",
            point_cycles if arb_fanout(len(idxs), cfgs[idxs[0]].backend)
            else 0)


def sweep(topo: topo_mod.Topology,
          cfgs: Sequence[sim.SimConfig],
          verify: bool = False) -> list[sim.SimResult]:
    """Run every config on ``topo`` in batched device executions.

    Configs sharing (cycles, warmup, starvation_limit, backend,
    strict_barrier, watchdog) — the static compile key — and trace-ness
    are executed as one vmapped dispatch, whatever their trace phase
    counts and fault buckets (the smaller are padded); results return in
    the order of ``cfgs``.  Metrics are bit-identical to per-point
    ``sim.simulate``.

    ``verify=True`` statically certifies the fabric first (deadlock
    freedom + route liveness, ``analysis.fabric``) and raises
    ``CertificationError`` before dispatching anything — the pre-flight
    for long grids on morphed/repaired fabrics (DESIGN.md §14).
    """
    if verify:
        from repro.analysis import fabric
        fabric.require_certified(topo)
    if not cfgs:
        return []
    geom, groups = _grouped(topo, cfgs)
    out: list[sim.SimResult | None] = [None] * len(cfgs)
    for k, (key, idxs, points) in enumerate(groups):
        with obs.tag(group=k):
            exe = _executable(geom, points, *key)
            _dispatch(topo, cfgs, geom, idxs, points, exe, out)
    return out  # type: ignore[return-value]


def precompile(tasks: Sequence[tuple[topo_mod.Topology,
                                     Sequence[sim.SimConfig]]],
               workers: int = 1) -> None:
    """Compile every (geometry, batch, budget) executable ``sweep`` will
    need for ``tasks``.  XLA compilation releases the GIL, so this can run
    from a worker thread concurrently with python-side work."""
    jobs = []
    for topo, cfgs in tasks:
        geom, groups = _grouped(topo, cfgs)
        jobs.extend(_in_group(k, _executable, geom, points, *key)
                    for k, (key, _, points) in enumerate(groups))
    with ThreadPoolExecutor(max_workers=workers) as ex:
        list(ex.map(lambda job: job(), jobs))


def sweep_many(tasks: Sequence[tuple[topo_mod.Topology,
                                     Sequence[sim.SimConfig]]]
               ) -> list[list[sim.SimResult]]:
    """Run a sweep per task, pipelining compilation with execution: a
    background thread compiles task i+1's executable (XLA releases the
    GIL) while the foreground dispatches task i, so the compile and
    dispatch streams overlap instead of serializing."""
    prepared = [(topo, cfgs, *_grouped(topo, cfgs)) for topo, cfgs in tasks]
    with ThreadPoolExecutor(max_workers=1) as ex:
        futs = [[ex.submit(_in_group(k, _executable, geom, points, *key))
                 for k, (key, _, points) in enumerate(groups)]
                for _, _, geom, groups in prepared]
        results = []
        for (topo, cfgs, geom, groups), group_futs in zip(prepared, futs):
            out: list[sim.SimResult | None] = [None] * len(cfgs)
            for k, ((_, idxs, points), fut) in enumerate(zip(groups,
                                                              group_futs)):
                with obs.tag(group=k):
                    _dispatch(topo, cfgs, geom, idxs, points, fut.result(),
                              out)
            results.append(out)
    return results  # type: ignore[return-value]


def _in_group(k, fn, *args):
    """``fn(*args)`` as a job for another thread, its spans tagged
    ``group=k`` and carrying the ids of the caller's spans."""
    ctx = contextvars.copy_context()

    def job():
        with obs.tag(group=k):
            return fn(*args)
    return functools.partial(ctx.run, job)


def grid(inj_rates: Iterable[float] = (0.25,),
         patterns: Iterable = (sim.UNIFORM,),
         seeds: Iterable[int] = (0,),
         cycles: int = 1200, warmup: int = 400,
         locality_ringlet: float = 0.0, locality_block: float = 0.0,
         starvation_limit: int = 8,
         backend: str = "xla",
         faults: Iterable = (None,)) -> list[sim.SimConfig]:
    """Cross-product config grid (rate-major, then pattern, then seed,
    then fault scenario).  ``patterns`` accepts legacy strings and
    ``traffic.TrafficSpec`` instances alike; the locality kwargs describe
    the grid's regime and are folded into specs that don't declare their
    own (declaring both is an error).  ``backend`` selects the simulator
    hot path (``"xla"`` scan oracle / ``"pallas"`` fused kernel) for every
    point.  ``faults`` is an axis of ``FaultSpec | None`` scenarios
    injected *unrepaired* (runtime drop masks on the healthy geometry, so
    the whole resilience grid still batches — fault lowering pads to
    shared bucket sizes and the lowered arrays are per-point data)."""
    patterns = tuple(patterns)  # seeds/patterns are re-iterated per rate:
    seeds = tuple(seeds)        # materialize so one-shot iterators work
    faults = tuple(faults)
    cfgs = []
    for ir in inj_rates:
        for p in patterns:
            lr, lb = locality_ringlet, locality_block
            if isinstance(p, traffic.TrafficSpec) and (lr or lb):
                if p.locality_ringlet or p.locality_block:
                    raise ValueError(
                        "locality declared both on grid() and on the "
                        f"TrafficSpec {traffic.name_of(p)!r}")
                p = dataclasses.replace(p, locality_ringlet=lr,
                                        locality_block=lb)
            if isinstance(p, traffic.TrafficSpec):
                lr = lb = 0.0
            cfgs.extend(
                sim.SimConfig(cycles=cycles, warmup=warmup, inj_rate=ir,
                              pattern=p, seed=s, locality_ringlet=lr,
                              locality_block=lb,
                              starvation_limit=starvation_limit,
                              backend=backend, faults=f)
                for s in seeds for f in faults)
    return cfgs


def sweep_grid(topo: topo_mod.Topology, verify: bool = False,
               **grid_kwargs) -> list[sim.SimResult]:
    """Convenience: build a ``grid(**grid_kwargs)`` and ``sweep`` it
    (``verify=True`` runs the static certification pre-flight first)."""
    return sweep(topo, grid(**grid_kwargs), verify=verify)


def compile_stats() -> dict:
    """Compile counters, for the benchmark's one-compile-per-geometry
    accounting in BENCH_noc.json (``batch_xla_compiles`` is a view of
    ``repro.obs``' ``sweep.batch_xla_compiles``)."""
    return {
        "batch_executables": len(_AOT),
        "batch_xla_compiles": int(obs.snapshot().get(
            "sweep.batch_xla_compiles", 0)),
        "single_cache_entries": sim.compile_cache_size(),
    }


def reset_caches() -> None:
    """Drop every compiled executable and zero the sweep's counters and
    spans in ``repro.obs`` (and ``sim``'s single-point cache), so tests
    can assert compile counts from a clean slate."""
    with _AOT_LOCK:
        _AOT.clear()
        obs.reset("sweep.", "repro.sweep.")
    sim.clear_compile_cache()
