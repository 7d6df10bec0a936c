"""Spans and counters inside the simulator: the arbitration-pass counter,
the named stages of the step, and the host spans and registry of
``repro.obs``."""
import contextlib
import contextvars
import dataclasses
import glob
import re
import sys
import threading

import jax
import pytest

from repro import compile_cache, obs
from repro.core import experiment, sim, sweep, topology
from repro.core.spec import TopologySpec

CYCLES, WARMUP = 200, 50
STAGES = ("point.traffic", "cycle.route", "cycle.arbitrate", "cycle.move",
          "cycle.inject", "cycle.count")


def _cfg(**kw):
    return sim.SimConfig(cycles=CYCLES, warmup=WARMUP, **kw)


def _sweep_counts(topo, cfgs):
    """(results, passes run, passes needed, point-cycles) of one sweep."""
    before = obs.snapshot()
    rs = sweep.sweep(topo, cfgs)
    after = obs.snapshot()
    d = {k: after.get(k, 0) - before.get(k, 0)
         for k in ("sweep.arb_passes_run", "sweep.arb_passes_needed",
                   "sweep.point_cycles")}
    return (rs, d["sweep.arb_passes_run"], d["sweep.arb_passes_needed"],
            d["sweep.point_cycles"])


# ---------------------------------------------------------------------------
# The arbitration-pass counter.
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n", [16, 64])
def test_arb_passes_bounds_and_paths_agree(n):
    """One pass a cycle with nothing to arbitrate; 1 to ARB_ITERS passes a
    cycle under load; the same count under simulate, sweep and the Pallas
    interpret backend."""
    t = topology.build("ring_mesh", n)
    idle = _cfg(inj_rate=0.0)
    busy = _cfg(inj_rate=1.0, pattern="hotspot", seed=3)
    assert sim.simulate(t, idle).arb_passes == CYCLES
    rx = sim.simulate(t, busy)
    assert CYCLES < rx.arb_passes <= CYCLES * sim.ARB_ITERS
    rp = sim.simulate(t, dataclasses.replace(busy, backend="pallas"))
    assert rp.arb_passes == rx.arb_passes
    batched = sweep.sweep(t, [idle, busy])
    assert [r.arb_passes for r in batched] == [CYCLES, rx.arb_passes]
    pallas = sweep.sweep(t, [dataclasses.replace(c, backend="pallas")
                             for c in (idle, busy)])
    assert [r.arb_passes for r in pallas] == [CYCLES, rx.arb_passes]


def test_one_point_dispatch_runs_what_it_needs():
    t = topology.build("flat_mesh", 16)
    (r,), run, needed, pc = _sweep_counts(
        t, [_cfg(inj_rate=0.9, pattern="transpose", seed=2)])
    assert run == needed == r.arb_passes > CYCLES
    assert pc == CYCLES


def test_batch_runs_its_slowest_points_passes():
    """An idle point (one pass a cycle) batched with a saturated one: the
    vmapped loop runs the saturated point's passes every cycle for both,
    so the batch runs 2 x its passes and needs its passes + CYCLES."""
    t = topology.build("ring_mesh", 16)
    (idle, sat), run, needed, pc = _sweep_counts(
        t, [_cfg(inj_rate=0.0), _cfg(inj_rate=1.0, pattern="hotspot")])
    assert idle.arb_passes == CYCLES < sat.arb_passes
    assert needed == idle.arb_passes + sat.arb_passes
    assert run == 2 * sat.arb_passes > needed
    assert pc == 2 * CYCLES


def test_pallas_batch_runs_what_it_needs():
    """The fused kernel runs batched points one after another."""
    t = topology.build("ring_mesh", 16)
    cfgs = [_cfg(inj_rate=0.0, backend="pallas"),
            _cfg(inj_rate=1.0, pattern="hotspot", backend="pallas")]
    _, run, needed, _ = _sweep_counts(t, cfgs)
    assert run == needed


def test_report_json_round_trips_arb_passes():
    exp = experiment.Experiment(
        topology=TopologySpec("ring_mesh", 16),
        budget=experiment.Budget(cycles=CYCLES, warmup=WARMUP),
        inj_rate=0.6, seed=4)
    rep = exp.run()
    assert rep.sim.arb_passes >= CYCLES
    assert rep.row()["arb_passes"] == rep.sim.arb_passes
    back = experiment.Report.from_json(rep.to_json())
    assert back == rep and back.sim.arb_passes == rep.sim.arb_passes


# ---------------------------------------------------------------------------
# Named stages.
# ---------------------------------------------------------------------------
def _lowered(topo, cfgs):
    geom, groups = sweep._grouped(topo, cfgs)
    key, _, points = groups[0]
    return sweep._run_batch.lower(
        geom, points, cycles=key[0], warmup=key[1], starvation_limit=key[2],
        backend=key[3], strict_barrier=key[4], watchdog=key[5])


def test_lowered_run_batch_names_every_stage():
    from repro.faults import sample_faults
    from repro.trace import Trace, TraceSpec
    t = TopologySpec("ring_mesh", 16).build()
    text = _lowered(t, [_cfg(inj_rate=0.5)]).as_text(debug_info=True)
    for s in STAGES:
        assert s in text, s
    assert "cycle.fault" not in text and "cycle.phase" not in text
    f = sample_faults(t, n_dead_links=2, seed=1)
    text = _lowered(t, [_cfg(inj_rate=0.5, faults=f)]).as_text(
        debug_info=True)
    assert "cycle.fault" in text
    replay = Trace(trace=TraceSpec(n_pes=16, phases=(((0, 5, 2),),)))
    text = _lowered(t, [sim.SimConfig(cycles=CYCLES, warmup=0,
                                      pattern=replay)]).as_text(
        debug_info=True)
    assert "cycle.phase" in text


def _compiled_ops(topo, cfgs) -> list[str]:
    """The compiled batch program's instructions, without metadata or
    instruction numbers."""
    jax.clear_caches()
    text = _lowered(topo, cfgs).compile().as_text()
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    return re.findall(r"^\s*(?:ROOT\s+)?%?[\w-]+(?:\.\d+)* = .*$",
                      re.sub(r"\.\d+", "", text), re.M)


def test_scopes_change_no_op(monkeypatch):
    t = TopologySpec("ring_mesh", 16).build()
    cfgs = [_cfg(inj_rate=0.5), _cfg(inj_rate=1.0, seed=1)]
    scoped = _compiled_ops(t, cfgs)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    try:
        plain = _compiled_ops(t, cfgs)
        assert "cycle.route" not in _lowered(t, cfgs).as_text(
            debug_info=True)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert scoped == plain and len(scoped) > 100


# ---------------------------------------------------------------------------
# Host spans and the registry.
# ---------------------------------------------------------------------------
def _host_spans(trace_dir) -> list:
    from jax.profiler import ProfileData
    path, = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    return [(ev.name, dict(ev.stats))
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith("repro.")]


def test_one_call_spans_share_its_call_id(tmp_path):
    exps = [experiment.Experiment(
        topology=TopologySpec("flat_mesh", 16),
        budget=experiment.Budget(cycles=120, warmup=20), inj_rate=r)
        for r in (0.2, 0.7)]
    sweep.reset_caches()       # the call lowers and compiles its program
    before = obs.snapshot()
    with jax.profiler.trace(str(tmp_path)):
        experiment.run_experiments(exps)
    after = obs.snapshot()
    spans = _host_spans(tmp_path)
    names = [n for n, _ in spans]
    assert names.count("repro.run_experiments") == 1
    assert names.count("repro.sweep.wait") >= 1
    for n in ("repro.sweep.prepare", "repro.sweep.lower",
              "repro.sweep.compile", "repro.sweep.to_result",
              "repro.experiment.report"):
        assert n in names, n
    assert len({st["call"] for _, st in spans}) == 1
    assert all(st["group"] == 0 for n, st in spans
               if n in ("repro.sweep.lower", "repro.sweep.compile",
                        "repro.sweep.wait", "repro.sweep.to_result"))
    assert (after["repro.run_experiments.n"]
            - before.get("repro.run_experiments.n", 0)) == 1
    assert after["repro.sweep.wait.s"] > before.get("repro.sweep.wait.s", 0)


def test_span_times_counts_and_inherits_ids():
    obs.reset("test.")
    with obs.span("test.outer", call=7):
        with obs.tag(group=2):
            with obs.span("test.inner"):
                ids = obs._IDS.get()
        ctx = contextvars.copy_context()
    assert ids == {"call": 7, "group": 2}
    assert ctx.run(obs._IDS.get) == {"call": 7}
    assert obs._IDS.get() == {}
    c = obs.snapshot()
    assert c["test.outer.n"] == c["test.inner.n"] == 1
    assert c["test.outer.s"] >= c["test.inner.s"] >= 0
    obs.add("test.count", 3)
    obs.add("test.count")
    assert obs.snapshot()["test.count"] == 4
    obs.reset("test.inner")
    assert not any(k.startswith("test.inner") for k in obs.snapshot())
    assert obs.snapshot()["test.count"] == 4
    obs.reset("test.")


def test_span_counts_a_block_that_raises():
    obs.reset("test.")
    with pytest.raises(KeyError):
        with obs.span("test.raises"):
            raise KeyError("x")
    assert obs.snapshot()["test.raises.n"] == 1
    obs.reset("test.")


def test_counters_survive_concurrent_adds():
    obs.reset("test.")
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work():
            for _ in range(2000):
                obs.add("test.concurrent")
                with obs.span("test.concurrent_span"):
                    pass
        threads = [threading.Thread(target=work) for _ in range(16)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60)
        assert not any(th.is_alive() for th in threads)
    finally:
        sys.setswitchinterval(interval)
    c = obs.snapshot()
    assert c["test.concurrent"] == c["test.concurrent_span.n"] == 32000
    obs.reset("test.")


def test_compile_stats_and_cache_stats_are_registry_views():
    t = TopologySpec("ring_mesh", 16).build()
    sweep.reset_caches()
    assert not any(k.startswith(("sweep.", "repro.sweep."))
                   for k in obs.snapshot())
    stats = sweep.compile_stats()
    assert set(stats) == {"batch_executables", "batch_xla_compiles",
                          "single_cache_entries"}
    assert stats["batch_xla_compiles"] == stats["batch_executables"] == 0
    sweep.sweep(t, [_cfg(inj_rate=0.3)])
    assert sweep.compile_stats()["batch_xla_compiles"] == 1
    assert obs.snapshot()["sweep.batch_xla_compiles"] == 1
    c0 = compile_cache.stats()
    assert set(c0) == {"dir", "entries", "hits", "misses", "compile_s"}
    assert isinstance(c0["hits"], int) and isinstance(c0["misses"], int)
    assert isinstance(c0["compile_s"], float)
    compile_cache._on_event("/jax/compilation_cache/cache_hits")
    compile_cache._on_event("/jax/compilation_cache/cache_misses")
    compile_cache._on_duration("/jax/core/compile/backend_compile_duration",
                               0.5)
    c1 = compile_cache.stats()
    assert (c1["hits"], c1["misses"]) == (c0["hits"] + 1, c0["misses"] + 1)
    assert c1["compile_s"] == pytest.approx(c0["compile_s"] + 0.5)


def test_fanout_counter_counts_big_batches_only():
    """``sweep.arb_fanout_point_cycles`` counts the point-cycles of the
    dispatches that ran the fan-out lookups: a batch of at least
    ``ARB_FANOUT_MIN_BATCH`` points, and not a 1-point dispatch."""
    t = topology.build("ring_mesh", 16)
    big = [_cfg(inj_rate=0.2 * (i + 1), seed=i)
           for i in range(sweep.ARB_FANOUT_MIN_BATCH)]
    one = sim.SimConfig(cycles=CYCLES // 2, warmup=WARMUP, inj_rate=0.5)
    before = obs.snapshot()
    sweep.sweep(t, big + [one])
    after = obs.snapshot()
    d = {k: after.get(k, 0) - before.get(k, 0)
         for k in ("sweep.arb_fanout_point_cycles", "sweep.point_cycles")}
    assert d["sweep.arb_fanout_point_cycles"] == len(big) * CYCLES
    assert d["sweep.point_cycles"] == len(big) * CYCLES + CYCLES // 2
    assert sweep.arb_fanout(len(big), "xla")
    assert not sweep.arb_fanout(1, "xla")
    assert not sweep.arb_fanout(len(big), "pallas")
