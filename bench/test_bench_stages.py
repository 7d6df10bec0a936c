"""Device time by step stage and idle time by program span, on hand-built
events; the new program-counter readers, on a small run and on a program
without a registry."""
import sys
import time

import pytest

from ringbench import harness, small, stages
from ringbench import trace_reduce as tr

DEV, HOST = "/device:TPU:0", "/host:CPU"
PREFIX = "jit(_run_batch)/vmap(while)/body/closed_call"


def ev(plane, line, name, start, dur, **stats):
    return tr.Event(plane, line, name, start, dur, stats)


def op(name, start, dur, scope=None):
    stats = {"tf_op": f"{PREFIX}/{scope}/gather"} if scope else {}
    return ev(DEV, tr.OPS_LINE, name, start, dur, **stats)


def events():
    # Window 0..1000 ns.  _run_batch runs 100..500: a cycle loop (while,
    # unscoped) holding an arbitrate gather, a move gather named only by
    # the compiled text, and a gap; another executable runs 700..900.
    # The host: one call 50..950, its wait 100..500, its report 600..950.
    return [
        ev(HOST, "python", "bench.window", 0, 1000),
        ev(HOST, "python", "repro.run_experiments", 50, 900, call=1),
        ev(HOST, "python", "repro.sweep.wait", 100, 400, call=1, group=0),
        ev(HOST, "python", "repro.experiment.report", 600, 350, call=1),
        ev(HOST, "python", "$array.py:631 _value", 500, 50),
        ev(DEV, tr.MODULES_LINE, "jit__run_batch(1)", 100, 400),
        ev(DEV, tr.MODULES_LINE, "jit_other", 700, 200),
        op("%while.45 = (s32[]) while((s32[]) %t)", 100, 380),
        op("%fusion.178 = s32[63945]{0} fusion(s32[9] %a), kind=kCustom",
           120, 200, "cycle.arbitrate"),
        op("%fusion.230 = pred[85260]{0} fusion(s32[9] %a), kind=kCustom",
           330, 100),
        op("%fusion.9 = s32[7105]{0} fusion(s32[9] %a), kind=kCustom",
           700, 200, "cycle.move"),   # not _run_batch: left out
    ]


HLO = """
ENTRY %main {
  %fusion.230 = pred[85260]{0} fusion(s32[9] %a), kind=kCustom, calls=%f, metadata={op_name="jit(_run_batch)/vmap(while)/body/cycle.move/and" source_file="noc_step.py" source_line=301}
  ROOT %tuple.3 = (s32[]) tuple(%x)
}
"""


def test_stage_of_finds_the_scope_anywhere_in_the_name():
    assert stages.stage_of(f"{PREFIX}/cycle.route/gather") == "cycle.route"
    assert stages.stage_of("jit(_run_batch)/vmap(point.traffic)/mul") \
        == "point.traffic"
    assert stages.stage_of("jit(_run_batch)/while/body/add") is None
    assert stages.stage_of("recycle.routex/add") is None


def test_self_time_by_stage_from_stats_and_hlo_text():
    r = stages.stage_self_s(events(), window="bench.window",
                            module_key="_run_batch", hlo_text=HLO)
    assert r == pytest.approx({
        "unscoped": 80e-9,           # the loop: 380 - 200 - 100
        "cycle.arbitrate": 200e-9,   # from the op event's tf_op stat
        "cycle.move": 100e-9,        # from the compiled text's metadata
    })
    r = stages.stage_self_s(events(), window="bench.window",
                            module_key="_run_batch")
    assert r == pytest.approx({"unscoped": 180e-9,
                               "cycle.arbitrate": 200e-9})


def test_hlo_op_names_reads_each_instruction():
    assert stages.hlo_op_names(HLO) == {
        "fusion.230": "jit(_run_batch)/vmap(while)/body/cycle.move/and"}


def test_idle_time_by_innermost_program_span():
    # Busy: 100..480 and 700..900.  JAX's own span ($array.py) is not the
    # program's and labels nothing.
    r = stages.idle_by_span(events(), window="bench.window")
    assert r == pytest.approx({
        "no repro span": 100e-9,             # 0..50, 950..1000
        "repro.run_experiments": 150e-9,     # 50..100, 500..600
        "in jit__run_batch(1)": 20e-9,       # 480..500
        "repro.experiment.report": 150e-9,   # 600..700, 900..950
    })
    assert sum(r.values()) == pytest.approx(1000e-9 - 580e-9)


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError, match="bench.window"):
        stages.stage_self_s(events()[1:], window="bench.window",
                            module_key="x")


def test_compiled_run_batch_names_its_stages():
    """The metadata join finds every stage of a real compiled program."""
    from repro.core import sim, sweep
    from repro.core.spec import TopologySpec
    t = TopologySpec("ring_mesh", 16).build()
    geom, groups = sweep._grouped(t, [sim.SimConfig(cycles=40, warmup=10)])
    key, _, points = groups[0]
    exe = sweep._executable(geom, points, *key)
    found = {stages.stage_of(n) for n in
             stages.hlo_op_names(exe.as_text()).values()}
    assert {"point.traffic", "cycle.route", "cycle.arbitrate", "cycle.move",
            "cycle.inject", "cycle.count"} <= found


NEW = ("step.arb_passes_run", "step.arb_passes_needed",
       "experiment.host_s_per_call", "sweep.lower_s")


def test_stage_split_run_reports_the_new_counters():
    import stage_split
    from repro import obs
    from repro.core import sweep
    sweep.reset_caches()    # the run lowers its program in set-up
    obs.reset()
    spec = small.shrink(harness.load_cell("ring_mesh_1024.fig15_grid"))
    out = stage_split.run(spec, 2**31 + 17, 0.05,
                          t_start=time.perf_counter(), platform="cpu")
    m = {k: v["value"] for k, v in out["result"]["metrics"].items()}
    assert out["result"]["correct"] is True
    assert set(NEW) <= set(m)
    assert 24 >= m["step.arb_passes_run"] >= m["step.arb_passes_needed"] >= 1
    assert m["experiment.host_s_per_call"] > 0 and m["sweep.lower_s"] > 0
    c = out["registry"]
    assert c["repro.run_experiments.n"] == out["result"]["attempted"] // 9
    assert c["sweep.point_cycles"] == c["repro.run_experiments.n"] * 9 * 160


def test_readers_are_silent_without_the_registry(monkeypatch):
    """A program older than ``repro.obs``: the new readers return None."""
    import repro
    monkeypatch.delattr(repro, "obs", raising=False)
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    ctx = {"window": {"seconds": 1.0, "calls": 1, "point_cycles": 900}}
    for name in NEW + ("experiment.host_s_per_call.faults",):
        assert harness.read_metric(name, ctx) is None, name


STAGE_READERS = {
    "step.arbitrate_us_per_point_cycle": "cycle.arbitrate",
    "step.move_us_per_point_cycle": "cycle.move",
    "step.phase_us_per_point_cycle": "cycle.phase",
    "step.arbitrate_us_per_point_cycle.faults": "cycle.arbitrate",
    "step.move_us_per_point_cycle.faults": "cycle.move",
}


@pytest.mark.parametrize("name", list(STAGE_READERS))
def test_stage_readers(name):
    """Microseconds of the stage per point-cycle from the run's stage
    split; silent where the split lacks the stage (the CPU's trace has no
    device plane, and a grid's step may run no phase op)."""
    ctx = {"window": {"seconds": 1.0, "calls": 1, "point_cycles": 2000},
           "stages": {STAGE_READERS[name]: 0.5, "unscoped": 0.1}}
    assert harness.read_metric(name, ctx) == pytest.approx(250.0)
    ctx["stages"] = {"unscoped": 0.1}
    assert harness.read_metric(name, ctx) is None
