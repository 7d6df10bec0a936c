"""The trace-to-metrics reduction on a hand-built event list."""
import pytest

from ringbench import trace_reduce as tr

DEV, HOST = "/device:TPU:0", "/host:CPU"


GATHER = ("%fusion.{} = s32[63945]{{0:T(1024)S(1)}} fusion(s32[9,4833] %a, "
          "s32[63945] %b), kind=kCustom, calls=%fused_computation")


def ev(plane, line, name, start, dur, **stats):
    return tr.Event(plane, line, name, start, dur, stats)


def events():
    # Window 0..1000 ns.  Executable A runs 100..500 with a loop op
    # (while) holding two fusions and a gap between them; executable B
    # runs 700..900.  The host does "np.asarray" in 500..700.
    return [
        ev(HOST, "python", "bench.window", 0, 1000),
        ev(HOST, "python", "bench.call", 50, 900),
        ev(HOST, "python", "np.asarray", 500, 200),
        ev(DEV, tr.MODULES_LINE, "jit__run_batch(1)", 100, 400),
        ev(DEV, tr.MODULES_LINE, "jit_other", 700, 200),
        ev(DEV, tr.OPS_LINE, "%while.45 = (s32[], pred[9,7105]{0,1:T(8,128)}) "
           "while((s32[], pred[9,7105]) %tuple.3), condition=%cond", 100, 300),
        ev(DEV, tr.OPS_LINE, GATHER.format(178), 100, 120),
        ev(DEV, tr.OPS_LINE, GATHER.format(230), 250, 100),
        ev(DEV, tr.OPS_LINE, "%copy.7 = s32[7105,12]{0,1:T(8,128)} "
           "copy(s32[7105,12]{1,0:T(8,128)} %p)", 420, 80),
        ev(DEV, tr.OPS_LINE, GATHER.format(9), 700, 200),
        ev(DEV, tr.OPS_LINE, GATHER.format(1), 1200, 50),  # after the window
    ]


def test_busy_window_and_module_time():
    r = tr.reduce(events(), window="bench.window", module_key="_run_batch")
    assert r["window_s"] == pytest.approx(1000e-9)
    # Union: 100..400, 420..500, 700..900 = 300 + 80 + 200 ns.
    assert r["busy_s"] == pytest.approx(580e-9)
    assert r["devices"] == 1
    assert r["module_s"] == pytest.approx(400e-9)


def test_self_time_by_category_not_hlo_number():
    r = tr.reduce(events(), window="bench.window", module_key="_run_batch")
    ops = r["op_self_s"]
    assert ops == pytest.approx({
        "while s32[]": 80e-9,                      # 300 - 120 - 100
        "fusion kCustom s32[63945]": 420e-9,       # 120 + 100 + 200
        "copy s32[7105,12]": 80e-9})
    assert sum(ops.values()) == pytest.approx(r["busy_s"])


def test_idle_gaps_are_labelled():
    r = tr.reduce(events(), window="bench.window", module_key="_run_batch")
    gaps = r["idle_gaps_s"]
    assert gaps == pytest.approx({
        "bench.call": 100e-9,                 # 0..100: before executable A
        "in jit__run_batch(1)": 20e-9,        # 400..420: between A's ops
        "np.asarray": 200e-9,                 # 500..700: host converting
        "no host span": 100e-9,               # 900..1000: after the call
    })
    assert sum(gaps.values()) + r["busy_s"] == pytest.approx(r["window_s"])
    assert tr.top(gaps, 2) == [["np.asarray", pytest.approx(200e-9)],
                               ["bench.call", pytest.approx(100e-9)]]


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError, match="bench.window"):
        tr.reduce(events()[1:], window="bench.window", module_key="x")
