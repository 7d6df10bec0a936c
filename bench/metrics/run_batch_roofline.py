"""The sweep's batched executable ``_run_batch``'s share of its roofline,
in %: the least time the chip could take for the traced point-cycles
(their least bytes over the HBM bandwidth, ``ringbench.roofline``), over
the executable's device time.  That time is the whole executable's: the
cycle step with its fault and phase stages, and the traffic drawn before
the cycles."""
from ringbench import peaks, roofline


def read(ctx):
    t, pc = ctx["trace"], ctx["window"]["point_cycles"]
    if not t or t["module_s"] <= 0 or not pc:
        return None
    least = (roofline.bytes_per_point_cycle(ctx["fabric"]) * pc
             / peaks.of(ctx["device_kind"])["hbm_bytes_per_s"])
    return 100.0 * least / t["module_s"]
