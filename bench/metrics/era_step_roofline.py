"""The fused era_step kernel's share of its roofline in the traced window:
the least time the chip could take for the window's GD steps (the larger
of the steps' operations over peak FLOP/s and their bytes over peak
bandwidth, from ``bench/counts/era_step.py``) over the kernel's device
time, in %."""
from bench.counts import era_step


def read(ctx):
    tr = ctx.get("trace")
    steps = sum(e["iters"] for e in ctx.get("events", [])
                if "solve_wall_s" in e)
    if tr is None or not steps:
        return None
    ns = tr.op_ns(era_step.is_kernel)
    if not ns:
        return None
    t_min, _ = era_step.min_seconds(ctx["u"], ctx["m"], ctx["n_aps"],
                                    ctx["peaks"]["peak_flops"],
                                    ctx["peaks"]["mem_bw"])
    return 100.0 * t_min * steps / (ns * 1e-9)
