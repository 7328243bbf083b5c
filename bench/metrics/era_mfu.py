"""The admission rounds' share of the chips' peak: the operations the
window's GD steps need (``bench/counts/era_step.py``) over the traced
window times the chips used times each chip's peak FLOP/s, in %."""
from bench.counts import era_step


def read(ctx):
    tr = ctx.get("trace")
    steps = sum(e["iters"] for e in ctx.get("events", [])
                if "solve_wall_s" in e)
    if tr is None or not steps:
        return None
    ops = era_step.step_ops(ctx["u"], ctx["m"], ctx["n_aps"]) * steps
    return 100.0 * ops / (tr.window_s * len(tr.used_chips)
                          * ctx["peaks"]["peak_flops"])
