"""Device time of the fused era_step kernel in the traced window, summed
over chips, per GD step of one lane (the window's steps from the program's
``admission_round`` telemetry), in ms."""
from bench.counts import era_step


def read(ctx):
    tr = ctx.get("trace")
    steps = sum(e["iters"] for e in ctx.get("events", [])
                if "solve_wall_s" in e)
    if tr is None or not steps:
        return None
    ns = tr.op_ns(era_step.is_kernel)
    return ns * 1e-6 / steps if ns else None
