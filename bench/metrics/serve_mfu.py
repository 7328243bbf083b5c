"""The serving rounds' share of the chip's peak: the FLOPs the window's
requests need (``bench/counts/transformer.py``: prompt processing, decode
steps, one LM head per wanted token; the engine's recomputation of the
prompt for decoding does not count) over the window times the chips used
times the peak FLOP/s, in %."""
from bench.counts import transformer


def read(ctx):
    run = ctx["run"]
    n = ctx.get("requests")
    if not n:
        return None
    flops = transformer.request_flops(ctx["model"], ctx["prompt"],
                                      ctx["decode_steps"]) * n
    window = ctx["t_end"] - run.t_window0
    return 100.0 * flops / (window * run.cell["chips"]
                            * ctx["peaks"]["peak_flops"])
