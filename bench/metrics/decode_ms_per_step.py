"""Mean time of one decode step of the engine's decode continuation
(``models.transformer.decode_step``, wrapped and synced on its result in
the traced run) over the window, in ms."""


def read(ctx):
    run = ctx["run"]
    s = run.spans.seconds("decode.step", run.t_window0, ctx["t_end"])
    if not s:
        return None
    return 1e3 * sum(s) / len(s)
