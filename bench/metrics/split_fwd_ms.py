"""Device prefix plus edge suffix time per serving round of the window:
the benchmark's wrappers around the engine's calls into
``split_runtime.device_forward`` and ``edge_forward``, each synced on its
result in the traced run, summed per round, in ms."""


def read(ctx):
    run = ctx["run"]
    rounds = ctx.get("rounds") or []
    s = run.spans.seconds("split", run.t_window0, ctx["t_end"])
    if not s or not rounds:
        return None
    return 1e3 * sum(s) / len(rounds)
