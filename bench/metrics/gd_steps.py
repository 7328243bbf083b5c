"""GD steps per admission round of the window, summed over the lanes the
round solved (the program's ``admission_round`` telemetry: ``iters``, an
exact count), as the mean over rounds."""


def read(ctx):
    ev = [e for e in ctx.get("events", []) if "solve_wall_s" in e]
    if not ev:
        return None
    return sum(e["iters"] for e in ev) / len(ev)
