"""Mean wall time of the solve (scheduler + Li-GD sweep + finalize) per
admission round of the window, from the program's ``admission_round``
telemetry (``solve_wall_s``), in ms."""


def read(ctx):
    ev = [e for e in ctx.get("events", []) if "solve_wall_s" in e]
    if not ev:
        return None
    return 1e3 * sum(e["solve_wall_s"] for e in ev) / len(ev)
