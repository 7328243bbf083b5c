"""Host time of an admission round outside the solve: the mean over the
window's rounds of the round's wall time less its solve's wall time (the
program's ``admission_round`` telemetry: ``round_wall_s`` and
``solve_wall_s``), in ms."""


def read(ctx):
    ev = [e for e in ctx.get("events", []) if "solve_wall_s" in e]
    if not ev:
        return None
    return 1e3 * sum(e["round_wall_s"] - e["solve_wall_s"] for e in ev) \
        / len(ev)
