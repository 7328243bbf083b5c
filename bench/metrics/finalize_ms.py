"""Mean time per admission round of the window from the Γ landscape on
the host until the round's schedules are built (s* pick, β rounding,
discretised evaluation, ``build_schedule``), from the program's
``admission_round`` telemetry (``finalize_s``, the
``era.solve.finalize`` spans), in ms; None where no event carries the
field."""


def read(ctx):
    ev = [e for e in ctx.get("events", []) if "finalize_s" in e]
    if not ev:
        return None
    return 1e3 * sum(e["finalize_s"] for e in ev) / len(ev)
