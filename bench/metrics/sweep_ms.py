"""Mean time per admission round of the window from the sweep's dispatch
until its Γ landscape is on the host, from the program's
``admission_round`` telemetry (``sweep_s``, the ``era.solve.sweep``
span), in ms; None where no event carries the field."""


def read(ctx):
    ev = [e for e in ctx.get("events", []) if "sweep_s" in e]
    if not ev:
        return None
    return 1e3 * sum(e["sweep_s"] for e in ev) / len(ev)
