"""Share of the traced window in which no operation ran on the device
(1 - the union of busy intervals / the window, averaged over the chips the
cell uses), in %."""


def read(ctx):
    tr = ctx.get("trace")
    if tr is None:
        return None
    return 100.0 * tr.idle_share()
