"""XLA compile requests made inside the measured window (jax.monitoring's
backend-compile event, counted whether the program then came from the
persistent cache or not).  Set-up warms every shape the cell uses, so
this should be 0."""


def read(ctx):
    return float(ctx["run"].compiles_in_window[0])
