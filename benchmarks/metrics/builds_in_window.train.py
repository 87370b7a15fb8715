"""XLA program builds (jax.monitoring build events) inside the window."""


def read(ctx):
    return ctx["counters"].get("builds_in_window")
