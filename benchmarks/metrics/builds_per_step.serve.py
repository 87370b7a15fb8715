"""XLA program builds (jax.monitoring build events) inside the window over
the scheduler steps of the window. A steady path reads 0."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("steps"):
        return None
    return c["builds_in_window"] / c["steps"]
