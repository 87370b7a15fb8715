"""Share of the traced window in which no operation ran on the device
(1 - union of device operation intervals over the window), in percent."""


def read(ctx):
    t = ctx["trace"]
    if t is None or t.idle_share is None:
        return None
    return 100.0 * t.idle_share
