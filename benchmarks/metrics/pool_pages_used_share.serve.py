"""Peak pages in use over the pool's pages, all layers' pools together,
over the window's steps, in percent."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("pool_pages"):
        return None
    return 100.0 * c["pool_pages_used_peak"] / c["pool_pages"]
