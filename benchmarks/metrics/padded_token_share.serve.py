"""Padding over packed + padded tokens of the window's scheduler steps
(each step's packed count rounded up to its bucket), in percent."""


def read(ctx):
    c = ctx["counters"]
    tot = c.get("packed_tokens", 0) + c.get("padded_tokens", 0)
    if not tot:
        return None
    return 100.0 * c["padded_tokens"] / tot
