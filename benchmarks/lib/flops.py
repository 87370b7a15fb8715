"""What every family's counts share. The operations and bytes that an
architecture's algorithm needs are its family's (families/<family>.py),
kept with the benchmark so that no PR that claims a gain can change them."""


def causal_pairs(seq_len, window=0):
    """(query, key) pairs of one causal sequence: key j seen from i where
    0 <= i - j < window (window 0 or >= seq_len: plain causal)."""
    s, w = int(seq_len), int(window or 0)
    if not w or w >= s:
        return s * (s + 1) // 2
    return w * (w + 1) // 2 + (s - w) * w


def roofline_seconds(ops, byts, peaks):
    """Least time the chip could take, and which peak bounds it."""
    t_c, t_m = ops / peaks["flops_bf16"], byts / peaks["hbm_bytes_per_s"]
    return max(t_c, t_m), ("compute" if t_c >= t_m else "memory")
