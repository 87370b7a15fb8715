"""Operations and bytes that the algorithm needs, from shapes alone. Kept
with the benchmark so that no PR that claims a gain can change them.
Recomputed operations are never counted."""


def matmul_params(cfg, head=True):
    """Parameters that a token multiplies: the projections and the MLP of
    every layer held here and, with ``head``, the output head. Not the
    embedding (a lookup) and not the norm gains."""
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    qd = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    per_layer = h * qd + 2 * h * kv + qd * h + 3 * h * f
    n = per_layer * cfg["num_hidden_layers"]
    return n + (h * cfg["vocab_size"] if head else 0)


def causal_pairs(seq_len, window=0):
    """(query, key) pairs of one causal sequence: key j seen from i where
    0 <= i - j < window (window 0 or >= seq_len: plain causal)."""
    s, w = int(seq_len), int(window or 0)
    if not w or w >= s:
        return s * (s + 1) // 2
    return w * (w + 1) // 2 + (s - w) * w


def attention_flops_per_pair(cfg):
    """QK^T and PV of every query head for one (query, key) pair."""
    return 4 * cfg["num_attention_heads"] * cfg["head_dim"]


def train_step_flops(cfg, batch, seq_len):
    """Forward and backward of one optimizer step: 6 x matmul parameters a
    token, plus attention (forward 4, backward 8 a pair and head-dim)."""
    tokens = batch * seq_len
    pairs = batch * causal_pairs(seq_len, cfg.get("sliding_window"))
    attn = 3 * attention_flops_per_pair(cfg) * pairs * cfg["num_hidden_layers"]
    return 6 * matmul_params(cfg) * tokens + attn


def flash_train_flops(cfg, batch, seq_len):
    """The attention kernels' own share of a step: forward 4 and backward 8
    multiply-adds x 2 a pair, head and head-dim; the backward's recomputed
    scores are not counted."""
    pairs = batch * causal_pairs(seq_len, cfg.get("sliding_window"))
    return 3 * attention_flops_per_pair(cfg) * pairs * cfg["num_hidden_layers"]


def serve_flops(cfg, tokens_fed, rows_sampled, context_of_fed):
    """Forward work of serving: every token fed (prompt or decode) goes
    through the layers' matmuls; the head runs once for each row that is
    sampled; attention pairs each fed token with its context
    (``context_of_fed``: the sum over fed tokens of the positions they see,
    at most the window)."""
    return (2 * matmul_params(cfg, head=False) * tokens_fed
            + 2 * cfg["hidden_size"] * cfg["vocab_size"] * rows_sampled
            + attention_flops_per_pair(cfg) * context_of_fed
            * cfg["num_hidden_layers"])


def ragged_attention_call(cfg, tokens_fed, context_of_fed, context_of_rows,
                          kv_bytes=2, act_bytes=2):
    """One ragged paged-attention call of one layer: operations, and the
    bytes it must move: every cached key and value of every row once
    (``context_of_rows``: sum over rows of the cached length, after this
    step's append, at most the window), queries in and outputs back."""
    nh, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    ops = attention_flops_per_pair(cfg) * context_of_fed
    byts = (2 * nkv * d * kv_bytes * context_of_rows
            + 2 * nh * d * act_bytes * tokens_fed)
    return ops, byts


def roofline_seconds(ops, byts, peaks):
    """Least time the chip could take, and which peak bounds it."""
    t_c, t_m = ops / peaks["flops_bf16"], byts / peaks["hbm_bytes_per_s"]
    return max(t_c, t_m), ("compute" if t_c >= t_m else "memory")
