"""Required operations and bytes for the Ouro looped decoder, from shapes.

"Required" is what the algorithm needs, not what a program happens to do.
The stack of ``layers`` weight layers runs ``total_ut_steps`` (T) times a
token, and a decode step has to read every layer's weights T TIMES: a pass
cannot start before the one before it ends (it reads that pass's hidden
state), and 0.6 GB of weights do not stay on the chip between passes.  The
head is read once.  Each pass keeps its own K/V, so the live tokens' K/V
is read over T x layers planes.
"""


def layer_parameters(cfg):
    """qkv + out, gate + up + down, and the four norm scales."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return d * (q + 2 * kv) + q * d + 3 * d * f + 4 * d


def head_parameters(cfg):
    return cfg["hidden_size"] * cfg["vocab_size"]


def kv_bytes_per_token(cfg, layers, bytes_per=2):
    """K and V of one token over all T x layers planes."""
    return cfg["total_ut_steps"] * layers * 2 * bytes_per \
        * cfg["num_key_value_heads"] * cfg["head_dim"]


def decode_attention_bytes(cfg, layers, live_tokens, bytes_per=2):
    """Bytes decode attention has to read in one step: K and V of every
    live token, in every plane."""
    return live_tokens * kv_bytes_per_token(cfg, layers, bytes_per)


def decode_step_bytes(cfg, layers, live_tokens, rows=None, bytes_per=2):
    """Bytes one decode step has to read: every layer's weights once a
    PASS (T reads are required, see above), the head once, and K and V of
    every live token in every plane.  ``rows`` is ignored: the block keeps
    no per-row state beyond K/V."""
    weights = (cfg["total_ut_steps"] * layers * layer_parameters(cfg)
               + head_parameters(cfg)) * bytes_per
    return weights + decode_attention_bytes(cfg, layers, live_tokens,
                                            bytes_per)
