"""Required operations and bytes for the Falcon-H1 block, from shapes.

"Required" is what the algorithm needs, not what a program happens to do:
a decode step has to read every weight once, the K/V of the tokens that
are live, and to read AND write the recurrent and convolution state of
every row that decodes (the state of a row that does not decode need not
be touched); the chunked scan's products are counted once, over the lower
triangle of each chunk.
"""


def _mixer(cfg):
    heads, hd = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    d_ssm = cfg["mamba_d_ssm"]
    gn = cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    return heads, hd, d_ssm, gn, d_ssm + 2 * gn


def layer_parameters(cfg):
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    heads, _, d_ssm, _, conv = _mixer(cfg)
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    mixer = d * (d_ssm + conv + heads) + d_ssm * d \
        + (cfg["mamba_d_conv"] + 1) * conv + 3 * heads + d_ssm
    return mixer + d * (q + 2 * kv) + q * d + 3 * d * f + 2 * d


def head_parameters(cfg):
    return cfg["hidden_size"] * cfg["vocab_size"]


def ssm_state_bytes(cfg):
    """One row's recurrent state in one layer: float32
    [heads, head, state]."""
    heads, hd, _, _, _ = _mixer(cfg)
    return 4 * heads * hd * cfg["mamba_d_state"]


def conv_state_bytes(cfg, bytes_per=2):
    """One row's convolution window in one layer."""
    return bytes_per * (cfg["mamba_d_conv"] - 1) * _mixer(cfg)[4]


def kv_bytes_per_token(cfg, bytes_per=2):
    """K and V of one token in one layer: the KEY/VALUE heads."""
    return 2 * bytes_per * cfg["num_key_value_heads"] * cfg["head_dim"]


def state_update_bytes(cfg, layers, rows):
    """Bytes the recurrent state's update has to move in one decode step:
    each decoding row's state read once and written once, every layer."""
    return 2 * rows * layers * ssm_state_bytes(cfg)


def decode_step_bytes(cfg, layers, live_tokens, rows=None, bytes_per=2):
    """Bytes one decode step has to move: every layer's weights and the
    head once, K and V of every live token in every layer, and the
    recurrent and convolution state of each of the ``rows`` that decode,
    read and written."""
    if rows is None:
        raise TypeError("decode_step_bytes of a family with per-row state "
                        "needs rows")
    weights = (layers * layer_parameters(cfg) + head_parameters(cfg)) \
        * bytes_per
    kv = layers * live_tokens * kv_bytes_per_token(cfg, bytes_per)
    state = 2 * rows * layers * (ssm_state_bytes(cfg)
                                 + conv_state_bytes(cfg, bytes_per))
    return weights + kv + state


def prefill_scan(cfg, tokens, bytes_per=2):
    """(flops, bytes) of one layer's chunked scan over ``tokens``
    positions (a multiple of the chunk): per chunk and group the C B^T
    scores, per chunk and head the masked product with dt x (both over
    the lower triangle), what each chunk adds to the state and what the
    carried state adds to y; reads x, dt, B, C once, writes y once and
    the final state once."""
    heads, hd, d_ssm, gn, _ = _mixer(cfg)
    n, chunk = cfg["mamba_d_state"], cfg["mamba_chunk_size"]
    groups = cfg["mamba_n_groups"]
    flops = tokens * chunk * n * groups           # scores, half the square
    flops += tokens * chunk * hd * heads          # within a chunk, half
    flops += 2 * 2 * tokens * heads * hd * n      # state in, state out
    nbytes = bytes_per * tokens * (2 * d_ssm + 2 * gn) + 4 * tokens * heads \
        + ssm_state_bytes(cfg)
    return flops, nbytes
