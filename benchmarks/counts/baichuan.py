"""Required operations and bytes for the Baichuan-7B block, from shapes.

"Required" is what the algorithm needs, not what a kernel happens to do:
causal attention is counted once (half the square), recomputation is not
counted, and a decode step has to read every weight once and the K/V of
the tokens that are live, no more.
"""


def layer_parameters(cfg):
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    return 4 * d * d + 3 * d * f  # qkv + out, gate + up + down


def head_parameters(cfg):
    return cfg["hidden_size"] * cfg["vocab_size"]


def train_flops_per_item(cfg, layers, traffic):
    """FLOPs one trained token requires: 6 per matmul parameter (forward
    and backward) plus causal attention, 6 L s d (two s x s x d matmuls a
    layer, 2 FLOPs a multiply-add, three passes, half the square)."""
    s, d = traffic["seq_len"], cfg["hidden_size"]
    p = layers * layer_parameters(cfg) + head_parameters(cfg)
    return 6 * p + 6 * layers * s * d


def flash_forward(bh, s, dh, bytes_per=2):
    """(flops, bytes) of causal attention forward on [bh, s, dh]: QK^T and
    PV over half the square; reads q, k, v and writes o."""
    return 2 * bh * s * s * dh, 4 * bh * s * dh * bytes_per


def flash_backward(bh, s, dh, bytes_per=2):
    """(flops, bytes) of its backward: the four gradient matmuls and the
    one recomputation of the scores that not storing them requires, over
    half the square; reads q, k, v, o, do and writes dq, dk, dv."""
    return 5 * bh * s * s * dh, 8 * bh * s * dh * bytes_per


def decode_step_bytes(cfg, layers, live_tokens, rows=None, bytes_per=2):
    """Bytes one decode step has to read: every layer's weights and the
    head once, and K and V of every live token in every layer.  ``rows``
    (how many rows decode) is ignored: this block keeps no per-row state
    beyond K/V; a family with recurrent or convolution state counts its
    read and write per row here."""
    weights = (layers * layer_parameters(cfg) + head_parameters(cfg))
    kv = 2 * layers * live_tokens * cfg["hidden_size"]
    return (weights + kv) * bytes_per
