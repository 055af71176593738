"""Required operations and bytes for the decoder-hybrid-decoder of
Phi-4-mini-flash (Mamba-1 and window differential attention, ONE full K/V
plane that the cross layers share, gated memory units), from shapes.

"Required" is what the algorithm needs, not what a program happens to do.
A decode step has to read: every weight once (the model is dense; the tied
embedding is the head); K and V of every live token of the ONE full plane
once for EACH layer that reads it (the full layer and the seven cross
layers run one after another, each with queries that depend on the layer
before: eight passes over one plane, which is held once); in every window
layer K and V of the last ``sliding_window`` tokens of each row and no
more; and the recurrent state of every row that decodes, read and written,
with the convolution's window.  A window layer's live tokens are ``rows x
min(live / rows, window)``, as ``counts/laguna.py`` counts them and for its
reason (the readers hand over means).

A prefill runs the self-decoder (layers 0 .. 17) over the padded prompt
and the cross-decoder (layers 18 .. 31) and the head over ONE token.
"""

BYTES = 2        # a served parameter, a cached number: bfloat16
STATE_BYTES = 4  # the recurrent state: float32


def sizes(cfg):
    a = cfg["assumed"]["mamba"]
    d = cfg["hidden_size"]
    return dict(d=d, f=cfg["intermediate_size"], v=cfg["vocab_size"],
                kv=cfg["num_key_value_heads"] * d
                // cfg["num_attention_heads"],
                di=a["expand"] * d, n=a["d_state"], k=a["d_conv"],
                r=a["dt_rank"])


def layer_kind(cfg, i):
    """The configuration's ``assumed.layer_plan``."""
    half, every = cfg["num_hidden_layers"] // 2, cfg["mb_per_layer"]
    if i <= half:
        return "mamba" if i % every == 0 else "window"
    if i == half + 1:
        return "full"
    return "gmu" if i % every == 0 else "cross"


def kinds(cfg, layers):
    return [layer_kind(cfg, i) for i in range(layers)]


def count(cfg, layers, kind):
    return kinds(cfg, layers).count(kind)


def plane_readers(cfg, layers):
    """Layers that read the full plane in a decode step."""
    return count(cfg, layers, "full") + count(cfg, layers, "cross")


def state_tail(cfg):
    """The recurrent state of one row and layer as the program holds it
    (state-major: ``readers/slab_update_roofline.py`` looks for float32
    arrays that end so)."""
    z = sizes(cfg)
    return (z["n"], z["di"])


def mixer_parameters(cfg, kind):
    z = sizes(cfg)
    d, di, head = z["d"], z["di"], z["d"] // cfg["num_attention_heads"]
    diff = 4 * head + 2 * head + d * d + d   # lambdas, norm, W_o and bias
    return {
        "mamba": d * 2 * di + z["k"] * di + di + di * (z["r"] + 2 * z["n"])
        + z["r"] * di + di + di * z["n"] + di + di * d,
        "gmu": 2 * d * di,
        "cross": d * d + d + diff,
        "window": d * (d + 2 * z["kv"]) + d + 2 * z["kv"] + diff,
        "full": d * (d + 2 * z["kv"]) + d + 2 * z["kv"] + diff}[kind]


def layer_parameters(cfg, i):
    """Layer ``i``: its mixer, the SwiGLU, two LayerNorms."""
    z = sizes(cfg)
    return mixer_parameters(cfg, layer_kind(cfg, i)) + 3 * z["d"] * z["f"] \
        + 4 * z["d"]


def parameters(cfg, layers):
    """The whole model: layers, the tied embedding, the final norm."""
    z = sizes(cfg)
    return sum(layer_parameters(cfg, i) for i in range(layers)) \
        + z["v"] * z["d"] + 2 * z["d"]


def kv_bytes_per_token(cfg):
    """K and V of one token in one plane."""
    return 2 * sizes(cfg)["kv"] * BYTES


def window_tokens(cfg, live_tokens, rows):
    if not rows:
        return 0.0
    return rows * min(live_tokens / rows, cfg["sliding_window"])


def shared_plane_bytes(cfg, layers, live_tokens):
    """Bytes of the ONE full plane a step has to read: every live token,
    once a reader."""
    return kv_bytes_per_token(cfg) * plane_readers(cfg, layers) * live_tokens


def decode_attention_bytes(cfg, layers, live_tokens, rows):
    """Bytes decode attention has to read in one step: the full plane once
    a reader, and each row's last ``sliding_window`` tokens in every
    window plane."""
    return shared_plane_bytes(cfg, layers, live_tokens) \
        + kv_bytes_per_token(cfg) * count(cfg, layers, "window") \
        * window_tokens(cfg, live_tokens, rows)


def state_update_bytes(cfg, layers, rows):
    """Bytes of recurrent state a decode step moves: each decoding row's
    state read and written in every Mamba layer (the convolution's window,
    under 2% of it, is left out)."""
    z = sizes(cfg)
    return 2 * rows * count(cfg, layers, "mamba") * z["n"] * z["di"] \
        * STATE_BYTES


def decode_step_bytes(cfg, layers, live_tokens, rows=None):
    """Bytes one decode step has to move at ``rows`` decoding rows (every
    live token in every plane, no state, where the caller does not say)."""
    weights = parameters(cfg, layers) * BYTES
    if rows is None:
        planes = plane_readers(cfg, layers) + count(cfg, layers, "window")
        return weights + planes * live_tokens * kv_bytes_per_token(cfg)
    return weights + decode_attention_bytes(cfg, layers, live_tokens, rows) \
        + state_update_bytes(cfg, layers, rows)


def prefill_flops(cfg, layers, tokens):
    """Matmul FLOPs of ONE prefill of ``tokens`` (padded) positions: the
    self-decoder's layers over all of them, the cross-decoder's and the
    head over one; attention's own products over the causal (or banded)
    pairs, two plain heads a differential head."""
    z = sizes(cfg)
    head = z["d"] // cfg["num_attention_heads"]
    heads = cfg["num_attention_heads"]
    w = min(tokens, cfg["sliding_window"])
    pairs = {"full": tokens * (tokens + 1) // 2,
             "window": w * (w + 1) // 2 + (tokens - w) * w,
             "cross": tokens}
    flops = 2 * z["v"] * z["d"]
    for i in range(layers):
        kind = layer_kind(cfg, i)
        extent = tokens if kind in ("mamba", "window", "full") else 1
        flops += 2 * extent * (layer_parameters(cfg, i) - 4 * z["d"])
        if kind in pairs:  # QK^T and PV: the pair's value is 2 heads wide
            flops += 2 * heads * pairs[kind] * (head + 2 * head)
    return flops
