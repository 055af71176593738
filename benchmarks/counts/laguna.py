"""Required operations and bytes for the Laguna decoder (window and full
attention layers with different head counts, a per-head gate, 256 narrow
dropless experts), from shapes.

"Required" is what the algorithm needs, not what a program happens to do.
A decode step has to read: every weight that every row uses (the
attentions' projections and gates, the routers, the shared experts, the
dense first layer, the head), the weights of the experts that at least one
decoding row was routed to, K and V of every live token in every FULL
layer, and in every WINDOW layer K and V of the last ``sliding_window``
tokens of each row and no more: a program that keeps, or reads, a window
layer's older tokens reads more than this counts.  The banded prefill
forward has to multiply each query with the keys its band holds,
``min(i + 1, window)`` of them, and no tile's worth more.

Which experts a step touches is decided by the data.  ``decode_step_bytes``
is handed the mean rows and live tokens and nothing else
(``readers/decode_roofline.py``), so it counts the EXPECTED number of
touched experts under uniform routing, as ``counts/glm_moe_lite.py`` does:
``E (1 - (1 - k/E)^rows)``, 222.1 of 256 at 64 rows (86.8%).  For the same
reason a window layer's live tokens are ``rows x min(live / rows,
window)``: the sum of ``min(length, window)`` over the rows is at most
that (the minimum is concave), and falls short of it only by the rows that
are shorter than the window while others are longer: under 0.5% of a step
in the cell this was written for, where a sixth of the rows are.
``moe256.expert_roofline`` is the one that takes the traced window's own
count (``expert_bytes``).
"""

BYTES = 2  # a served parameter, a cached number: bfloat16


def sparse(cfg, i):
    return cfg["mlp_layer_types"][i] == "sparse"


def window_layer(cfg, i):
    return cfg["layer_types"][i] == "sliding_attention"


def stack_shapes(cfg):
    """The shapes of a layer's stacks of experts, as an instruction that
    reads one names them (``readers/stack_roofline.py``)."""
    e, d = cfg["num_experts"], cfg["hidden_size"]
    f = cfg["moe_intermediate_size"]
    return {(e, d, f), (e, f, d)}


def expert_parameters(cfg):
    """One routed expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def attention_parameters(cfg, i):
    """Layer ``i``'s attention: q of its own head count, k and v of the
    key/value heads, the per-head gate, the output projection."""
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    h = cfg["num_attention_heads_per_layer"][i]
    return d * h * dh + 2 * d * cfg["num_key_value_heads"] * dh + d * h \
        + h * dh * d


def fixed_parameters(cfg, i):
    """What every row uses of layer ``i``: attention, two norms, and the
    dense SwiGLU or the router and the shared expert."""
    d = cfg["hidden_size"]
    fixed = attention_parameters(cfg, i) + 2 * d
    if sparse(cfg, i):
        return fixed + d * cfg["num_experts"] \
            + 3 * d * cfg["shared_expert_intermediate_size"]
    return fixed + 3 * d * cfg["intermediate_size"]


def expert_layers(cfg, layers):
    return sum(sparse(cfg, i) for i in range(layers))


def head_parameters(cfg):
    """The final norm and the head (the embedding is gathered by row)."""
    return cfg["hidden_size"] * (cfg["vocab_size"] + 1)


def expected_touched(cfg, rows):
    """Experts of ONE layer that at least one of ``rows`` tokens is routed
    to, under uniform routing."""
    e, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    return e * (1.0 - (1.0 - k / e) ** rows)


def expert_bytes(cfg, touched):
    """Bytes of ``touched`` (layer, expert) pairs' weights."""
    return touched * expert_parameters(cfg) * BYTES


def kv_bytes_per_token(cfg):
    """K and V of one token in one plane."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * BYTES


def planes(cfg, layers):
    """(full layers, window layers) among the first ``layers``."""
    window = sum(window_layer(cfg, i) for i in range(layers))
    return layers - window, window


def window_tokens(cfg, live_tokens, rows):
    """Tokens a window layer has to read at ``rows`` decoding rows that
    hold ``live_tokens`` between them: see the module's docstring."""
    if not rows:
        return 0.0
    return rows * min(live_tokens / rows, cfg["sliding_window"])


def decode_attention_bytes(cfg, layers, live_tokens, rows):
    """Bytes decode attention has to read in one step: K and V of every
    live token in every full plane, and of each row's last
    ``sliding_window`` tokens in every window plane."""
    full, window = planes(cfg, layers)
    return kv_bytes_per_token(cfg) * (
        full * live_tokens + window * window_tokens(cfg, live_tokens, rows))


def decode_step_bytes(cfg, layers, live_tokens, rows=None):
    """Bytes one decode step has to read at ``rows`` decoding rows (every
    expert, and every live token in every plane, where the caller does
    not say)."""
    moe = expert_layers(cfg, layers)
    touched = cfg["num_experts"] if rows is None \
        else expected_touched(cfg, rows)
    fixed = sum(fixed_parameters(cfg, i) for i in range(layers)) \
        + head_parameters(cfg)
    if rows is None:
        attention = layers * live_tokens * kv_bytes_per_token(cfg)
    else:
        attention = decode_attention_bytes(cfg, layers, live_tokens, rows)
    return fixed * BYTES + expert_bytes(cfg, moe * touched) + attention


def band_pairs(s, window):
    """(query, key) pairs of a sequence of ``s`` under the band ``0 <= i -
    j < window``: ``min(i + 1, window)`` keys a query."""
    w = min(s, window)
    return w * (w + 1) // 2 + (s - w) * w


def band_tiles(s, block, window):
    """(q tile, k tile) pairs of ``block x block`` that hold at least one
    pair of the band: what a tiled kernel has to visit at the least."""
    tiles = 0
    for qi in range(-(-s // block)):
        first = max((qi * block - window + 1) // block, 0)
        tiles += qi - first + 1
    return tiles


def band_forward(bh, bhk, s, dh, window, bytes_per=BYTES):
    """(flops, bytes) of the banded attention forward on q ``[bh, s, dh]``
    over k, v ``[bhk, s, dh]``: QK^T and PV over the band's pairs; reads
    q, k, v and writes o."""
    return 4 * bh * band_pairs(s, window) * dh, \
        2 * (bh + bhk) * s * dh * bytes_per
