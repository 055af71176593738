"""Required operations and bytes for the GLM-4.7-Flash decoder (latent
attention, dropless experts), from shapes.

"Required" is what the algorithm needs, not what a program happens to do.
A decode step has to read: every weight that every row uses (attention's
projections, the routers, the shared experts, the dense first layer, the
head), the weights of the experts that at least one decoding row was
routed to, and the latent of every live token, ONCE: the latent is key and
value at once, 576 numbers a token a plane, and a program that streams it
twice, or that streams the 640 lanes the chip's layout pads a row to,
reads more than this counts.

Which experts a step touches is decided by the data.  ``decode_step_bytes``
is handed the mean rows and live tokens and nothing else
(``readers/decode_roofline.py``), so it counts the EXPECTED number of
touched experts under uniform routing: of E experts, a layer's ``rows x k``
assignments (k distinct experts a row) leave one untouched with probability
``(1 - k/E)^rows``, i.e. ``E (1 - (1 - k/E)^rows)`` touched: 63.0 of 64
at 64 rows, 98.4%.  (ISSUE 42 wrote ``(63/64)^(4 rows)``, assignments
drawn one by one with replacement: 98.2%; a row's four are distinct.)  An
expectation and not the trace's count because a count from shapes is the
same for every program, and a program that routes wrongly cannot lower its
own bar.  Seeded routing that is less even than uniform touches fewer
experts than this counts, and ``model.decode_roofline`` then over-reads by
as much: ``moe.experts_touched_share`` stands beside it.
``moe.expert_roofline`` is the one that takes the traced window's own
count (``expert_bytes``).
"""

BYTES = 2  # a served parameter, a cached number: bfloat16


def expert_parameters(cfg):
    """One routed expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def attention_parameters(cfg):
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    v = cfg["v_head_dim"]
    return (d * rq + rq + rq * h * (nope + rope) + d * (rkv + rope) + rkv
            + rkv * h * (nope + v) + h * v * d)


def dense_layer_parameters(cfg):
    """A leading dense layer: attention, the SwiGLU, two norms."""
    d = cfg["hidden_size"]
    return attention_parameters(cfg) + 3 * d * cfg["intermediate_size"] \
        + 2 * d


def expert_layer_fixed_parameters(cfg):
    """What every row uses of an expert layer: attention, the router and
    its bias, the shared experts, two norms."""
    d, e = cfg["hidden_size"], cfg["n_routed_experts"]
    return attention_parameters(cfg) + d * e + e \
        + cfg["n_shared_experts"] * expert_parameters(cfg) + 2 * d


def expert_layers(cfg, layers):
    return max(layers - cfg["first_k_dense_replace"], 0)


def head_parameters(cfg):
    """The final norm and the head (the embedding is gathered by row)."""
    return cfg["hidden_size"] * (cfg["vocab_size"] + 1)


def expected_touched(cfg, rows):
    """Experts of ONE layer that at least one of ``rows`` decoding rows is
    routed to, under uniform routing."""
    e, k = cfg["n_routed_experts"], cfg["num_experts_per_tok"]
    return e * (1.0 - (1.0 - k / e) ** rows)


def expert_bytes(cfg, touched):
    """Bytes of ``touched`` (layer, expert) pairs' weights."""
    return touched * expert_parameters(cfg) * BYTES


def latent_bytes_per_token(cfg, layers):
    """What one token leaves in the cache over all planes: c and k_r."""
    return layers * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * BYTES


def decode_attention_bytes(cfg, layers, live_tokens):
    """Bytes decode attention has to read in one step: the latent of every
    live token in every plane, ONE read (it is key and value)."""
    return live_tokens * latent_bytes_per_token(cfg, layers)


def decode_step_bytes(cfg, layers, live_tokens, rows=None):
    """Bytes one decode step has to read at ``rows`` decoding rows (every
    expert where the caller does not say)."""
    dense = min(layers, cfg["first_k_dense_replace"])
    moe = expert_layers(cfg, layers)
    touched = cfg["n_routed_experts"] if rows is None \
        else expected_touched(cfg, rows)
    fixed = dense * dense_layer_parameters(cfg) \
        + moe * expert_layer_fixed_parameters(cfg) + head_parameters(cfg)
    return fixed * BYTES + expert_bytes(cfg, moe * touched) \
        + decode_attention_bytes(cfg, layers, live_tokens)
