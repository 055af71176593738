"""Mixture-of-Experts layer with expert parallelism over the 'ep' mesh axis.

The reference has no MoE/expert parallelism (SURVEY.md §2.6); this is a
capability extension the task spec makes first-class. TPU-first design is
the GShard/Switch pattern, not a per-device gather/scatter runtime:

  * Routing, dispatch and combine are dense einsums over one-hot
    capacity-limited masks — static shapes, jit-clean, MXU-friendly.
  * Expert weights are stacked [E, ...] and sharded over 'ep' via
    PartitionSpecs; under GSPMD jit, XLA inserts the all-to-alls that move
    token slots to their expert's shard and back (the ICI-native analogue
    of an MoE all_to_all dispatch layer).
  * Over-capacity tokens are dropped (their combine weight is zero) — the
    standard capacity-factor trade that keeps shapes static for XLA.
  * A Switch-style load-balance auxiliary loss is exposed via
    ``sow('losses', 'moe_aux_loss', ...)``; training steps can pull it from
    the mutable collection and add ``aux_weight *`` it to the task loss.

Serving has another contract, and another layer for it at the end of this
module: ``route`` and ``experts``, plain functions over parameter leaves
(models/latent_moe.py and models/window_moe.py call them in their prefill
and their decode). That layer is DROPLESS: there is no capacity, every
token gets every expert it chose, and an expert that no token chose is not
computed and its weights are not read (tokens sorted by expert, one
grouped product over the experts routed to: ops/grouped_matmul.py's kernel
on one TPU chip at ANY number of assignments, the sorted rows resident in
VMEM where they fit and streamed through it from HBM where they do not,
which the kernel decides from the shape; ``jax.lax.ragged_dot``
elsewhere). ``MoEMLP`` above stays what the training path runs.
"""

from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops import grouped_matmul


class MoEMLP(nn.Module):
    """Gated (SwiGLU) expert FFN with top-k routing and fixed capacity.

    Drop-in replacement for models.transformer.MLP when
    cfg.num_experts > 0.
    """
    cfg: object  # TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        E = cfg.num_experts
        k = cfg.num_experts_per_tok
        b, s, d = x.shape
        # capacity per expert per batch row: factor × fair share
        capacity = max(1, int(cfg.expert_capacity_factor * s * k / E))

        # --- routing (fp32 for numerics) ---
        router_logits = nn.Dense(E, use_bias=False, dtype=jnp.float32,
                                 name="router")(x.astype(jnp.float32))
        probs = jax.nn.softmax(router_logits, axis=-1)      # [b, s, E]
        gate_vals, gate_idx = jax.lax.top_k(probs, k)       # [b, s, k]
        gate_vals = gate_vals / jnp.clip(
            gate_vals.sum(-1, keepdims=True), 1e-9)         # renormalize

        # --- capacity assignment: sequential priority over the k slots ---
        # position_in_expert for slot j counts tokens of slots 0..j to keep
        # slot-0 (highest gate) tokens first in line for capacity.
        combine = jnp.zeros((b, s, E, capacity), jnp.float32)
        prev_counts = jnp.zeros((b, 1, E), jnp.int32)  # tokens already taken
        for j in range(k):
            mask_j = jax.nn.one_hot(gate_idx[..., j], E,
                                    dtype=jnp.int32)        # [b, s, E]
            pos_j = (jnp.cumsum(mask_j, axis=1) - mask_j
                     + prev_counts) * mask_j                # [b, s, E]
            prev_counts = prev_counts + mask_j.sum(
                axis=1, keepdims=True)
            within = (pos_j < capacity) & (mask_j > 0)
            pos_oh = jax.nn.one_hot(pos_j, capacity,
                                    dtype=jnp.float32)      # [b, s, E, C]
            combine = combine + (gate_vals[..., j][..., None, None]
                                 * within[..., None] * pos_oh)
        dispatch = (combine > 0).astype(cfg.dtype)          # [b, s, E, C]

        # --- load-balance aux loss (Switch: E * Σ_e f_e · P_e) ---
        token_frac = jnp.mean(
            jax.nn.one_hot(gate_idx[..., 0], E, dtype=jnp.float32),
            axis=(0, 1))
        prob_frac = jnp.mean(probs, axis=(0, 1))
        self.sow("losses", "moe_aux_loss",
                 E * jnp.sum(token_frac * prob_frac))

        # --- dispatch → expert FFN → combine (XLA shards E over 'ep') ---
        xd = x.astype(cfg.dtype)
        expert_in = jnp.einsum("bsec,bsm->ebcm", dispatch, xd)
        w_gate = self.param("w_gate", nn.initializers.lecun_normal(),
                            (E, d, cfg.d_ff), jnp.float32).astype(cfg.dtype)
        w_up = self.param("w_up", nn.initializers.lecun_normal(),
                          (E, d, cfg.d_ff), jnp.float32).astype(cfg.dtype)
        w_down = self.param("w_down", nn.initializers.lecun_normal(),
                            (E, cfg.d_ff, d), jnp.float32).astype(cfg.dtype)
        h = (nn.silu(jnp.einsum("ebcm,emf->ebcf", expert_in, w_gate))
             * jnp.einsum("ebcm,emf->ebcf", expert_in, w_up))
        expert_out = jnp.einsum("ebcf,efm->ebcm", h, w_down)
        out = jnp.einsum("bsec,ebcm->bsm", combine.astype(cfg.dtype),
                         expert_out)
        return out.astype(cfg.dtype)


def aux_loss_from(mutables, weight=0.01):
    """Sum every sown moe_aux_loss in a mutable-collection dict (as returned
    by ``model.apply(..., mutable=['losses'])``), scaled by ``weight``."""
    total = 0.0
    losses = mutables.get("losses", {}) if mutables else {}
    for leaf in jax.tree_util.tree_leaves(losses):
        total = total + jnp.sum(leaf)
    return weight * total


# -- the dropless layer that serving runs ------------------------------------

def route(y, w_router, bias, k, scale=1.0, normalise=True):
    """Which ``k`` experts each token takes, and with what weight.

    y [..., d]; w_router [d, E]; bias [E] or None. Scores are sigmoids of
    float32 logits. ``bias`` SELECTS and never weighs (the aux-loss-free
    balancing of DeepSeek-V3's ``noaux_tc``): the top ``k`` of ``score +
    bias`` are taken, and their weights are the scores alone, divided by
    their sum if ``normalise``, times ``scale``.

    Returns (idx [..., k] int32, weights [..., k] float32)."""
    logits = jnp.dot(y, w_router.astype(y.dtype),
                     preferred_element_type=jnp.float32)
    scores = jax.nn.sigmoid(logits)
    chosen = scores if bias is None else scores + bias.astype(jnp.float32)
    _, idx = jax.lax.top_k(chosen, k)
    weights = jnp.take_along_axis(scores, idx, axis=-1)
    if normalise:
        weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), weights * scale


def experts(y, idx, weights, gate, up, down, mask=None):
    """``sum_j weights[t, j] * Expert_idx[t, j](y[t])`` for every token, no
    token dropped: there is no capacity.

    y [t, d]; idx, weights [t, k] (``route``); gate, up [E, d, f] and down
    [E, f, d], the experts' SwiGLU stacked. The ``t * k`` assignments are
    sorted by expert, so each expert's tokens lie together, and the three
    products are GROUPED (group e is expert e's rows against expert e's
    matrix): an expert with no row costs no product and no read of its
    weights, and no expert is computed for a token under a mask. On one
    TPU chip, in bfloat16 at widths of whole lane tiles, that is ONE
    Mosaic kernel that streams each touched expert's three matrices once
    and keeps the hidden in VMEM, whatever the number of rows
    (ops/grouped_matmul.py, which decides from the call: ``selected``;
    the sorted rows are gathered with ``room`` behind them for its last
    window, and stay in HBM where VMEM does not hold them); everywhere
    else three ``jax.lax.ragged_dot``. The outputs go back to the tokens'
    order and are summed under their weights in float32.

    ``mask`` [t] bool: a token outside it (a slot that does not decode, a
    prompt's padding) is routed to NO expert (its assignments sort behind
    the last group, which a grouped product leaves alone) and gets zeros.

    Returns (out [t, d] in y's dtype, load [E] int32: the assignments each
    expert got)."""
    t, k = idx.shape
    num = gate.shape[0]
    flat = idx.reshape(t * k)
    if mask is not None:
        flat = jnp.where(jnp.repeat(mask, k), flat, num)
    order = jnp.argsort(flat, stable=True)
    load = jnp.sum(flat[:, None] == jnp.arange(num, dtype=flat.dtype),
                   axis=0, dtype=jnp.int32)
    source = order // k              # the token of each sorted assignment
    kernel = grouped_matmul.selected(t * k, gate.shape, y.dtype)
    if kernel:  # its windows are whole: room behind the last row for one
        source = jnp.pad(source, (0, grouped_matmul.room(t * k)))
    # every index is in bounds: "clip" spares the pass that selects a fill
    rows = jnp.take(y, source, axis=0, mode="clip")      # [t * k (+), d]
    if kernel:
        out = grouped_matmul.grouped_swiglu(rows, gate, up, down, load)
    else:
        hidden = jax.nn.silu(jax.lax.ragged_dot(rows, gate, load)) \
            * jax.lax.ragged_dot(rows, up, load)
        out = jax.lax.ragged_dot(hidden, down, load)     # [t * k, d]
    # back to the tokens' order: assignment a sits at row inverse[a]
    inverse = jnp.zeros_like(order).at[order].set(
        jnp.arange(t * k, dtype=order.dtype))
    out = jnp.take(out, inverse, axis=0, mode="clip").reshape(t, k, -1)
    if mask is not None:  # rows behind the last group hold what they held
        out = jnp.where(mask[:, None, None], out, 0)
    out = jnp.sum(out.astype(jnp.float32) * weights[..., None], axis=1)
    return out.astype(y.dtype), load
