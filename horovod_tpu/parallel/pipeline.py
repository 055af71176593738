"""Pipeline parallelism: GPipe-style microbatch schedule over the 'pp' mesh
axis.

The reference implements no pipeline parallelism (SURVEY.md §2.6: data
parallelism only); this is a capability extension the task spec makes
first-class. The design is TPU-idiomatic rather than a port of any
GPU/NCCL send/recv scheme:

  * Stages are pp-mesh shards inside ``shard_map``: every rank runs the SAME
    compiled SPMD program; "send to next stage" is ``lax.ppermute`` over ICI
    (a neighbour hop on the torus — the cheapest possible collective).
  * The schedule is a ``lax.scan`` over M + P - 1 ticks (M microbatches,
    P stages): compiler-friendly static control flow, no per-step host
    involvement, fully differentiable (ppermute's transpose is the reverse
    permute, so jax.grad derives the backward pipeline automatically).
  * Bubble ticks compute on garbage activations; their outputs are never
    read, so their gradients are exactly zero and correctness is unaffected
    — the standard GPipe trade (bubble fraction (P-1)/(M+P-1)).

``gpipe`` is the generic primitive; ``make_pipeline_step`` builds a full
dp × pp training step for the flagship transformer (models/transformer.py),
with layer stacks sharded over 'pp' and embedding/head/final-norm replicated
(their gradients are pp-summed — each is only *used* on one stage, so the
sum recovers the true gradient).
"""

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P



def gpipe(stage_fn, microbatches, axis_name="pp"):
    """Run ``stage_fn`` as one stage of a GPipe pipeline. Must be called
    inside ``shard_map`` with ``axis_name`` bound.

    Args:
      stage_fn: activation -> activation, this rank's stage (same output
        shape/dtype as input — homogeneous-block pipelines; put embed/head
        outside the pipeline).
      microbatches: [M, ...] stacked microbatch activations, replicated
        across the pp axis (only stage 0 reads them).
      axis_name: the pipeline mesh axis.

    Returns:
      [M, ...] outputs, valid on the LAST stage (zeros elsewhere); use
      ``last_stage_value`` to broadcast results to every stage.
    """
    stage = lax.axis_index(axis_name)
    n_stages = lax.axis_size(axis_name)
    num_micro = microbatches.shape[0]
    ticks = num_micro + n_stages - 1

    # the carry becomes device-varying over pp after the first ppermute /
    # stage-masked write; mark it varying from the start so the scan's
    # carry type is stable (no-op when the activations already vary, e.g.
    # when the embedding params were cast varying for the backward pass)
    from ..ops.collective_ops import ensure_varying
    state = ensure_varying(jnp.zeros_like(microbatches[0]), (axis_name,))
    outputs = ensure_varying(jnp.zeros_like(microbatches), (axis_name,))

    def tick(carry, t):
        state, outputs = carry
        inject = microbatches[jnp.clip(t, 0, num_micro - 1)]
        x_in = jnp.where(stage == 0, inject, state)
        y = stage_fn(x_in)
        out_idx = jnp.clip(t - (n_stages - 1), 0, num_micro - 1)
        take = jnp.logical_and(t >= n_stages - 1, stage == n_stages - 1)
        outputs = jnp.where(take, outputs.at[out_idx].set(y), outputs)
        # neighbour hop: stage i's output becomes stage i+1's next input
        state = lax.ppermute(y, axis_name,
                             [(i, i + 1) for i in range(n_stages - 1)])
        return (state, outputs), None

    (_, outputs), _ = lax.scan(tick, (state, outputs), jnp.arange(ticks))
    return outputs


def last_stage_value(x, axis_name="pp"):
    """Broadcast a value computed on the last pipeline stage to all stages
    (masked psum — lowers to a one-to-all over ICI)."""
    stage = lax.axis_index(axis_name)
    n_stages = lax.axis_size(axis_name)
    return lax.psum(jnp.where(stage == n_stages - 1, x, jnp.zeros_like(x)),
                    axis_name)


# ---------------------------------------------------------------------------
# Transformer pipeline step (dp × pp)
# ---------------------------------------------------------------------------

def stack_pipeline_params(params, num_layers):
    """Convert TransformerLM params ({'layer_0'..'layer_{L-1}', 'embed',
    'ln_f', 'lm_head'}) into pipeline layout: {'layers': stacked-[L, ...],
    'embed', 'ln_f', 'lm_head'}. The stacked leading axis shards over 'pp'."""
    layers = [params[f"layer_{i}"] for i in range(num_layers)]
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *layers)
    rest = {k: v for k, v in params.items() if not k.startswith("layer_")}
    return {"layers": stacked, **rest}


def unstack_pipeline_params(pparams, num_layers):
    """Inverse of stack_pipeline_params (e.g. for checkpointing in the
    canonical layout)."""
    out = {k: v for k, v in pparams.items() if k != "layers"}
    for i in range(num_layers):
        out[f"layer_{i}"] = jax.tree_util.tree_map(
            lambda x, i=i: x[i], pparams["layers"])
    return out


# Megatron-style TP rules for the STACKED layer layout: the leading dim
# is the pp-sharded layer axis, then models/transformer.py's _TP_RULES
# shifted right by one (column-parallel qkv/gate/up, row-parallel
# out/down).
_STACKED_TP_RULES = (
    (("attn", "qkv", "kernel"), P("pp", None, "tp")),
    (("attn", "out", "kernel"), P("pp", "tp", None)),
    (("mlp", "gate", "kernel"), P("pp", None, "tp")),
    (("mlp", "up", "kernel"), P("pp", None, "tp")),
    (("mlp", "down", "kernel"), P("pp", "tp", None)),
)


def pipeline_param_specs(pparams, tp=False):
    """PartitionSpecs for the pipeline layout: layer stack sharded over
    'pp' on the leading axis, everything else replicated.

    ``tp=True`` additionally shards the stacked layer kernels and the
    lm_head over the 'tp' mesh axis (Megatron column/row parallelism,
    same rules as models.transformer.param_specs) — the placement side
    of the combined dp x pp x tp step (make_pipeline_step leaves 'tp'
    out of shard_map's manual axes, so GSPMD inserts the tp
    collectives)."""
    def spec(path, leaf):
        names = tuple(str(getattr(p, "key", getattr(p, "name", p)))
                      for p in path)
        if names[0] == "layers":
            if tp:
                for suffix, s in _STACKED_TP_RULES:
                    if names[-len(suffix):] == suffix:
                        return s
            return P("pp")
        if tp and names[-2:] == ("lm_head", "kernel"):
            return P(None, "tp")          # vocab-sharded head
        return P()
    return jax.tree_util.tree_map_with_path(spec, pparams)


def make_pipeline_step(cfg, tx, mesh, num_microbatches, pparams,
                       dp_axis="dp", pp_axis="pp", tp_axis="tp",
                       sp_axis="sp"):
    """Build a jitted dp × pp (× tp) training step for TransformerLM.

    The layer stack is split over ``pp_axis`` (layers_per_stage =
    num_layers / pp); the batch over ``dp_axis``; microbatches flow through
    stages via the gpipe schedule. Gradients: dp-mean over ``dp_axis`` for
    everything (the DistributedOptimizer role, done explicitly here because
    replicated-vs-stacked params need different pp treatment), plus pp-sum
    for the replicated embed/head/norm params, which only one stage touches.

    Tensor parallelism composes automatically: when the mesh carries a
    ``tp_axis`` with more than one way, the pipeline's shard_map is
    manual over (dp, pp) ONLY — 'tp' stays a GSPMD axis, the returned
    shardings place the stacked kernels Megatron-style
    (pipeline_param_specs(tp=True)), and XLA inserts the tp all-reduces
    inside each stage. Manual code never mentions tp, so the same step
    serves dp×pp and dp×pp×tp meshes.

    Sequence parallelism composes when the mesh carries ``sp_axis`` > 1
    AND ``cfg.attention_impl`` can attend across sequence shards
    ('ring'/'ring_flash'/'ulysses'): tokens arrive sp-REPLICATED, each
    sp member slices its global-position sequence chunk after the shift
    (so the label shift never straddles a shard boundary), attention
    runs blockwise over the sp ring inside every pipeline stage, and
    gradients/loss are sp-means. With attention_impl='full' an sp>1
    mesh axis is simply left replicated (the pre-round-4 behavior).

    Args: ``pparams`` is the stacked layout from ``stack_pipeline_params``
    (used for shape/spec inference — pass the actual params or shapes).

    Returns (step, pparam_shardings, batch_sharding); step(pparams,
    opt_state, tokens[b, S+1]) -> (pparams, opt_state, loss).
    """
    from ..models.transformer import Block
    from .. import trainer as trainer_mod
    import flax.linen as nn

    pp = mesh.shape[pp_axis]
    dp = mesh.shape[dp_axis]
    if cfg.num_layers % pp:
        raise ValueError(
            f"num_layers={cfg.num_layers} not divisible by pp={pp}")
    if cfg.num_experts > 0:
        raise NotImplementedError(
            "make_pipeline_step does not yet thread the MoE aux loss "
            "through the pipeline (the sown 'losses' collection would be "
            "silently dropped inside lax.scan); use make_gspmd_step with "
            "models.transformer.lm_loss_fn for MoE configs.")
    if cfg.tie_embeddings:
        raise NotImplementedError(
            "make_pipeline_step does not support tie_embeddings: the "
            "embedding lives on the first stage and the head on the "
            "last, so tying needs a cross-stage weight exchange; use "
            "make_gspmd_step, or an untied config, for pipeline "
            "parallelism.")
    sp = mesh.shape.get(sp_axis, 1)
    sp_active = sp > 1 and cfg.attention_impl in ("ring", "ring_flash",
                                                  "ulysses")
    # single source for shard_map's manual axes AND ensure_varying's —
    # desynchronizing them would corrupt gradient scaling
    manual_axes = ((dp_axis, pp_axis, sp_axis) if sp_active
                   else (dp_axis, pp_axis))
    block = Block(cfg, sp=sp_axis if sp_active else None)
    ln_f = nn.RMSNorm(dtype=cfg.dtype)

    def per_rank_loss(pparams, tokens):
        # tokens: [b_loc, S+1] — inputs + shifted targets. Under sp the
        # array is sp-replicated; the GLOBAL shift happens here, then
        # each sp member takes its sequence chunk (a shard-local shift
        # would pair the wrong tokens at every shard boundary).
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        b_loc, s = inputs.shape
        if b_loc % num_microbatches:
            raise ValueError(
                f"local batch {b_loc} not divisible by "
                f"num_microbatches={num_microbatches}")
        if sp_active:
            if s % sp:
                raise ValueError(
                    f"sequence length {s} not divisible by sp={sp}")
            s = s // sp
            start = lax.axis_index(sp_axis) * s
            inputs = lax.dynamic_slice_in_dim(inputs, start, s, axis=1)
            targets = lax.dynamic_slice_in_dim(targets, start, s, axis=1)
            positions = (start + jnp.arange(s))[None, :]
        else:
            positions = jnp.arange(s)[None, :]
        x = pparams["embed"]["embedding"][inputs].astype(cfg.dtype)
        mb = b_loc // num_microbatches
        x = x.reshape(num_microbatches, mb, s, cfg.d_model)

        def stage_fn(act):
            def body(a, layer_params):
                return block.apply({"params": layer_params}, a,
                                   positions), None
            act, _ = lax.scan(body, act, pparams["layers"])
            return act

        y = gpipe(stage_fn, x, axis_name=pp_axis)  # valid on last stage
        y = y.reshape(b_loc, s, cfg.d_model)
        y = ln_f.apply({"params": pparams["ln_f"]}, y)
        logits = (y @ pparams["lm_head"]["kernel"].astype(cfg.dtype)
                  ).astype(jnp.float32)
        loss = trainer_mod.softmax_cross_entropy(logits, targets)
        # only the last stage computed a real loss; share it
        return last_stage_value(loss, pp_axis)

    import optax

    def step(pparams, opt_state, tokens):
        # Backward pass on a device-varying copy so grads come out truly
        # per-device (see ops.collective_ops.ensure_varying): otherwise
        # shard_map's autodiff pre-sums the cotangents over every axis a
        # param is replicated on, and the explicit psums below keep (or
        # re-multiply) those sums — dp× on the layer stack, dp·pp× on the
        # replicated embed/head/norm.
        from ..ops.collective_ops import ensure_varying
        vpparams = jax.tree_util.tree_map(
            lambda p: ensure_varying(p, manual_axes), pparams)
        loss, grads = jax.value_and_grad(per_rank_loss)(vpparams, tokens)
        # ONE fused reduction: dp-average, and under sp also sp-average
        # (each sp member saw 1/sp of the tokens, so the global token
        # mean is the mean of the local means); pp-sum below for the
        # replicated (non-stacked) params — each is used on exactly one
        # stage, so the sum is the true grad.
        red_axes = (dp_axis, sp_axis) if sp_active else (dp_axis,)
        red_ways = dp * (sp if sp_active else 1)
        grads = jax.tree_util.tree_map(
            lambda g: lax.psum(g, red_axes) / red_ways, grads)
        grads = {k: (v if k == "layers" else
                     jax.tree_util.tree_map(
                         lambda g: lax.psum(g, pp_axis), v))
                 for k, v in grads.items()}
        updates, opt_state = tx.update(grads, opt_state, pparams)
        pparams = optax.apply_updates(pparams, updates)
        return pparams, opt_state, lax.pmean(loss, red_axes)

    tp = mesh.shape.get(tp_axis, 1)
    # shard_map is manual over (dp, pp) only; its specs must not name
    # the GSPMD axes, so the manual tree stays pp-only even when tp > 1
    param_specs_tree = pipeline_param_specs(pparams)
    opt_specs = trainer_mod.opt_state_specs(tx, pparams, param_specs_tree)
    batch_spec = P(dp_axis, None)
    fn = jax.jit(jax.shard_map(
        step, mesh=mesh, axis_names=frozenset(manual_axes),
        in_specs=(param_specs_tree, opt_specs, batch_spec),
        out_specs=(param_specs_tree, opt_specs, P())))

    # placement shardings DO carry tp: GSPMD propagates them through the
    # manual region and inserts the Megatron collectives
    place_specs = pipeline_param_specs(pparams, tp=tp > 1)

    def shardings(spec_tree):
        return jax.tree_util.tree_map(
            lambda s: jax.sharding.NamedSharding(mesh, s), spec_tree,
            is_leaf=lambda s: isinstance(s, P))

    return fn, shardings(place_specs), \
        jax.sharding.NamedSharding(mesh, batch_spec)
