"""The decoder with latent attention and dropless experts
(models/latent_moe.py, models/moe.py ``route`` / ``experts``) against the
plain reference the benchmark keeps (benchmarks/reference/glm_moe_lite.py),
at tiny sizes on seeded weights: 1 dense + 2 expert layers of hidden 64,
8 experts of which a token takes 2, one shared, 4 heads, ranks 16 / 32,
vocabulary 256.

Tolerances, and why each:
  F32_TOL 3e-4   the program computed in float32 against the float32
                 reference: the same arithmetic in another order (flax's
                 norm, a sorted grouped product against a loop over the
                 experts); measured 4e-6 at logits of standard deviation
                 1.0 (3 seeds). A router scored in bfloat16 moves a
                 routing weight by 2e-3 and fails ``ROUTE_TOL``.
  ROUTE_TOL 2e-5 the routing weights, float32 sigmoids of float32 logits
                 on both sides; measured 2e-7.
  BF16_TOL 0.15  the program as it is served (bfloat16 activations and
                 weights) against the float32 reference through 3 layers;
                 measured 0.025-0.045 (10 seeds) where the routing agrees.
  NEAR_TIE 0.04  routing is discrete: a token whose third-best expert lies
                 within bfloat16's error of its second may be routed
                 elsewhere than in float32. Over 10 seeds x 80 choices 10
                 differed, at margins (second over third of score + bias)
                 of 0.0000-0.0203; the test holds the two to the same
                 experts wherever the reference's margin is over twice
                 that, and compares the logits of the positions before the
                 first flip (a flip moves every later position).
Leaving out the shared expert, the selection bias, the normalisation, the
factor or the inner norms moves the logits by 0.05 and over
(``test_what_is_left_out_shows``), so none can hide inside F32_TOL.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import weights
from benchmarks.programs import glm_moe_lite as prog
from benchmarks.reference import glm_moe_lite as ref
from horovod_tpu.models import latent_moe, moe
from horovod_tpu.serving import decode as serve_decode

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F32_TOL, ROUTE_TOL, BF16_TOL, NEAR_TIE = 3e-4, 2e-5, 0.15, 0.04
LAYERS = 3


def published():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "glm-4.7-flash.json")) as f:
        return json.load(f)


def tiny_config(**kw):
    """The published configuration file with every width made tiny; the
    routing keys, the rotary base, eps and the dense first layer stay the
    published ones. ``expert_gain_log2`` 1: at 8 experts the stacks join
    the flat draw scaled by 1 / sqrt(8 x rows), and 2 is the power of two
    next to sqrt(8)."""
    cfg = published()
    cfg.update(hidden_size=64, intermediate_size=128, num_attention_heads=4,
               num_key_value_heads=4, q_lora_rank=16, kv_lora_rank=32,
               qk_nope_head_dim=24, qk_rope_head_dim=8, v_head_dim=32,
               moe_intermediate_size=32, n_routed_experts=8,
               num_experts_per_tok=2, vocab_size=256,
               max_position_embeddings=512)
    cfg["assumed"] = dict(cfg["assumed"], init=dict(
        cfg["assumed"]["init"], expert_gain_log2=1))
    cfg.update(kw)
    return cfg


def drawn(cfg, seed=5):
    shapes = ref.weight_shapes(cfg, LAYERS)
    return jax.jit(lambda k: weights.make(shapes, k, jnp.bfloat16))(
        weights.seed_key(seed))


def model(cfg, w, dtype, **overrides):
    mcfg = prog.latent_moe_config(cfg, LAYERS, dtype=dtype,
                                  attention_impl="full", **overrides)
    params = jax.jit(lambda w: prog.to_tree(w, LAYERS, cfg))(w)
    if dtype == jnp.float32:
        params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                        params)
    return mcfg, params


def sequence(n, seed=3):
    return np.random.default_rng(seed).integers(0, 256, n).astype(np.int32)


def reference(cfg, w, tokens):
    """(logits [s, vocab], idx [expert layers, s, k], weights, scores +
    bias [expert layers, s, E]) of the plain reference."""
    with jax.default_matmul_precision("highest"):
        toks = jnp.asarray(tokens)
        logits = ref.logits_at(w, toks, jnp.arange(len(tokens)), cfg, LAYERS)
        idx, wts, chosen = ref.routes_at(w, toks, cfg, LAYERS)
    return (np.asarray(logits), np.asarray(idx), np.asarray(wts),
            np.asarray(chosen))


def by_expert(idx, wts, experts):
    """[layers, s, k] choices -> [layers, s, E] weights, 0 where not
    chosen: the order inside the top k is no part of the contract."""
    dense = np.zeros(idx.shape[:2] + (experts,), np.float32)
    np.put_along_axis(dense, idx, wts, axis=-1)
    return dense


def program_routes(routing, s):
    idx = np.stack([np.asarray(r[0]).reshape(s, -1) for r in routing])
    wts = np.stack([np.asarray(r[1]).reshape(s, -1) for r in routing])
    return idx, wts


def test_the_plain_forward_is_the_reference_in_float32():
    """Logits, the experts every token chose and their weights."""
    cfg = tiny_config()
    w = drawn(cfg)
    tokens = sequence(40)
    mcfg, params = model(cfg, w, jnp.float32)
    logits, routing = latent_moe.forward(mcfg, params,
                                         jnp.asarray(tokens)[None])
    want, idx, wts, _ = reference(cfg, w, tokens)
    assert np.std(want) == pytest.approx(1.0, abs=0.25)
    assert len(routing) == 2 and idx.shape == (2, 40, 2)
    got_idx, got_wts = program_routes(routing, 40)
    np.testing.assert_array_equal(np.sort(got_idx, -1), np.sort(idx, -1))
    np.testing.assert_allclose(by_expert(got_idx, got_wts, 8),
                               by_expert(idx, wts, 8), atol=ROUTE_TOL)
    np.testing.assert_allclose(np.asarray(logits[0]), want, atol=F32_TOL)
    # every assignment is there: no capacity, nothing dropped
    for _, _, load in routing:
        assert int(load.sum()) == 40 * 2


@pytest.mark.parametrize("seed", [5, 6, 8])
def test_the_served_precision_is_near_the_reference(seed):
    """bfloat16 as served: the same experts wherever the choice is no near
    tie, and the logits up to the first position routed elsewhere."""
    cfg = tiny_config()
    w = drawn(cfg, seed)
    tokens = sequence(40, seed)
    mcfg, params = model(cfg, w, jnp.bfloat16)
    logits, routing = latent_moe.forward(mcfg, params,
                                         jnp.asarray(tokens)[None])
    want, idx, wts, chosen = reference(cfg, w, tokens)
    got_idx, _ = program_routes(routing, 40)
    ranked = np.sort(chosen, -1)
    margin = ranked[..., -2] - ranked[..., -3]     # second over third
    same = (np.sort(got_idx, -1) == np.sort(idx, -1)).all(-1)
    assert same[margin > NEAR_TIE].all()
    assert same.mean() > 0.9
    flipped = np.flatnonzero(~same.all(0))
    upto = flipped[0] if len(flipped) else 40
    assert upto >= 8
    np.testing.assert_allclose(np.asarray(logits[0, :upto], np.float32),
                               want[:upto], atol=BF16_TOL)


def _expert_inputs(t=24, d=64, f=32, e=8, seed=0):
    rng = np.random.default_rng(seed)
    y = jnp.asarray(rng.normal(size=(t, d)), jnp.float32)
    gate, up = (jnp.asarray(rng.normal(size=(e, d, f)) / d ** 0.5,
                            jnp.float32) for _ in range(2))
    down = jnp.asarray(rng.normal(size=(e, f, d)) / f ** 0.5, jnp.float32)
    return y, gate, up, down


def _loop_over_experts(y, idx, wts, gate, up, down):
    """The reference's way: every expert over every token under the
    token's weight for it."""
    out = np.zeros(y.shape, np.float32)
    for e in range(gate.shape[0]):
        share = np.where(np.asarray(idx) == e, np.asarray(wts), 0).sum(-1)
        piece = (jax.nn.silu(y @ gate[e]) * (y @ up[e])) @ down[e]
        out += share[:, None] * np.asarray(piece)
    return out


def test_every_token_to_one_expert_and_none_is_dropped():
    """There is no capacity: all 24 tokens of a batch choose expert 5 (and
    expert 2 second) and every one of them gets both, as the loop over the
    experts gives them; the six other experts get no row."""
    y, gate, up, down = _expert_inputs()
    idx = jnp.tile(jnp.asarray([[5, 2]], jnp.int32), (24, 1))
    wts = jnp.asarray(np.random.default_rng(1).uniform(0.2, 1.0, (24, 2)),
                      jnp.float32)
    with jax.default_matmul_precision("highest"):
        got, load = moe.experts(y, idx, wts, gate, up, down)
        want = _loop_over_experts(y, idx, wts, gate, up, down)
    assert load.tolist() == [0, 0, 24, 0, 0, 24, 0, 0]
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)
    assert np.abs(want).min(axis=1).max() > 0  # no token got zeros


def test_a_token_outside_the_mask_is_routed_to_no_expert():
    y, gate, up, down = _expert_inputs()
    rng = np.random.default_rng(2)
    idx = jnp.asarray(np.stack([rng.choice(8, 2, replace=False)
                                for _ in range(24)]), jnp.int32)
    wts = jnp.asarray(rng.uniform(0.2, 1.0, (24, 2)), jnp.float32)
    mask = np.ones(24, bool)
    mask[[3, 4, 17]] = False
    with jax.default_matmul_precision("highest"):
        got, load = moe.experts(y, idx, wts, gate, up, down,
                                jnp.asarray(mask))
        want = _loop_over_experts(y, idx, wts, gate, up, down)
    assert int(load.sum()) == 21 * 2
    assert load.tolist() == np.bincount(
        np.asarray(idx)[mask].ravel(), minlength=8).tolist()
    np.testing.assert_allclose(np.asarray(got)[mask], want[mask], atol=2e-5)
    assert not np.asarray(got)[~mask].any()


def test_the_bias_selects_and_never_weighs():
    """A selection bias that changes which experts are taken leaves the
    weights what the scores alone give: normalised over the chosen, times
    the factor."""
    rng = np.random.default_rng(4)
    y = jnp.asarray(rng.normal(size=(16, 64)), jnp.float32)
    w_router = jnp.asarray(rng.normal(size=(64, 8)) / 8, jnp.float32)
    bias = jnp.zeros(8).at[6].set(10.0)
    scores = np.asarray(jax.nn.sigmoid(y @ w_router))
    plain_idx, plain_w = moe.route(y, w_router, None, 2, 1.8)
    idx, wts = moe.route(y, w_router, bias, 2, 1.8)
    idx, wts = np.asarray(idx), np.asarray(wts)
    assert (idx == 6).any(-1).all()                # the bias chose
    assert not (np.asarray(plain_idx) == 6).any(-1).all()
    picked = np.take_along_axis(scores, idx, -1)
    np.testing.assert_allclose(
        wts, picked / picked.sum(-1, keepdims=True) * 1.8, atol=1e-6)
    np.testing.assert_allclose(wts.sum(-1), 1.8, atol=1e-5)
    # not normalised: the raw scores times the factor
    _, raw = moe.route(y, w_router, bias, 2, 1.8, normalise=False)
    np.testing.assert_allclose(np.asarray(raw), picked * 1.8, atol=1e-6)
    np.testing.assert_allclose(np.asarray(plain_w).sum(-1), 1.8, atol=1e-5)


@pytest.mark.parametrize("what", ["shared", "bias", "normalise", "factor",
                                  "inner_norms", "dense_first"])
def test_what_is_left_out_shows(what):
    cfg = tiny_config()
    w = drawn(cfg)
    tokens = sequence(40)
    want, _, _, _ = reference(cfg, w, tokens)
    overrides, w2 = {}, dict(w)
    if what == "shared":
        for i in (1, 2):
            w2[f"layers.{i}.shared.down"] = jnp.zeros_like(
                w[f"layers.{i}.shared.down"])
    elif what == "bias":
        for i in (1, 2):
            w2[f"layers.{i}.router.bias"] = jnp.zeros_like(
                w[f"layers.{i}.router.bias"])
    elif what == "normalise":
        overrides["route_normalise"] = False
    elif what == "factor":
        overrides["route_scale"] = 1.0
    elif what == "inner_norms":
        for i in range(LAYERS):
            for n in ("q_norm", "kv_norm"):
                name = f"layers.{i}.attn.{n}.scale"
                w2[name] = 2 * w[name]
    else:
        w2["layers.0.mlp.down"] = jnp.zeros_like(w["layers.0.mlp.down"])
    mcfg, params = model(cfg, w2, jnp.float32, **overrides)
    logits, _ = latent_moe.forward(mcfg, params, jnp.asarray(tokens)[None])
    assert np.abs(np.asarray(logits[0]) - want).max() > 0.05


def test_eps_is_the_configurations():
    """1e-5 as published against the program's default 1e-6: told apart
    where a norm's input is small. The embedding scaled by 2^-10 puts the
    first norm's mean square near 1e-6."""
    cfg = tiny_config()
    w = dict(drawn(cfg))
    w["embed"] = w["embed"] * jnp.asarray(2.0 ** -10, jnp.bfloat16)
    tokens = sequence(24)
    want, _, _, _ = reference(cfg, w, tokens)
    mcfg, params = model(cfg, w, jnp.float32)
    assert mcfg.rms_eps == 1e-5
    logits, _ = latent_moe.forward(mcfg, params, jnp.asarray(tokens)[None])
    np.testing.assert_allclose(np.asarray(logits[0]), want, atol=F32_TOL)
    other, params = model(cfg, w, jnp.float32, rms_eps=1e-6)
    logits, _ = latent_moe.forward(other, params, jnp.asarray(tokens)[None])
    assert np.abs(np.asarray(logits[0]) - want).max() > 0.05
    # and every other family's norm is what it was
    x = jnp.asarray(np.random.default_rng(0).normal(size=(4, 64)) * 1e-3,
                    jnp.float32)
    scale = jnp.ones(64)
    import flax.linen as nn
    np.testing.assert_array_equal(
        np.asarray(serve_decode._rmsnorm(x, scale, jnp.float32)),
        np.asarray(nn.RMSNorm(dtype=jnp.float32).apply(
            {"params": {"scale": scale}}, x)))


def test_the_absorbed_decode_is_the_expanded_prefills_last_row():
    """Two formulations of one mathematics: a decode step over the cache
    a prefill left, W_kvb folded into the query and the output, against
    the expanded forward over the sequence one token longer."""
    cfg = tiny_config()
    w = drawn(cfg)
    mcfg, params = model(cfg, w, jnp.float32)
    tokens = sequence(21)
    n = 20
    _, row = serve_decode.prefill(mcfg, params,
                                  jnp.asarray(tokens[None, :n]), n - 1)
    assert set(row) == {"latent"}
    assert row["latent"].shape == (3, 1, n, 1, mcfg.latent_lanes)
    assert mcfg.latent_dim == 40 and mcfg.latent_lanes == 128
    assert not np.asarray(row["latent"])[..., 40:].any()
    cache = jnp.zeros((3, 2, 32, 1, 128), jnp.float32).at[:, 1, :n].set(
        row["latent"][:, 0])
    logits, state, counts = serve_decode.decode(
        mcfg, params, jnp.asarray([0, tokens[n]], jnp.int32),
        jnp.asarray([31, n], jnp.int32), {"latent": cache},
        jnp.asarray([False, True]))
    full, _ = latent_moe.forward(mcfg, params, jnp.asarray(tokens)[None])
    np.testing.assert_allclose(np.asarray(logits[1]), np.asarray(full[0, n]),
                               atol=2e-5)
    # one decoding row: two experts in each of two layers, one token each
    assert counts.tolist() == [4, 1]
    # the new token's latent is the expanded forward's, at its position
    _, latent, _ = latent_moe.hidden_states(mcfg, params,
                                            jnp.asarray(tokens)[None])
    np.testing.assert_allclose(np.asarray(state["latent"])[:, 1, n],
                               np.asarray(latent)[:, 0, n], atol=2e-5)


def test_a_transformer_config_with_experts_is_sent_to_this_family():
    from horovod_tpu.models import transformer as tr
    cfg = tr.TransformerConfig.tiny(num_experts=4)
    with pytest.raises(NotImplementedError, match="LatentMoEConfig"):
        serve_decode.state_shapes(cfg, 2, 16)
