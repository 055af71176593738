"""The decoder with window and full attention layers, a per-head gate and
dropless experts (models/window_moe.py) against the plain reference the
benchmark keeps (benchmarks/reference/laguna.py), at tiny sizes on seeded
weights: the published configuration file with every width made tiny and
BOTH group sizes kept: layers full / window / window / window / full with
3, 4, 4, 4, 3 query heads over 1 key/value head of 16, window 8, a dense
first layer, 16 experts of 32 of which a token takes 4, one shared,
vocabulary 256; the full layers' rotary partial (8 of 16 lanes) under YaRN
(factor 8 over an original length of 16, so the ramp lies inside the 4
pairs), the window layers' whole and plain.

Tolerances, and why each:
  F32_TOL 3e-4   the program computed in float32 against the float32
                 reference: the same arithmetic in another order (flax's
                 norm, a grouped query einsum, a sorted grouped product
                 against a loop over the experts); measured 3e-6 at logits
                 of standard deviation 1.0. bfloat16 where float32 is
                 stated reads 0.3-1.4 and int8 2.5
                 (``test_a_lower_precision_shows``).
  ROUTE_TOL 2e-5 the routing weights, float32 sigmoids of float32 logits
                 on both sides; measured 3e-7.
  BF16_TOL 0.2   the program as it is served (bfloat16) against the
                 float32 reference through 5 layers, at 85% of the
                 positions (top 4 of 16 has near ties in one choice of
                 ten, and a token routed elsewhere reads 0.3-1.5);
                 measured 0.03-0.08 at the others.
Leaving out the gate, the window, the partial rotation, YaRN's blend or
its factor, the routed scale or the shared expert moves the logits by
0.02 and over (``test_what_is_left_out_shows``), so none can hide inside
F32_TOL.
"""

import dataclasses
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import weights
from benchmarks.programs import laguna as prog
from benchmarks.reference import laguna as ref
from horovod_tpu.models import window_moe
from horovod_tpu.serving import decode as serve_decode

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F32_TOL, ROUTE_TOL, BF16_TOL = 3e-4, 2e-5, 0.2
LAYERS = 5


def published():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "laguna-xs.2.json")) as f:
        return json.load(f)


def tiny_config(**kw):
    """The published configuration file with every width made tiny; the
    pattern of layers, the routing keys, eps, both rotary bases and the
    dense first layer stay the published ones. ``expert_gain_log2`` 2: at
    16 experts the stacks join the flat draw scaled by 1 / sqrt(16 x
    rows)."""
    cfg = published()
    cfg.update(hidden_size=64, head_dim=16, num_key_value_heads=1,
               intermediate_size=128, num_experts=16, num_experts_per_tok=4,
               moe_intermediate_size=32, shared_expert_intermediate_size=32,
               vocab_size=256, sliding_window=8,
               max_position_embeddings=512,
               num_attention_heads_per_layer=[3, 4, 4, 4] * 10)
    rope = json.loads(json.dumps(cfg["rope_parameters"]))
    rope["full_attention"].update(
        factor=8, original_max_position_embeddings=16, beta_fast=4,
        attention_factor=0.1 * math.log(8) + 1)
    cfg["rope_parameters"] = rope
    cfg["assumed"] = dict(cfg["assumed"], init=dict(
        cfg["assumed"]["init"], expert_gain_log2=2))
    cfg.update(kw)
    return cfg


def drawn(cfg, seed=5, layers=LAYERS):
    shapes = ref.weight_shapes(cfg, layers)
    return jax.jit(lambda k: weights.make(shapes, k, jnp.bfloat16))(
        weights.seed_key(seed))


def model(cfg, w, dtype, layers=LAYERS, **overrides):
    mcfg = prog.window_moe_config(cfg, layers, dtype=dtype,
                                  attention_impl="full", **overrides)
    params = jax.jit(lambda w: prog.to_tree(w, layers, cfg))(w)
    if dtype == jnp.float32:
        params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                        params)
    return mcfg, params


def sequence(n, seed=3):
    return np.random.default_rng(seed).integers(0, 256, n).astype(np.int32)


def reference(cfg, w, tokens, quant=None, layers=LAYERS):
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.logits_at(
            w, jnp.asarray(tokens), jnp.arange(len(tokens)), cfg, layers,
            quant))


def test_the_adapter_reads_the_published_file():
    mcfg = prog.window_moe_config(published(), LAYERS)
    assert mcfg.layer_types == ("full", "window", "window", "window", "full")
    assert mcfg.heads_per_layer == (48, 64, 64, 64, 48)
    assert (mcfg.window, mcfg.ring_len, mcfg.num_kv_heads, mcfg.head_dim) \
        == (512, 640, 8, 128)
    assert (mcfg.planes("full"), mcfg.planes("window")) == (2, 3)
    assert [mcfg.plane(i) for i in range(5)] == [0, 0, 1, 2, 1]
    assert (mcfg.first_dense, mcfg.num_experts, mcfg.experts_per_tok,
            mcfg.d_expert, mcfg.d_shared, mcfg.route_scale) == \
        (1, 256, 8, 512, 512, 2.5)
    assert mcfg.rope_full == window_moe.Rotary(
        theta=500000.0, fraction=0.5, factor=64.0, original_len=4096,
        beta_fast=64.0, beta_slow=1.0,
        attention_factor=1.4158883083359672)
    assert mcfg.rope_window == window_moe.Rotary(theta=10000.0)
    assert mcfg.rope_full.attention_factor == \
        pytest.approx(0.1 * math.log(64) + 1)
    hash(mcfg)     # a static argument of the serving programs
    shapes = serve_decode.state_shapes(mcfg, 64, 5120)
    assert shapes["k"].shape == shapes["v"].shape == (2, 64, 5120, 8, 128)
    assert shapes["k_ring"].shape == shapes["v_ring"].shape == \
        (3, 64, 640, 8, 128)
    # the arithmetic of ISSUE 44: 2.68 GB + 0.50 GB, against 6.71 GB
    nbytes = {k: math.prod(a.shape) * 2 for k, a in shapes.items()}
    assert round((nbytes["k"] + nbytes["v"]) / 1e9, 2) == 2.68
    assert round((nbytes["k_ring"] + nbytes["v_ring"]) / 1e9, 2) == 0.5
    assert round(5 * 64 * 5120 * 4096 / 1e9, 2) == 6.71


def test_the_plain_forward_is_the_reference_in_float32():
    """Logits, the experts every token chose and their weights."""
    cfg = tiny_config()
    w = drawn(cfg)
    tokens = sequence(48)
    mcfg, params = model(cfg, w, jnp.float32)
    assert (mcfg.heads_per_layer, mcfg.window) == ((3, 4, 4, 4, 3), 8)
    logits, loads = window_moe.forward(mcfg, params,
                                       jnp.asarray(tokens)[None])
    want = reference(cfg, w, tokens)
    assert np.std(want) == pytest.approx(1.0, abs=0.25)
    np.testing.assert_allclose(np.asarray(logits[0]), want, atol=F32_TOL)
    # every assignment is there: no capacity, nothing dropped
    assert len(loads) == 4
    for load in loads:
        assert int(load.sum()) == 48 * 4
    # the router alone, on the reference's own normed input
    with jax.default_matmul_precision("highest"):
        idx, wts, scores = ref.routes_at(w, jnp.asarray(tokens), cfg, LAYERS)
    assert idx.shape == (4, 48, 4) and scores.shape == (4, 48, 16)
    np.testing.assert_allclose(np.asarray(wts).sum(-1), 2.5, atol=1e-5)


@pytest.mark.parametrize("quant,least", [("bfloat16", 0.1), ("int8", 1.0)])
def test_a_lower_precision_shows(quant, least):
    """F32_TOL is tight enough: the program in bfloat16 where float32 is
    stated, and the reference's int8 control, both fail it by far."""
    cfg = tiny_config()
    w = drawn(cfg)
    tokens = sequence(48)
    want = reference(cfg, w, tokens)
    if quant == "int8":
        got = reference(cfg, w, tokens, quant="int8")
    else:
        mcfg, params = model(cfg, w, jnp.bfloat16)
        got = np.asarray(window_moe.forward(
            mcfg, params, jnp.asarray(tokens)[None])[0][0], np.float32)
    assert np.abs(got - want).max() > least > 100 * F32_TOL


@pytest.mark.parametrize("seed", [5, 6, 8])
def test_the_served_precision_is_near_the_reference(seed):
    """bfloat16 as served: 85% of the positions within BF16_TOL (the served
    check's own statistic), and the rest one flipped choice away: a token
    that bfloat16 routes elsewhere than float32 moves its own logits by
    0.3-1.5, and the later ones hardly (they meet it through K and V)."""
    cfg = tiny_config()
    w = drawn(cfg, seed)
    tokens = sequence(48, seed)
    mcfg, params = model(cfg, w, jnp.bfloat16)
    logits = np.asarray(window_moe.forward(
        mcfg, params, jnp.asarray(tokens)[None])[0][0], np.float32)
    err = np.abs(logits - reference(cfg, w, tokens)).max(-1)
    assert np.quantile(err, 0.85, method="higher") < BF16_TOL, err
    assert np.median(err) < BF16_TOL / 2 and err.max() < 2.5, err


def _with(cfg, **kw):
    return dataclasses.replace(cfg, **kw)


@pytest.mark.parametrize("what", ["gate", "window", "partial", "yarn_blend",
                                  "yarn_factor", "route_scale", "shared",
                                  "heads"])
def test_what_is_left_out_shows(what, monkeypatch):
    """Each mechanism moves the logits by far more than F32_TOL: none of
    them is vacuous at the test's size."""
    cfg = tiny_config()
    w = drawn(cfg)
    tokens = sequence(48)
    mcfg, params = model(cfg, w, jnp.float32)
    want = reference(cfg, w, tokens)
    full = mcfg.rope_full
    if what == "gate":
        monkeypatch.setattr(window_moe, "gate_activation",
                            lambda x: jnp.ones_like(x))
    elif what == "window":
        mcfg = _with(mcfg, window=512)
    elif what == "partial":
        mcfg = _with(mcfg, rope_full=dataclasses.replace(full, fraction=1.0))
    elif what == "yarn_blend":
        mcfg = _with(mcfg, rope_full=dataclasses.replace(full, factor=1.0))
    elif what == "yarn_factor":
        mcfg = _with(mcfg, rope_full=dataclasses.replace(
            full, attention_factor=1.0))
    elif what == "route_scale":
        mcfg = _with(mcfg, route_scale=1.0)
    elif what == "shared":
        monkeypatch.setattr(window_moe, "shared_expert",
                            lambda cfg, layer, y: jnp.zeros_like(y))
    elif what == "heads":
        # the window layers' fourth head silenced: its gate column zeroed
        for i in (1, 2, 3):
            g = params[f"layer_{i}"]["attn"]["gate"]["kernel"]
            params[f"layer_{i}"]["attn"]["gate"]["kernel"] = \
                g.at[:, 3].set(-1e4)
    got = np.asarray(window_moe.forward(mcfg, params,
                                        jnp.asarray(tokens)[None])[0][0])
    assert np.abs(got - want).max() > 0.02, what


# -- both rotary laws against the written formulas ----------------------------

def test_the_window_layers_rotary_is_the_plain_law():
    law = window_moe.Rotary(theta=10000.0)
    freq = window_moe.inverse_frequencies(law, 128)
    want = 1.0 / 10000.0 ** (np.arange(64) / 64.0)
    np.testing.assert_allclose(freq, want, rtol=1e-6)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, 6, 2, 128)),
                    jnp.float32)
    pos = jnp.arange(6)[None]
    got = np.asarray(window_moe.rotate(x, pos, law))
    ang = np.arange(6)[:, None] * want[None]                  # [s, 64]
    x1, x2 = np.asarray(x)[0, :, :, :64], np.asarray(x)[0, :, :, 64:]
    cos, sin = np.cos(ang)[:, None], np.sin(ang)[:, None]
    np.testing.assert_allclose(
        got[0], np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               -1), atol=1e-5)
    # the program's own _rope is this law
    from horovod_tpu.models.transformer import _rope
    np.testing.assert_allclose(got, np.asarray(_rope(x, pos, 10000.0)),
                               atol=1e-6)


def test_the_full_layers_rotary_is_yarn_on_half_the_lanes():
    """The published law of Laguna-XS.2's full layers, from the formulas of
    ISSUE 44: 64 of 128 lanes, base 500,000, factor 64 over 4,096, beta
    64 and 1, the ramp between the two correction dimensions, cos and sin
    times 0.1 ln 64 + 1."""
    law = prog.rotary(published()["rope_parameters"]["full_attention"])
    freq = window_moe.inverse_frequencies(law, 128)
    assert freq.shape == (32,)
    dim, base = 64, 500000.0
    extrapolation = 1.0 / base ** (np.arange(0, dim, 2) / dim)

    def correction(rotations):
        return dim * math.log(4096 / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))
    low, high = math.floor(correction(64)), math.ceil(correction(1))
    assert (low, high) == (5, 16)
    ramp = np.clip((np.arange(32) - low) / (high - low), 0, 1)
    want = extrapolation / 64 * ramp + extrapolation * (1 - ramp)
    np.testing.assert_allclose(freq, want, rtol=1e-6)
    # fast pairs keep the base's own frequency, slow ones are divided by 64
    np.testing.assert_allclose(freq[:6], extrapolation[:6], rtol=1e-6)
    np.testing.assert_allclose(freq[16:], extrapolation[16:] / 64, rtol=1e-6)
    # and the reference computes the same frequencies by its own code
    np.testing.assert_allclose(
        ref.yarn_inverse_frequencies(
            published()["rope_parameters"]["full_attention"], 64),
        freq, rtol=1e-6)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(1, 5, 3, 128)),
                    jnp.float32)
    got = np.asarray(window_moe.rotate(x, jnp.arange(5)[None] + 700, law))
    # the last 64 lanes are not rotated and not scaled
    np.testing.assert_array_equal(got[..., 64:], np.asarray(x)[..., 64:])
    ang = (np.arange(5) + 700)[:, None] * want[None]
    af = 1.4158883083359672
    cos, sin = np.cos(ang)[:, None] * af, np.sin(ang)[:, None] * af
    x1, x2 = np.asarray(x)[0, ..., :32], np.asarray(x)[0, ..., 32:64]
    np.testing.assert_allclose(
        got[0, ..., :64],
        np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1),
        atol=2e-4)
    # the reference's rotary, the same law by other code
    by_ref = ref.rotary(jnp.asarray(x[0]), jnp.arange(5) + 700,
                        published()["rope_parameters"]["full_attention"], 128)
    np.testing.assert_allclose(got[0], np.asarray(by_ref), atol=2e-4)


# -- the ring -----------------------------------------------------------------

@pytest.mark.parametrize("s,last", [(6, 5), (8, 7), (16, 4), (16, 8),
                                    (16, 15), (32, 20), (32, 31)])
def test_a_prefill_leaves_its_last_window_in_the_ring(s, last):
    """Entry r holds the last real position p with p mod 8 == r; what the
    row has no token for yet is junk that the length hides."""
    cfg = window_moe.WindowMoEConfig.tiny()
    kv = jnp.arange(s, dtype=jnp.float32)[None, :, None, None] \
        * jnp.ones((1, s, 1, 2))
    ring = np.asarray(window_moe.ring_of(cfg, kv, jnp.int32(last)))[0, :, 0, 0]
    assert len(ring) == min(s, 8)
    for p in range(max(0, last - 7), last + 1):
        assert ring[p % 8] == p
    assert len(ring) <= 8 < cfg.ring_len


def test_prefill_then_decode_is_the_whole_forward_as_the_ring_wraps():
    """The model's own two serving forwards by hand: a prompt longer than
    the window (the ring is written wrapped at once), then 30 tokens (the
    ring wraps almost four times more), with another row masked out, in
    float32 against the model's plain forward."""
    cfg = window_moe.WindowMoEConfig.tiny(dtype=jnp.float32)
    params = window_moe.init_params(cfg, jax.random.PRNGKey(0))
    tokens = sequence(43, 4)
    logits, _ = window_moe.forward(cfg, params, jnp.asarray(tokens)[None])
    n0 = 13
    state = {k: jnp.zeros(a.shape, a.dtype) for k, a in
             serve_decode.state_shapes(cfg, 2, 64).items()}
    assert state["k_ring"].shape == (3, 2, 9, 1, 16)
    pad = np.zeros((1, 16), np.int32)
    pad[0, :n0] = tokens[:n0]
    first, row = serve_decode.prefill(cfg, params, jnp.asarray(pad), n0 - 1)
    assert {k: v.shape[2] for k, v in row.items()} == \
        {"k": 16, "v": 16, "k_ring": 8, "v_ring": 8}
    np.testing.assert_allclose(np.asarray(first[0]),
                               np.asarray(logits[0, n0 - 1]), atol=F32_TOL)
    state = {k: a.at[:, 1, :row[k].shape[2]].set(row[k][:, 0])
             for k, a in state.items()}
    step = jax.jit(serve_decode.decode, static_argnums=0)
    for p in range(n0, 43):
        got, state, routed = step(
            cfg, params, jnp.asarray([0, tokens[p]]), jnp.asarray([63, p]),
            state, jnp.asarray([False, True]))
        np.testing.assert_allclose(np.asarray(got[1]),
                                   np.asarray(logits[0, p]), atol=F32_TOL)
        assert routed.tolist()[0] == 16        # one row, 4 layers x 4
    # the masked-out row: nothing in its ring but the place to park
    ring = np.asarray(state["k_ring"])[:, 0]
    assert not ring[:, :8].any() and ring[:, 8].any()


def test_a_transformer_config_with_experts_is_sent_to_a_family_that_has_them():
    from horovod_tpu.models import transformer as tr
    cfg = tr.TransformerConfig.tiny(num_experts=4)
    with pytest.raises(NotImplementedError, match="WindowMoEConfig"):
        serve_decode.state_shapes(cfg, 2, 32)


def test_a_pattern_that_is_no_pattern_is_refused():
    cfg = window_moe.WindowMoEConfig.tiny(layer_types=("full", "banded"),
                                          heads_per_layer=(3, 4))
    with pytest.raises(ValueError, match="'full'"):
        window_moe.check_served(cfg)
    cfg = window_moe.WindowMoEConfig.tiny(num_kv_heads=2)
    with pytest.raises(ValueError, match="key/value heads"):
        window_moe.check_served(cfg)
