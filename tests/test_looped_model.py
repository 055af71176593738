"""The looped decoder (models/looped.py: the dense block with a norm
closing each branch, its layer stack run T times over one set of weights)
against the plain reference the benchmark keeps (benchmarks/reference/
ouro.py), at tiny sizes on seeded weights.

Tolerances, and why each:
  F32_TOL 2e-4   the program computed in float32 against the float32
                 reference: the same arithmetic in another order (a fused
                 qkv, flax's norm); measured 6e-6 at logits of standard
                 deviation 1 after 4 passes of 2 layers (3 seeds).
  BF16_TOL 0.2   the program as it is served (bfloat16 activations and
                 weights) against the float32 reference: 8 bits of
                 mantissa through 8 layer applications, each branch
                 renormalised; measured 0.06-0.09 (3 seeds).
Leaving out a pass, a norm or a branch moves the logits by 0.3 and over
(``test_what_is_left_out_shows``), so none of them can hide inside either.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import weights
from benchmarks.programs import ouro as prog
from benchmarks.reference import ouro as ref
from horovod_tpu.models import looped
from horovod_tpu.models import transformer as tr
from horovod_tpu.serving import decode as serve_decode

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F32_TOL, BF16_TOL = 2e-4, 0.2
LAYERS = 2


def tiny_config(**kw):
    """The published configuration file with every width made tiny; the
    four passes, the rotary base and the threshold stay the published
    ones."""
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "ouro-2.6b.json")) as f:
        cfg = json.load(f)
    cfg.update(hidden_size=64, intermediate_size=128, num_attention_heads=4,
               num_key_value_heads=4, head_dim=16, vocab_size=256,
               max_position_embeddings=512)
    cfg.update(kw)
    return cfg


def drawn(cfg, seed=5):
    shapes = ref.weight_shapes(cfg, LAYERS)
    return jax.jit(lambda k: weights.make(shapes, k, jnp.bfloat16))(
        weights.seed_key(seed))


def model(cfg, w, dtype, **overrides):
    lcfg = prog.looped_config(cfg, LAYERS, dtype=dtype,
                              attention_impl="full", **overrides)
    params = jax.jit(lambda w: prog.to_tree(w, LAYERS))(w)
    if dtype == jnp.float32:
        params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                        params)
    return lcfg, params


def sequence(n, seed=3):
    return np.random.default_rng(seed).integers(0, 256, n).astype(np.int32)


def reference(cfg, w, tokens):
    """(logits [s, vocab], hidden [T, s, d], p_t [T, s]) of the plain
    reference over the whole sequence."""
    with jax.default_matmul_precision("highest"):
        hidden = ref.pass_states(w, jnp.asarray(tokens), cfg, LAYERS)
        return (np.asarray(ref.head(w, hidden[-1], None)),
                np.asarray(hidden), np.asarray(ref.exit_pdf(w, hidden)))


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, F32_TOL),
                                       (jnp.bfloat16, BF16_TOL)])
def test_the_plain_forward_is_the_reference(dtype, tol):
    """Last-pass logits, EVERY pass's normalised hidden state and the
    exit distribution of the program's own forward against the
    reference's."""
    cfg = tiny_config()
    w = drawn(cfg)
    tokens = sequence(40)
    lcfg, params = model(cfg, w, dtype)
    logits, hidden, pdf = looped.forward(lcfg, params, jnp.asarray(tokens)[None])
    want_logits, want_hidden, want_pdf = reference(cfg, w, tokens)
    assert hidden.shape == (4, 1, 40, 64) and pdf.shape == (4, 1, 40)
    assert np.std(want_logits) == pytest.approx(1.0, abs=0.2)
    np.testing.assert_allclose(np.asarray(logits[0], np.float32),
                               want_logits, atol=tol)
    np.testing.assert_allclose(np.asarray(hidden[:, 0], np.float32),
                               want_hidden, atol=tol)
    np.testing.assert_allclose(np.asarray(pdf[:, 0]), want_pdf, atol=tol)
    np.testing.assert_allclose(np.asarray(pdf).sum(axis=0), 1.0, atol=1e-6)
    # the passes are not copies of one another
    assert np.abs(want_hidden[1] - want_hidden[0]).max() > 0.1


def test_one_pass_without_the_closing_norms_is_the_dense_model():
    """T = 1, no norm after a branch, the dense model's rotary base: the
    dense model on the same leaves, bit for bit, through the whole-sequence
    forward, the prefill and a decode step."""
    dense = tr.TransformerConfig.tiny(dtype=jnp.bfloat16,
                                      attention_impl="full")
    _, params = tr.init_params(dense, jax.random.PRNGKey(1))
    lcfg = looped.LoopedConfig.tiny(passes=1, sandwich_norm=False,
                                    dtype=jnp.bfloat16)
    assert (lcfg.d_ff, lcfg.num_layers, lcfg.planes) == (dense.d_ff, 2, 2)
    gate = looped.init_params(lcfg, jax.random.PRNGKey(2))["exit_gate"]
    tokens = jnp.asarray(sequence(24))[None]
    want = tr.TransformerLM(dense).apply({"params": params}, tokens)
    logits, hidden, pdf = looped.forward(lcfg, dict(params, exit_gate=gate),
                                         tokens)
    assert np.array_equal(np.asarray(logits), np.asarray(want))
    assert np.array_equal(np.asarray(pdf), np.ones((1, 1, 24), np.float32))
    got, want = (serve_decode.prefill(c, params, tokens, jnp.int32(20))
                 for c in (lcfg, dense))
    # the looped prefill applies the head to the one row asked for, the
    # dense one to all and takes the row: another order of one float32 sum
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               atol=1e-6)
    for kind in ("k", "v"):
        assert np.array_equal(np.asarray(got[1][kind]),
                              np.asarray(want[1][kind]))
    kv = jnp.zeros((2, 3, 32, 4, 16), jnp.bfloat16)
    args = (jnp.asarray([5, 6, 7]), jnp.asarray([0, 3, 9]),
            {"k": kv, "v": kv})
    got, want = (serve_decode.decode(c, params, *args)
                 for c in (lcfg, dense))
    assert np.array_equal(np.asarray(got[0]), np.asarray(want[0]))
    for kind in ("k", "v"):
        assert np.array_equal(np.asarray(got[1][kind]),
                              np.asarray(want[1][kind]))


def test_the_reference_is_its_loop_unrolled_by_hand():
    """T = 2 written out: layer 0, layer 1, the final norm, then the same
    two layers and the same norm again, and the gate after each."""
    cfg = tiny_config(total_ut_steps=2)
    w = drawn(cfg, seed=9)
    tokens = jnp.asarray(sequence(20))
    eps = cfg["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        x = w["embed"][tokens].astype(jnp.float32)
        x = ref.layer(w, "layers.0.", x, cfg, None)
        x = ref.layer(w, "layers.1.", x, cfg, None)
        first = ref.rms_norm(x, w["ln_f.scale"], eps)
        x = ref.layer(w, "layers.0.", first, cfg, None)
        x = ref.layer(w, "layers.1.", x, cfg, None)
        second = ref.rms_norm(x, w["ln_f.scale"], eps)
        gate = jax.nn.sigmoid(
            first @ w["exit_gate.w"].astype(jnp.float32)[:, 0]
            + w["exit_gate.bias"].astype(jnp.float32))
        hidden = ref.pass_states(w, tokens, cfg, LAYERS)
        pdf = ref.exit_pdf(w, hidden)
        logits = ref.logits_at(w, tokens, jnp.arange(20), cfg, LAYERS)
        by_hand = second @ w["head"].astype(jnp.float32)
    np.testing.assert_array_equal(np.asarray(hidden),
                                  np.stack([first, second]))
    np.testing.assert_allclose(np.asarray(pdf), np.stack([gate, 1 - gate]),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(by_hand),
                               atol=1e-5)


@pytest.mark.parametrize("left_out", ["a pass", "the norm between passes",
                                      "the attention branch",
                                      "the norm closing a branch"])
def test_what_is_left_out_shows(left_out):
    """Each part of the loop moves the reference's logits by far more
    than either tolerance."""
    cfg = tiny_config()
    w = dict(drawn(cfg))
    tokens = sequence(32)
    want = reference(cfg, w, tokens)[0]
    if left_out == "a pass":
        got = reference(dict(cfg, total_ut_steps=3), w, tokens)[0]
    elif left_out == "the norm between passes":
        with jax.default_matmul_precision("highest"):
            x = w["embed"][jnp.asarray(tokens)].astype(jnp.float32)
            for _ in range(4):
                for i in range(LAYERS):
                    x = ref.layer(w, f"layers.{i}.", x, cfg, None)
            x = ref.rms_norm(x, w["ln_f.scale"], cfg["rms_norm_eps"])
            got = np.asarray(ref.head(w, x, None))
    elif left_out == "the attention branch":
        w["layers.1.ln_attn_out.scale"] = jnp.zeros_like(
            w["layers.1.ln_attn_out.scale"])
        got = reference(cfg, w, tokens)[0]
    else:  # the norm's gain in place of the norm: a constant scale
        w["layers.0.attn.o"] = w["layers.0.attn.o"] * 4
        got_scaled = reference(cfg, w, tokens)[0]
        # N2 makes the branch's scale immaterial: with it the logits stay
        np.testing.assert_allclose(got_scaled, want, atol=2e-3)
        w["layers.0.ln_attn_out.scale"] = \
            w["layers.0.ln_attn_out.scale"] * 4
        got = reference(cfg, w, tokens)[0]
    assert np.abs(got - want).max() > 0.3


@pytest.mark.parametrize("passes", [1, 2, 4, 7])
def test_the_exit_distribution_sums_to_one(passes):
    gates = jax.random.uniform(jax.random.PRNGKey(passes), (passes, 3, 5))
    pdf = np.asarray(looped.exit_distribution(gates))
    assert pdf.shape == (passes, 3, 5) and (pdf >= 0).all()
    np.testing.assert_allclose(pdf.sum(axis=0), 1.0, atol=1e-6)
    g = np.asarray(gates)
    np.testing.assert_allclose(pdf[0], g[0] if passes > 1 else 1.0,
                               atol=1e-6)
    if passes > 2:
        np.testing.assert_allclose(pdf[1], g[1] * (1 - g[0]), atol=1e-6)
    # a gate that always fires leaves at the first pass, one that never
    # does at the last
    always = np.asarray(looped.exit_distribution(jnp.ones((passes, 2))))
    never = np.asarray(looped.exit_distribution(jnp.zeros((passes, 2))))
    assert always[0].tolist() == [1.0, 1.0] and never[-1].tolist() == [1, 1]
    assert always.sum() == never.sum() == 2.0


def test_the_adapter_fuses_q_k_v_and_refuses_another_eps():
    cfg = tiny_config()
    w = drawn(cfg)
    tree = prog.to_tree(w, LAYERS)
    qkv = np.asarray(tree["layer_1"]["attn"]["qkv"]["kernel"])
    for n, part in zip("qkv", np.split(qkv, 3, axis=1)):
        assert np.array_equal(part, np.asarray(w[f"layers.1.attn.{n}"]))
    lcfg = prog.looped_config(cfg, LAYERS)
    assert (lcfg.passes, lcfg.rope_theta, lcfg.exit_threshold,
            lcfg.sandwich_norm, lcfg.planes) == (4, 1e6, 1.0, True, 8)
    want = jax.tree_util.tree_structure(
        looped.init_params(lcfg, jax.random.PRNGKey(0)))
    assert jax.tree_util.tree_structure(tree) == want
    with pytest.raises(ValueError, match="eps"):
        prog.looped_config(dict(cfg, rms_norm_eps=1e-5), LAYERS)
    with pytest.raises(ValueError, match="key/value"):
        prog.looped_config(dict(cfg, num_key_value_heads=2), LAYERS)
