"""The decoder-hybrid-decoder (models/sambay.py: Mamba-1 and window
differential attention, ONE full K/V plane that the cross layers share,
gated memory units) against the plain reference the benchmark keeps
(benchmarks/reference/phi4flash.py), at tiny sizes on seeded weights: the
published configuration file with every width made tiny and the plan kept
(8 layers: mamba, window, mamba, window, mamba, full, gmu, cross), 8 query
and 4 key/value heads of 8 (4 differential heads over 2 pairs), window 8,
a state of 4 a channel, vocabulary 256.

Tolerances, and why each:
  F32_TOL 3e-4   the program computed in float32 against the float32
                 reference: the same arithmetic in another order (packed
                 heads of two against two softmaxes over 8 lanes each, a
                 state-major scan against a channel-major one); measured
                 4e-6 at logits of standard deviation 1.0. bfloat16 where
                 float32 is stated reads 0.01-0.3
                 (``test_a_lower_precision_shows``).
  BF16_TOL 0.25  the program as it is served (bfloat16) against the float32
                 reference through 8 layers: measured 0.05-0.1; int8 reads
                 0.2 and over at the same positions.
Leaving out lambda, the sub-norm's scale, the window, the memory's skip or
the cross layers' causal length moves the logits by 0.01 and over
(``test_what_is_left_out_shows``), so none can hide inside F32_TOL.
"""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import weights
from benchmarks.programs import phi4flash as prog
from benchmarks.reference import phi4flash as ref
from horovod_tpu.models import sambay
from horovod_tpu.ops import flash_attention as fa
from horovod_tpu.serving import decode as serve_decode

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F32_TOL, BF16_TOL = 3e-4, 0.25
LAYERS = 8


def published():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "phi-4-mini-flash.json")) as f:
        return json.load(f)


def tiny_config(**kw):
    """The published configuration file with every width made tiny; the
    layer plan, eps, the biases and the init rules stay the published
    ones. ``embed_gain_log2`` -3: a tied head over 64 lanes."""
    cfg = published()
    cfg.update(hidden_size=64, num_attention_heads=8, num_key_value_heads=4,
               intermediate_size=128, num_hidden_layers=LAYERS,
               sliding_window=8, vocab_size=256,
               max_position_embeddings=512)
    cfg["assumed"] = dict(
        cfg["assumed"],
        mamba=dict(cfg["assumed"]["mamba"], d_state=4, dt_rank=4),
        init=dict(cfg["assumed"]["init"], embed_gain_log2=-3))
    cfg.update(kw)
    return cfg


def drawn(cfg, seed=3, dtype=jnp.float32):
    shapes = ref.weight_shapes(cfg, cfg["num_hidden_layers"])
    return jax.jit(lambda k: weights.make(shapes, k, dtype))(
        weights.seed_key(seed))


def model(cfg, w, dtype, **kw):
    kw.setdefault("attention_impl", "full")
    kw.setdefault("max_seq_len", 64)
    mcfg = prog.sambay_config(cfg, cfg["num_hidden_layers"], dtype=dtype,
                              **kw)
    w = {k: v.astype(dtype) for k, v in w.items()}
    return mcfg, prog.to_tree(w, mcfg, cfg)


def sequence(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, n).astype(np.int32)


def reference_logits(w, tokens, rows, cfg, quant=None):
    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(lambda w, t, r: ref.logits_at(
            w, t, r, cfg, cfg["num_hidden_layers"], quant))(
                w, jnp.asarray(tokens), jnp.asarray(rows)))


@functools.lru_cache(maxsize=None)
def world(seed=3, dtype="float32"):
    """(cfg, w, mcfg, params, jitted forward) of the tiny model, shared by
    the tests that only read it."""
    cfg = tiny_config()
    w = drawn(cfg, seed, jnp.dtype(dtype))
    mcfg, params = model(cfg, w, jnp.dtype(dtype))
    return cfg, w, mcfg, params, jax.jit(
        lambda p, t: sambay.forward(mcfg, p, t))


def test_the_plan_is_the_published_one():
    """32 layers: nine Mamba, eight window, THE full layer 17, seven gated
    memory units and seven cross layers; the adapter, the reference and the
    counts each derive it by their own code."""
    cfg = published()
    mcfg = prog.sambay_config(cfg, 32)
    kinds = sambay.layer_kinds(mcfg)
    assert kinds == tuple(ref.layer_kind(cfg, i) for i in range(32))
    assert [kinds.count(k) for k in
            ("mamba", "window", "full", "gmu", "cross")] == [9, 8, 1, 7, 7]
    assert kinds[16:20] == ("mamba", "full", "gmu", "cross")
    assert sambay.readers(mcfg) == 8 and mcfg.memory_layer == 16
    assert (mcfg.head_dim, mcfg.lanes, mcfg.pairs, mcfg.d_inner,
            mcfg.dt_rank, mcfg.ring_len) == (64, 128, 10, 5120, 160, 640)
    assert round(sambay.lambda_init(17), 4) == round(ref.lambda_init(17), 4) \
        == 0.7963


@pytest.mark.parametrize("seed", [3, 4])
def test_forward_is_the_references_at_every_position(seed):
    cfg, w, mcfg, params, forward = world(seed)
    tokens = sequence(40, seed)
    want = reference_logits(w, tokens, np.arange(40), cfg)
    got = np.asarray(forward(params, jnp.asarray(tokens)[None]))
    assert 0.5 < want.std() < 2.0
    assert np.abs(got[0] - want).max() < F32_TOL


def test_the_tied_head_does_not_answer_with_the_input_token():
    """The head is the embedding. At unit gain a position's own input
    token's row stands in the residual and answers the head with |e|^2:
    every greedy step serves its input again, whatever the layers compute
    (the cell's first chip run, PR 48: a served gap of exactly 0). Under
    ``assumed.init.embed_gain_log2`` the input token's logit is one of
    the row's; at unit gain it stands deviations above it."""
    cfg, w, _, _, _ = world()
    tokens = sequence(40)

    def own(cfg):
        """How far above the row's mean the input token's logit stands, in
        the row's deviations, on average."""
        logits = reference_logits(w, tokens, np.arange(40), cfg)
        z = (logits - logits.mean(-1, keepdims=True)) \
            / logits.std(-1, keepdims=True)
        return z[np.arange(40), tokens].mean()
    assert abs(own(cfg)) < 0.5
    loud = dict(cfg, assumed=dict(cfg["assumed"], init=dict(
        cfg["assumed"]["init"], embed_gain_log2=0)))
    # sqrt(64) / rms at this width: measured 1.8 (sqrt(2560) / rms published)
    assert own(loud) > 1.5


def test_the_served_dtype_stays_close_and_int8_does_not():
    cfg, w, mcfg, params, forward = world(3, "bfloat16")
    tokens = sequence(40)
    want = reference_logits(w, tokens, np.arange(40), cfg)
    got = np.asarray(forward(params, jnp.asarray(tokens)[None]), np.float32)
    low = reference_logits(w, tokens, np.arange(40), cfg, "int8")
    assert np.abs(got[0] - want).max() < BF16_TOL
    assert np.abs(low - want).max() > np.abs(got[0] - want).max()


@pytest.mark.parametrize("n", [3, 8, 13, 29])
def test_the_prefills_short_cut_is_the_full_forward_at_the_last_position(n):
    """The cross-decoder at the last real position only, over a padded
    prompt shorter than, equal to and longer than the window: the logits
    of the plain forward (every layer, every position) there."""
    cfg, w, mcfg, params, forward = world()
    padded = np.zeros((1, 32), np.int32)
    padded[0, :n] = sequence(n, n)
    row, state = jax.jit(sambay.prefill, static_argnums=0)(
        mcfg, params, jnp.asarray(padded), jnp.int32(n - 1))
    # what follows position n - 1 is nothing it sees: the padded forward
    whole = forward(params, jnp.asarray(padded))
    assert np.abs(np.asarray(row[0]) - np.asarray(whole[0, n - 1])).max() \
        < F32_TOL
    want = reference_logits(w, padded[0], [n - 1], cfg)
    assert np.abs(np.asarray(row[0]) - want[0]).max() < F32_TOL
    assert state["k"].shape == state["v"].shape == (1, 1, 32, 1, 32)
    assert state["k_ring"].shape == (2, 1, 8, 1, 32)
    assert state["ssm"].shape == (3, 1, 4, 128)
    assert state["conv"].shape == (3, 1, 3, 128)


def test_what_is_left_out_shows():
    """Each convention moves the logits by far more than F32_TOL."""
    cfg, w, mcfg, params, forward = world()
    tokens = jnp.asarray(sequence(40))[None]
    base = np.asarray(forward(params, tokens))

    def moved(change):
        p = jax.tree_util.tree_map(lambda a: a, params)
        change(p)
        return np.abs(np.asarray(forward(p, tokens)) - base).max()

    def no_lambda(p):
        for i in (1, 3, 5, 7):
            for n in ("q1", "q2"):
                p[f"layer_{i}"]["attn"]["lambda"][n] = jnp.zeros(8)

    def no_skip(p):
        p["layer_4"]["mixer"]["D"] = jnp.zeros(128)

    def plain_subln(p):
        p["layer_7"]["attn"]["subln"]["scale"] = jnp.ones(16)
    for change in (no_lambda, no_skip, plain_subln):
        assert moved(change) > 30 * F32_TOL, change.__name__
    wide = np.asarray(sambay.forward(
        model(cfg, w, jnp.float32, window=64)[0], params, tokens))
    assert np.abs(wide - base).max() > 30 * F32_TOL


@pytest.mark.parametrize("which", ["decays", "softmax"])
def test_a_lower_precision_shows(which, monkeypatch):
    """The recurrence's decays, or the softmax, in bfloat16 where float32
    is stated: the float32 program leaves F32_TOL by a factor of 10 and
    more (a decay of exp(-0.003) rounds to 1 - 2^-8; a probability to 8
    bits)."""
    cfg, w, mcfg, params, _ = world()
    tokens = sequence(40)
    want = reference_logits(w, tokens, np.arange(40), cfg)
    if which == "decays":
        real = jnp.exp
        monkeypatch.setattr(sambay.mamba1.jnp, "exp", lambda x: real(
            x.astype(jnp.bfloat16)).astype(jnp.float32))
    else:
        real = jax.nn.softmax
        monkeypatch.setattr(sambay.jax.nn, "softmax", lambda x, axis: real(
            x.astype(jnp.bfloat16), axis=axis).astype(jnp.float32))
    got = np.asarray(sambay.forward(mcfg, params, jnp.asarray(tokens)[None]))
    assert np.abs(got[0] - want).max() > 10 * F32_TOL


# -- differential attention against its two-softmax definition ----------------

def two_softmax(q, k, v, lengths, head_dim):
    """The definition, row by row in numpy: q [b, heads, 2 head_dim] packed
    ([q1|0] on even heads, [0|q2] on odd), k and v [b, s, pairs, 2
    head_dim]: each plain head's softmax over its OWN 64-lane key, the
    pair's whole value."""
    b, heads, lanes = q.shape
    pairs = k.shape[2]
    out = np.zeros((b, heads, lanes), np.float64)
    for r in range(b):
        n = int(lengths[r])
        if n == 0:
            continue
        for h in range(heads):
            half = slice(0, head_dim) if h % 2 == 0 else slice(head_dim, None)
            pair = h // (heads // pairs)
            logits = k[r, :n, pair, half].astype(np.float64) \
                @ q[r, h, half].astype(np.float64) * head_dim ** -0.5
            p = np.exp(logits - logits.max())
            out[r, h] = (p / p.sum()) @ v[r, :n, pair].astype(np.float64)
    return out


def packed_case(lengths, s_max, pairs=3, per=4, planes=2, seed=0,
                dtype=jnp.bfloat16):
    rng = np.random.default_rng(seed)
    b = len(lengths)
    k = jnp.asarray(rng.normal(size=(planes, b, s_max, 1, pairs * 128)),
                    dtype)
    v = jnp.asarray(rng.normal(size=(planes, b, s_max, 1, pairs * 128)),
                    dtype)
    q = rng.normal(size=(b, pairs * per, 2, 64))
    q[:, 0::2, 1] = 0.0   # [q1|0]
    q[:, 1::2, 0] = 0.0   # [0|q2]
    return jnp.asarray(q.reshape(b, pairs * per, 128), dtype), k, v, \
        jnp.asarray(lengths, jnp.int32)


@pytest.mark.parametrize("path", ["einsum", "kernel"])
@pytest.mark.parametrize("lengths,s_max", [
    ((0, 1, 100, 128, 129, 300), 384),   # none, one, under / at / over a block
    ((640, 512, 7), 640),                # a ring's row: 512 and a park
])
def test_packed_decode_attention_is_the_two_softmax_definition(
        path, lengths, s_max):
    """Both implementations of ``packed_decode_attention`` (the Mosaic
    kernel interpreted, the einsum) on plane 1 of a cache of three pairs
    against the definition: bfloat16 operands, so 2e-2 of a value of order
    1 (measured 4e-3); a row of length 0 reads nothing."""
    q, k, v, n = packed_case(lengths, s_max)
    if path == "kernel":
        got = fa._packed_decode_attention_kernel(q, k, v, n, 1, 0.125)
    else:
        assert not fa._packed_kernel_selected(k.shape)   # the CPU
        got = fa.packed_decode_attention(q, k, v, n, 1, 0.125)
    assert got.dtype == jnp.float32 and got.shape == q.shape
    want = two_softmax(
        np.asarray(q, np.float32), np.asarray(k[1], np.float32).reshape(
            len(lengths), s_max, 3, 128),
        np.asarray(v[1], np.float32).reshape(len(lengths), s_max, 3, 128),
        lengths, 64)
    live = np.asarray(lengths) > 0
    assert np.abs(np.asarray(got)[live] - want[live]).max() < 2e-2
    if path == "kernel":
        assert not np.asarray(got)[~live].any()


def test_the_kernel_is_selected_from_the_call(monkeypatch):
    """On one TPU chip, rows of whole 128-lane packed heads and whole
    blocks; never on the CPU, for heads of another width, or for rows the
    blocks do not divide."""
    shape = (1, 96, 3072, 1, 1280)
    assert not fa._packed_kernel_selected(shape)
    monkeypatch.setattr(fa.jax, "default_backend", lambda: "tpu")
    assert fa._packed_kernel_selected(shape)
    assert fa._packed_kernel_selected((8, 96, 640, 1, 1280))
    assert not fa._packed_kernel_selected((1, 96, 3072, 1, 1280), lanes=64)
    assert not fa._packed_kernel_selected((1, 96, 3000, 1, 1280))
    assert not fa._packed_kernel_selected((1, 96, 3072, 10, 128))
    with pytest.raises(ValueError, match="packed_decode_attention wants"):
        fa.packed_decode_attention(jnp.zeros((2, 5, 16)),
                                   jnp.zeros((1, 2, 8, 1, 32)),
                                   jnp.zeros((1, 2, 8, 1, 32)),
                                   jnp.ones(2, jnp.int32), 0, 1.0)


@pytest.mark.parametrize("window", [None, 8])
def test_the_packed_forward_is_the_two_softmax_definition(window):
    """``_attend_whole`` and ``_differential`` (packed queries over packed
    pairs, then the subtraction) against the reference's ``differential``
    written with two softmaxes over 8-lane heads, causal and banded."""
    cfg, w, mcfg, params, _ = world()
    i = 1 if window else 5
    p = params[f"layer_{i}"]["attn"]
    y = jnp.asarray(np.random.default_rng(1).normal(size=(1, 24, 64)),
                    jnp.float32)
    q, k, v = sambay._qkv(mcfg, p, y)
    got = sambay._differential(
        mcfg, i, p, sambay._attend_whole(mcfg, q, k, v, window))
    with jax.default_matmul_precision("highest"):
        rq, rk, rv = ref.keys_values(w, f"layers.{i}.", y[0], cfg, None)
        want = ref.differential(w, i, rq, rk, rv, cfg, None, window=window)
    assert np.abs(np.asarray(got[0]) - np.asarray(want)).max() < 1e-5


def test_a_mesh_and_a_wrong_plan_are_refused_by_name():
    with pytest.raises(ValueError, match="a multiple of 4"):
        sambay.check_served(sambay.SambaYConfig.tiny(num_layers=6))
    with pytest.raises(ValueError, match="pairs on both sides"):
        sambay.check_served(sambay.SambaYConfig.tiny(num_kv_heads=3))
    with pytest.raises(ValueError, match="the layer plan is the whole"):
        prog.sambay_config(published(), 8)
    assert serve_decode._own(sambay.SambaYConfig.tiny()) is sambay
