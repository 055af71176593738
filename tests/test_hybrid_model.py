"""The hybrid block (models/hybrid.py: a Mamba-2 mixer and grouped-query
attention in parallel, then a SwiGLU) and its scan (ops/ssm.py) against
the plain reference the benchmark keeps (benchmarks/reference/
falcon_h1.py), at tiny sizes on seeded weights with the published
multipliers and the configuration file's seeded-weight rule.

Tolerances, and why each:
  F32_TOL 1e-4   the program computed in float32 against the float32
                 reference: the same arithmetic in another order (chunks,
                 a cache, fused projections); measured 2e-6 at logits of
                 standard deviation 1.
  BF16_TOL 0.12  the program as it is served (bfloat16 activations and
                 weights, float32 state and decays) against the float32
                 reference: 8 bits of mantissa through two layers;
                 measured 0.03-0.05.
Leaving a branch out moves the logits by more than 10 x F32_TOL (far
more: by 0.1 and over), so a missing mixer, attention or SwiGLU cannot
hide inside the tolerance.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import weights
from benchmarks.programs import falcon_h1 as prog
from benchmarks.reference import falcon_h1 as ref
from horovod_tpu.models import hybrid
from horovod_tpu.ops import ssm
from horovod_tpu.serving import decode as serve_decode
from horovod_tpu.serving import engine as engine_mod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F32_TOL, BF16_TOL = 1e-4, 0.12
LAYERS = 2


def tiny_config(**kw):
    """The published configuration file with every width made tiny: the
    multipliers, the init rule and the conventions stay the published
    ones."""
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "falcon-h1-34b.json")) as f:
        cfg = json.load(f)
    cfg.update(hidden_size=64, intermediate_size=128, num_attention_heads=4,
               num_key_value_heads=2, head_dim=16, mamba_n_heads=4,
               mamba_d_head=8, mamba_d_ssm=32, mamba_d_state=16,
               mamba_n_groups=2, mamba_chunk_size=128, vocab_size=256,
               max_position_embeddings=512)
    cfg.update(kw)
    return cfg


def drawn(cfg, seed=5):
    shapes = ref.weight_shapes(cfg, LAYERS)
    return jax.jit(lambda k: weights.make(shapes, k, jnp.bfloat16))(
        weights.seed_key(seed))


def model(cfg, w, dtype):
    hcfg = prog.hybrid_config(cfg, LAYERS, dtype=dtype,
                              attention_impl="full")
    params = jax.jit(lambda w: prog.to_tree(w, LAYERS, cfg))(w)
    if dtype == jnp.float32:
        params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                        params)
    return hcfg, params


_PREFILL = jax.jit(serve_decode.prefill, static_argnums=0)
_DECODE = jax.jit(serve_decode.decode, static_argnums=0)


def served_logits(hcfg, params, tokens, prompt_len, block, max_len,
                  slots=3, slot=1):
    """Teacher-forced logits through the SERVING path: one padded prefill
    written into a slot by the engine's own ``_write_slot``, then one
    decode step a token over all slots, with the other rows masked out."""
    pad = min(-(-prompt_len // block) * block, max_len)
    first = np.zeros((1, pad), np.int32)
    first[0, :prompt_len] = tokens[:prompt_len]
    row, state_row = _PREFILL(hcfg, params, jnp.asarray(first),
                              jnp.int32(prompt_len - 1))
    state = {k: jnp.zeros(a.shape, a.dtype) for k, a in
             serve_decode.state_shapes(hcfg, slots, max_len).items()}
    state, _ = engine_mod._write_slot(state, state_row, jnp.int32(slot),
                                      jnp.zeros(slots, jnp.int32),
                                      jnp.int32(tokens[prompt_len]))
    out = [np.asarray(row[0])]
    mask = np.zeros(slots, bool)
    mask[slot] = True
    for j in range(prompt_len, len(tokens) - 1):
        toks = np.zeros(slots, np.int32)
        pos = np.full(slots, max_len - 1, np.int32)
        toks[slot], pos[slot] = tokens[j], j
        logits, state = _DECODE(hcfg, params, jnp.asarray(toks),
                                jnp.asarray(pos), state, jnp.asarray(mask))
        out.append(np.asarray(logits[slot]))
    return np.stack(out)


def reference_logits(cfg, w, tokens, prompt_len):
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.logits_at(
            w, jnp.asarray(tokens[:-1]),
            jnp.arange(prompt_len - 1, len(tokens) - 1), cfg, LAYERS))


@pytest.mark.parametrize("length", [1, 16, 32, 37, 50])
def test_chunked_scan_is_the_literal_scan(length):
    """Lengths that are and are not multiples of the chunk (16): the pad
    has ``dt == 0``, which holds the state."""
    k = jax.random.split(jax.random.PRNGKey(length), 5)
    bt, h, p, g, n, chunk = 2, 4, 8, 2, 16, 16
    x = jax.random.normal(k[0], (bt, length, h, p))
    dt = jax.nn.softplus(jax.random.normal(k[1], (bt, length, h)) - 2.0)
    a = -jnp.exp(jax.random.normal(k[2], (h,)))
    b = jax.random.normal(k[3], (bt, length, g, n))
    c = jax.random.normal(k[4], (bt, length, g, n))
    want_y, want_s = ssm.literal_scan(x, dt, a, b, c)
    pad = -length % chunk
    padded = [jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
              for t in (x, dt, b, c)]
    got_y, got_s = ssm.chunked_scan(padded[0], padded[1], a, padded[2],
                                    padded[3], chunk)
    # float32 sums in another order, values of order 10
    np.testing.assert_allclose(got_y[:, :length], want_y, atol=2e-4)
    np.testing.assert_allclose(got_s, want_s, atol=2e-4)


def test_chunked_scan_carries_a_state_in():
    k = jax.random.split(jax.random.PRNGKey(0), 5)
    bt, s, h, p, g, n = 1, 48, 4, 8, 2, 16
    x = jax.random.normal(k[0], (bt, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(k[1], (bt, s, h)) - 2.0)
    a = -jnp.exp(jax.random.normal(k[2], (h,)))
    b = jax.random.normal(k[3], (bt, s, g, n))
    c = jax.random.normal(k[4], (bt, s, g, n))
    want_y, want_s = ssm.literal_scan(x, dt, a, b, c)
    _, mid = ssm.literal_scan(x[:, :16], dt[:, :16], a, b[:, :16], c[:, :16])
    got_y, got_s = ssm.chunked_scan(x[:, 16:], dt[:, 16:], a, b[:, 16:],
                                    c[:, 16:], 16, state=mid)
    np.testing.assert_allclose(got_y, want_y[:, 16:], atol=2e-4)
    np.testing.assert_allclose(got_s, want_s, atol=2e-4)


MASKS = {"every_row": None, "some_rows": (True, False, True, True, False),
         "no_row": (False,) * 5}


@pytest.fixture
def update_kernel(monkeypatch):
    """``decode_update`` takes its Mosaic kernel, interpreted: the
    predicate sees a TPU backend, the kernels still see the CPU."""
    monkeypatch.setattr(ssm, "_on_one_tpu_chip", lambda: True)


@pytest.mark.parametrize("impl", ["state_step", "kernel"])
@pytest.mark.parametrize("mask", list(MASKS))
def test_decode_update_is_the_literal_scan_on_the_rows_of_the_mask(
        mask, impl, request):
    """Two tokens through ``decode_update`` on layer 1 of a stacked state
    (8 heads over 2 groups of B and C) against ``literal_scan`` over the
    same two positions: the new state and ``y`` of a row of the mask to
    float32 rounding, the state of a row outside it, and of every other
    layer, BIT-IDENTICAL. Both implementations: the ``jax.numpy`` form
    the CPU runs, and the kernel, interpreted."""
    layers, bt, h, p, g, n = 3, 5, 8, 8, 2, 128
    if impl == "kernel":
        request.getfixturevalue("update_kernel")
    assert ssm._update_kernel_selected(
        (layers, bt, h, p, n), jnp.float32) == (impl == "kernel")
    k = jax.random.split(jax.random.PRNGKey(3), 6)
    start = jax.random.normal(k[0], (layers, bt, h, p, n))
    x = jax.random.normal(k[1], (bt, 2, h, p)).astype(jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(k[2], (bt, 2, h)) - 1.0)
    a = -jnp.exp(jax.random.normal(k[3], (h,)))
    b = jax.random.normal(k[4], (bt, 2, g, n)).astype(jnp.bfloat16)
    c = jax.random.normal(k[5], (bt, 2, g, n)).astype(jnp.bfloat16)
    rows = np.ones(bt, bool) if MASKS[mask] is None else \
        np.asarray(MASKS[mask])
    step = jax.jit(ssm.decode_update, static_argnums=1)
    state, ys = start, []
    for t in range(2):
        state, y = step(state, 1, x[:, t], dt[:, t], a, b[:, t], c[:, t],
                        None if MASKS[mask] is None else jnp.asarray(rows))
        ys.append(y)
    want_y, want_s = ssm.literal_scan(x, dt, a, b, c, state=start[1])
    state, start = np.asarray(state), np.asarray(start)
    np.testing.assert_array_equal(state[[0, 2]], start[[0, 2]])
    np.testing.assert_array_equal(state[1][~rows], start[1][~rows])
    np.testing.assert_allclose(state[1][rows], np.asarray(want_s)[rows],
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.stack(ys, 1)[rows],
                               np.asarray(want_y)[rows], rtol=1e-5,
                               atol=1e-4)
    assert ys[0].shape == (bt, h, p) and ys[0].dtype == jnp.float32


@pytest.mark.parametrize("impl", ["state_step", "kernel"])
@pytest.mark.parametrize("mask", ["some_rows", "no_row"])
def test_decode_leaves_the_state_of_a_row_outside_the_mask_as_it_was(
        mask, impl, request):
    """``hybrid.decode``'s promise, through the whole program: ``ssm`` and
    ``conv`` of a row outside the mask are BIT-IDENTICAL, those of a row
    inside it move; with either update (a state of 128 lanes, so that the
    kernel takes it)."""
    hcfg = hybrid.HybridConfig.tiny(ssm_state=128)
    if impl == "kernel":
        request.getfixturevalue("update_kernel")
    params = hybrid.init_params(hcfg, jax.random.PRNGKey(0))
    slots, max_len = 5, 32
    k = jax.random.split(jax.random.PRNGKey(1), 4)
    state = {kind: jax.random.normal(k[i], a.shape).astype(a.dtype)
             for i, (kind, a) in enumerate(
                 serve_decode.state_shapes(hcfg, slots, max_len).items())}
    rows = np.asarray(MASKS[mask])
    _, after = jax.jit(hybrid.decode, static_argnums=0)(
        hcfg, params, jnp.arange(slots, dtype=jnp.int32),
        jnp.full((slots,), 7, jnp.int32), state, jnp.asarray(rows))
    for kind in ("ssm", "conv"):
        was, now = np.asarray(state[kind]), np.asarray(after[kind])
        np.testing.assert_array_equal(now[:, ~rows], was[:, ~rows])
        assert all((now[:, r] != was[:, r]).any() for r in np.flatnonzero(rows))


@pytest.mark.parametrize("prompt_len", [1, 127, 128, 129, 300])
def test_prefill_then_decode_through_the_cache_is_the_reference(prompt_len):
    """Prefill, then 40 decoded tokens through the cache, against the
    reference's full forward: LOGITS, not tokens, under kv_block 128 (=
    the scan's chunk), for prompts below, at and above a block's edge.
    The prompt is right-padded; the state must be that of its true
    length."""
    cfg = tiny_config()
    w = drawn(cfg)
    hcfg, params = model(cfg, w, jnp.float32)
    rng = np.random.default_rng(prompt_len)
    tokens = rng.integers(0, cfg["vocab_size"], prompt_len + 40)
    with jax.default_matmul_precision("highest"):
        got = served_logits(hcfg, params, tokens, prompt_len, 128, 384)
    want = reference_logits(cfg, w, tokens, prompt_len)
    assert want.std() > 0.5           # logits that say something
    assert got.shape == want.shape == (40, cfg["vocab_size"])
    assert np.abs(got - want).max() < F32_TOL


def test_the_served_precision_is_within_its_tolerance():
    cfg = tiny_config()
    w = drawn(cfg, seed=11)
    hcfg, params = model(cfg, w, jnp.bfloat16)
    tokens = np.random.default_rng(3).integers(0, cfg["vocab_size"], 200)
    got = served_logits(hcfg, params, tokens, 160, 128, 256)
    want = reference_logits(cfg, w, tokens, 160)
    assert F32_TOL < np.abs(got - want).max() < BF16_TOL


@pytest.mark.parametrize("leaf", ["mixer.out_proj", "attn.o", "mlp.down"])
def test_every_branch_matters(leaf):
    """Zeroing one branch of one layer in the PROGRAM moves its logits
    away from the reference by more than ten times the tolerance."""
    cfg = tiny_config()
    w = drawn(cfg)
    broken = dict(w)
    broken["layers.1." + leaf] = jnp.zeros_like(w["layers.1." + leaf])
    hcfg, params = model(cfg, broken, jnp.float32)
    tokens = np.random.default_rng(1).integers(0, cfg["vocab_size"], 60)
    with jax.default_matmul_precision("highest"):
        got = served_logits(hcfg, params, tokens, 40, 128, 128)
    want = reference_logits(cfg, w, tokens, 40)
    assert np.abs(got - want).max() > 10 * F32_TOL


def test_the_init_rule_is_applied_alike_by_program_and_reference():
    cfg = tiny_config()
    w = drawn(cfg)
    tree = prog.to_tree(w, LAYERS, cfg)
    a_log, dt_bias, skip = ref.mixer_vectors(cfg, w["layers.0.mixer.A"],
                                             w["layers.0.mixer.dt"])
    mixer = tree["layer_0"]["mixer"]
    np.testing.assert_allclose(mixer["A_log"], a_log, rtol=1e-6)
    np.testing.assert_allclose(mixer["dt_bias"], dt_bias, rtol=1e-6)
    np.testing.assert_array_equal(mixer["D"], skip)
    a = np.exp(np.asarray(a_log))
    assert a.min() >= 1.0 and a.max() <= 16.0
    np.testing.assert_array_equal(
        np.asarray(tree["layer_0"]["attn"]["k"], np.float32),
        np.asarray(w["layers.0.attn.k"], np.float32)
        * ref.gain(cfg, "layers.0.attn.k"))
    assert ref.gain(cfg, "layers.0.attn.q") == 1.0


@pytest.mark.parametrize("slots,runs_ahead", [(2, True), (4, False)])
def test_the_engine_serves_the_hybrid_model_like_any_other(slots,
                                                           runs_ahead):
    """Through ``ServeEngine`` itself (submit/step, scheduler, queue,
    ledger, the three jitted programs): greedy tokens of prompts of
    several lengths, batched and joined mid-stream, are the reference's
    own greedy continuation - with every slot busy, where a step's ids
    are read a step late and the next pass feeds on the device's own,
    and with a slot kept free, where every pass is read at once."""
    from horovod_tpu.serving import ServeEngine
    from horovod_tpu.serving.queue import Request
    from horovod_tpu.utils import metrics as hvd_metrics
    reg = hvd_metrics.reset(enabled=True)
    cfg = tiny_config(mamba_chunk_size=16)
    w = drawn(cfg)
    hcfg, params = model(cfg, w, jnp.float32)
    rng = np.random.default_rng(7)
    prompts = [tuple(int(t) for t in rng.integers(0, 256, n))
               for n in (5, 16, 23)]
    with jax.default_matmul_precision("highest"):
        engine = ServeEngine(hcfg, params, num_slots=slots, max_len=64,
                             kv_block=16)
        for i, p in enumerate(prompts):
            engine.submit(Request(f"r{i}", p, max_new_tokens=6))
        got = {r.request_id: list(r.tokens)
               for r in engine.run_to_completion()}
        # greedy: each served token is the reference's best at its place
        # (teacher-forced: one reference forward a request)
        for i, p in enumerate(prompts):
            seq = list(p) + got[f"r{i}"]
            logits = ref.logits_at(
                w, jnp.asarray(seq[:-1]),
                jnp.arange(len(p) - 1, len(seq) - 1), cfg, LAYERS)
            assert len(got[f"r{i}"]) == 6
            assert got[f"r{i}"] == [int(t) for t in
                                    jnp.argmax(logits, axis=-1)]
    ahead = reg.snapshot()["metrics"]["hvd_serve_steps_ahead_total"]
    hvd_metrics.reset()
    assert bool(ahead["values"] and ahead["values"][0]["value"]) == \
        runs_ahead
    assert engine.kv.ledger.blocks_in_use == 0
    assert set(engine.kv.arrays) == {"k", "v", "ssm", "conv"}
    assert engine.kv.arrays["k"].shape[3] == hcfg.num_kv_heads
    assert engine.kv.arrays["ssm"].dtype == jnp.float32


def test_state_shapes_are_what_the_model_declares():
    hcfg = hybrid.HybridConfig.tiny()
    shapes = serve_decode.state_shapes(hcfg, 3, 32)
    assert shapes["k"].shape == (2, 3, 32, hcfg.num_kv_heads, hcfg.head_dim)
    assert shapes["ssm"].shape == (2, 3, 4, 8, 16)
    assert shapes["ssm"].dtype == jnp.float32
    assert shapes["conv"].shape == (2, 3, 3, hcfg.conv_dim)
