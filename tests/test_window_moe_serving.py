"""Window and full attention layers in ONE cache manager
(models/window_moe.py through ``ServeEngine`` / ``KVCache`` /
``serving/decode.py``): the third declared class of cache state, a ring a
window layer; what the step record counts of it; and the served path
against the plain reference (benchmarks/reference/laguna.py) on logits.

Tolerances as tests/test_window_moe_model.py states them (F32_TOL 3e-4:
the program in float32 against the float32 reference).
"""

import gc
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import test_window_moe_model as wm
from horovod_tpu.models import window_moe
from horovod_tpu.serving import decode as serve_decode
from horovod_tpu.serving import engine as engine_mod
from horovod_tpu.serving.kv_cache import KVCache
from horovod_tpu.serving.queue import AdmissionQueue, Request
from horovod_tpu.utils import metrics as hvd_metrics
from horovod_tpu.utils import tracing as hvd_tracing

REPO = wm.REPO


@pytest.fixture(autouse=True)
def planes():
    hvd_metrics.reset(enabled=True)
    hvd_tracing.reset(enabled=True, rank=0)
    yield
    hvd_tracing.reset()
    hvd_metrics.reset()
    gc.collect()


def tiny(**kw):
    kw.setdefault("dtype", jnp.float32)
    cfg = window_moe.WindowMoEConfig.tiny(max_seq_len=64, **kw)
    return cfg, window_moe.init_params(cfg, jax.random.PRNGKey(0))


def engine_of(cfg, params, **kw):
    kw.setdefault("num_slots", 3)
    kw.setdefault("max_len", 64)
    kw.setdefault("kv_block", 16)
    kw.setdefault("queue", AdmissionQueue(max_depth=64,
                                          admission_timeout_s=1e9))
    return engine_mod.ServeEngine(cfg, params, **kw)


def prompt(n, seed):
    return tuple(int(t) for t in
                 np.random.default_rng(seed).integers(0, 256, n))


def test_the_cache_declares_three_classes_of_state():
    """Positional at ``max_len`` a row, positional in a ring, recurrent:
    by declaration. Two full planes, three rings of 8 + 1 to park."""
    cfg, params = tiny()
    kv = engine_of(cfg, params).kv
    assert serve_decode.positional_kinds(cfg) == kv.positional == \
        ("k", "v", "k_ring", "v_ring")
    assert serve_decode.ring_kinds(cfg) == kv.ring == ("k_ring", "v_ring")
    assert kv.recurrent == () and kv.window == 8
    assert kv.k.shape == kv.v.shape == (2, 3, 64, 1, 16)
    assert kv.arrays["k_ring"].shape == kv.arrays["v_ring"].shape == \
        (3, 3, 9, 1, 16)
    assert kv.planes == 5
    by_kind = kv.bytes_by_kind()
    assert by_kind == {"k": 2 * 3 * 64 * 16 * 4, "v": 2 * 3 * 64 * 16 * 4,
                       "k_ring": 3 * 3 * 9 * 16 * 4,
                       "v_ring": 3 * 3 * 9 * 16 * 4}
    # a position of one slot: K and V of 16 float32 a plane
    assert kv.kv_block_bytes(8) == 8 * 2 * 2 * 16 * 4
    assert kv.ring_block_bytes(9) == 9 * 3 * 2 * 16 * 4
    assert kv.row_state_bytes() == 0
    # no other model has a ring
    from test_serving import MODELS
    for name in ("dense", "hybrid", "looped", "latent_moe"):
        cfg2, _ = MODELS[name]()
        assert serve_decode.ring_kinds(cfg2) == ()
        other = KVCache(cfg2, 2, max_len=48, block_size=8)
        assert other.ring == () and other.window is None
        assert other.ring_block_bytes(8) == 0


def test_temp0_matches_no_cache_greedy_as_rows_join_and_retire():
    """Three slots, six requests: rows admitted and retired mid-run,
    prompts shorter and longer than the window (8) and than a block (16),
    contexts that wrap a ring up to five times; every token the plain
    forward's greedy choice over the growing sequence."""
    cfg, params = tiny()
    engine = engine_of(cfg, params)
    requests = [("a", prompt(5, 1), 30), ("b", prompt(20, 2), 25),
                ("c", prompt(3, 3), 4), ("d", prompt(33, 4), 20),
                ("e", prompt(9, 5), 40), ("f", prompt(17, 6), 2)]
    for rid, p, new in requests:
        assert engine.submit(Request(rid, p, max_new_tokens=new))
    results = {r.request_id: r for r in engine.run_to_completion()}
    forward = jax.jit(lambda toks: window_moe.forward(cfg, params, toks)[0])
    for rid, p, new in requests:
        assert results[rid].outcome == "completed"
        seq = np.zeros((1, 64), np.int32)
        seq[0, :len(p) + new] = list(p) + list(results[rid].tokens)
        logits = np.asarray(forward(jnp.asarray(seq)))[0]
        want = logits[len(p) - 1:len(p) + new - 1].argmax(-1)
        assert list(results[rid].tokens) == want.tolist(), rid


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, wm.F32_TOL),
                                       (jnp.bfloat16, wm.BF16_TOL)])
def test_the_engines_tokens_lie_at_the_top_of_the_references_logits(dtype,
                                                                    tol):
    """The cell's own served check at a tiny size: requests through
    ``ServeEngine`` and the two-class cache (admitted and retired mid-run,
    a context of 49 that wraps the ring six times, one of 7 that never
    fills it), then ONE float32 forward of the plain reference over prompt
    and served tokens: every served token's reference logit within ``tol``
    of the reference's best (in bfloat16: 85% of them, the cell's
    statistic; a token routed elsewhere is a near tie's)."""
    cfg = wm.tiny_config()
    w = wm.drawn(cfg, seed=8)
    mcfg, params = wm.model(cfg, w, dtype, max_seq_len=64)
    engine = engine_of(mcfg, params)
    requests = [("long", prompt(19, 1), 30), ("short", prompt(4, 2), 3),
                ("mid", prompt(11, 3), 12), ("late", prompt(6, 4), 9)]
    for rid, p, new in requests:
        assert engine.submit(Request(rid, p, max_new_tokens=new))
    results = {r.request_id: r for r in engine.run_to_completion()}
    gaps = []
    for rid, p, new in requests:
        toks = list(results[rid].tokens)
        assert len(toks) == new
        seq = np.asarray(list(p) + toks, np.int32)
        with jax.default_matmul_precision("highest"):
            logits = np.asarray(wm.ref.logits_at(
                w, jnp.asarray(seq[:-1]),
                jnp.arange(len(p) - 1, len(seq) - 1), cfg, wm.LAYERS))
        gaps.append(logits.max(-1) - logits[np.arange(new), toks])
    gaps = np.concatenate(gaps)
    assert np.quantile(gaps, 0.85, method="higher") <= tol, gaps
    if dtype == jnp.float32:
        assert gaps.max() <= tol


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, wm.F32_TOL),
                                       (jnp.bfloat16, wm.BF16_TOL)])
def test_prefill_then_decode_through_the_cache_is_the_references_forward(
        dtype, tol):
    """Logits, not tokens: one padded prefill of 13 tokens (longer than
    the window: the ring is written wrapped) put into a slot by the
    engine's own ``_write_slot``, then a decode step a token to a context
    of 45 (the ring wraps four times more) with the other rows masked out,
    against ONE float32 forward of the plain reference over the whole
    sequence: an explicit [s, s] mask, no cache, no ring."""
    cfg = wm.tiny_config()
    w = wm.drawn(cfg, seed=8)
    mcfg, params = wm.model(cfg, w, dtype, max_seq_len=64)
    tokens, prompt_len, slots, slot, max_len = wm.sequence(46, 8), 13, 3, 1, 64
    first = np.zeros((1, 16), np.int32)
    first[0, :prompt_len] = tokens[:prompt_len]
    row, state_row = jax.jit(serve_decode.prefill, static_argnums=0)(
        mcfg, params, jnp.asarray(first), jnp.int32(prompt_len - 1))
    assert state_row["k"].shape == (2, 1, 16, 1, 16)
    assert state_row["k_ring"].shape == (3, 1, 8, 1, 16)
    state = {k: jnp.zeros(a.shape, a.dtype) for k, a in
             serve_decode.state_shapes(mcfg, slots, max_len).items()}
    state, _ = engine_mod._write_slot(
        state, state_row, jnp.int32(slot), jnp.zeros(slots, jnp.int32),
        jnp.int32(tokens[prompt_len]))
    step = jax.jit(serve_decode.decode, static_argnums=0)
    got = [np.asarray(row[0])]
    mask = np.zeros(slots, bool)
    mask[slot] = True
    for j in range(prompt_len, len(tokens) - 1):
        toks = np.zeros(slots, np.int32)
        pos = np.full(slots, max_len - 1, np.int32)
        toks[slot], pos[slot] = tokens[j], j
        logits, state, routed = step(
            mcfg, params, jnp.asarray(toks), jnp.asarray(pos), state,
            jnp.asarray(mask))
        assert routed.tolist() == [16, 1]      # one row, 4 layers x 4
        got.append(np.asarray(logits[slot]))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(wm.ref.logits_at(
            w, jnp.asarray(tokens[:-1]),
            jnp.arange(prompt_len - 1, len(tokens) - 1), cfg, wm.LAYERS))
    err = np.abs(np.stack(got).astype(np.float32) - want).max(-1)
    if dtype == jnp.float32:
        assert err.max() < tol, err
    else:   # a token that bfloat16 routes elsewhere is a near tie's
        assert np.quantile(err, 0.85, method="higher") < tol, err


def test_a_row_outside_the_mask_keeps_its_ring_bit_for_bit():
    """A pass whose mask leaves a row out writes that row's K/V where the
    engine parks it in a full plane (``max_len - 1``) and at index
    ``window`` of a ring, OUTSIDE the ring's 8 entries: those are, bit for
    bit, what they were; the row reads nothing and is routed nowhere."""
    cfg, params = tiny()
    rng = np.random.default_rng(0)
    state = {k: jnp.asarray(rng.normal(size=a.shape), jnp.float32)
             for k, a in serve_decode.state_shapes(cfg, 3, 32).items()}
    before = {k: np.asarray(a) for k, a in state.items()}
    toks = jnp.asarray([5, 9, 17])
    pos = jnp.asarray([31, 11, 31])          # rows 0 and 2 parked
    mask = jnp.asarray([False, True, False])
    _, after, routed = serve_decode.decode(cfg, params, toks, pos, state,
                                           mask)
    after = {k: np.asarray(a) for k, a in after.items()}
    assert routed.tolist() == [16, 1]
    for kind in ("k_ring", "v_ring"):
        for row in (0, 2):
            np.testing.assert_array_equal(after[kind][:, row, :8],
                                          before[kind][:, row, :8])
            assert (after[kind][:, row, 8] != before[kind][:, row, 8]).any()
        # the decoding row: position 11 went to entry 11 mod 8 and no other
        changed = (after[kind][:, 1] != before[kind][:, 1]).any(axis=(0, 2, 3))
        assert changed.tolist() == [i == 3 for i in range(9)]
    for kind in ("k", "v"):
        for row in (0, 2):
            np.testing.assert_array_equal(after[kind][:, row, :31],
                                          before[kind][:, row, :31])
        changed = (after[kind][:, 1] != before[kind][:, 1]).any(axis=(0, 2, 3))
        assert changed.tolist() == [i == 11 for i in range(32)]
    # with every row in the pass, rows 0 and 2 write entry 31 mod 8 = 7
    _, full, _ = serve_decode.decode(cfg, params, toks, pos, state, None)
    ring = np.asarray(full["k_ring"])
    assert (ring[:, 0, 7] != before["k_ring"][:, 0, 7]).any()
    np.testing.assert_array_equal(ring[:, 0, 8], before["k_ring"][:, 0, 8])


def test_the_step_record_counts_both_classes():
    """``kv_bytes``: each decoding row's full planes in whole blocks up to
    its length and its rings up to min(length, window);
    ``window_kv_bytes``: the rings' part; ``experts_touched`` and
    ``expert_tokens_max`` as the other family with experts has them."""
    cfg, params = tiny()
    engine = engine_of(cfg, params, num_slots=2, max_len=64)
    position = 2 * 16 * 4                      # K and V of one plane
    assert engine.kv._reads == [(64, 64 * 2 * position, 64),
                                (9, 9 * 3 * position, 8)]
    first = len(hvd_tracing.get_tracer().steps())
    for rid, p, new in (("a", prompt(5, 1), 6), ("b", prompt(19, 2), 6)):
        engine.submit(Request(rid, p, max_new_tokens=new))
    engine.run_to_completion()
    recs = hvd_tracing.get_tracer().steps()[first:]
    decoded = [r for r in recs if r.get("active")]
    assert decoded
    for r in decoded:
        # a row is one block of 64 in a full plane and one of 9 in a ring
        rows = r["active"]
        assert r["window_kv_bytes"] == rows * 9 * 3 * position
        assert r["kv_bytes"] == rows * (64 * 2 + 9 * 3) * position
    assert any("experts_touched" in r for r in recs)
    for r in recs:
        if "experts_touched" in r:
            assert 4 <= r["experts_touched"] <= 4 * 8
            assert 1 <= r["expert_tokens_max"] <= 2
    # no other family's record has the ring's count
    from test_serving import MODELS, _drive, _engine, _prompt
    for name in ("dense", "hybrid", "looped", "latent_moe"):
        cfg2, params2 = MODELS[name]()
        _, recs2 = _drive(_engine(cfg2, params2), [("x", _prompt(5, 1), 3)])
        assert not any("window_kv_bytes" in r for r in recs2)
        assert any("kv_bytes" in r for r in recs2)


def test_a_ring_counts_its_window_and_no_more():
    """At whole blocks of 128 (the decode kernel's, as on the chip): a row
    of 700 tokens reads 6 blocks of each full plane and 4 of each ring, a
    row of 130 two and two."""
    class Rec:
        def __init__(self):
            self.counts = {}

        def count(self, name, n):
            self.counts[name] = self.counts.get(name, 0) + n
    cfg = window_moe.WindowMoEConfig.tiny(window=512, num_kv_heads=2,
                                          heads_per_layer=(4, 6, 6, 6, 4))
    kv = KVCache(cfg, 2, max_len=1024, block_size=128)
    assert kv.arrays["k_ring"].shape == (3, 2, 640, 2, 16)
    position = 2 * 2 * 16 * 2                  # K and V, bfloat16
    rec = Rec()
    kv.count_reads(rec, [700, 130])
    assert rec.counts["window_kv_bytes"] == (4 + 2) * 128 * 3 * position
    assert rec.counts["kv_bytes"] == (6 + 2) * 128 * 2 * position \
        + rec.counts["window_kv_bytes"]
    # what 3 x sum(min(length, 512)) entries hold, in whole blocks
    assert rec.counts["window_kv_bytes"] >= \
        3 * (512 + 130) * position
    assert rec.counts["window_kv_bytes"] < \
        3 * (512 + 130 + 128) * position


CELLS = {  # cell -> (family, builder of the model's configuration, bytes
           # of K/V, or of the latent, that one position of one slot holds
           # over all planes)
    "baichuan7b-serve-closed": ("baichuan", "transformer_config",
                                10 * 2 * 32 * 128 * 2),
    "falconh1-34b-serve-closed": ("falcon_h1", "hybrid_config",
                                  6 * 2 * 4 * 128 * 2),
    "ouro2.6b-serve-closed": ("ouro", "looped_config",
                              6 * 4 * 2 * 16 * 128 * 2),
    "glm4.7flash-serve-closed": ("glm_moe_lite", "latent_moe_config",
                                 7 * 640 * 2),
}


@pytest.mark.parametrize("cell", list(CELLS))
def test_the_accepted_cells_count_the_kv_bytes_they_counted(cell):
    """``attn.kv_bytes_per_step`` has no ``workloads`` list: it reads the
    new cell too, and is UNCHANGED on the four accepted serving cells. At
    each cell's published widths and depth (one slot of 256, which the
    count does not depend on): a block of 128 positions is what it was,
    from the shapes, and a step's ``kv_bytes`` is blocks x that."""
    from benchmarks.lib import registry as registry_mod
    reg = registry_mod.Registry([REPO])
    bench = reg.benchmark()
    family, builder, position = CELLS[cell]
    spec = registry_mod.cell_of(bench, cell)
    traffic = reg.data("traffic", spec["traffic"])
    conf = next(c for c in bench["configs"] if c["name"] == spec["config"])
    with open(os.path.join(REPO, conf["file"])) as f:
        config = json.load(f)
    adapter = reg.module("programs", family)
    mcfg = getattr(adapter, builder)(config, adapter.depth(config, traffic))
    kv = KVCache(mcfg, 1, max_len=256, block_size=128)
    assert kv.ring == () and kv.window is None
    assert kv.kv_block_bytes(128) == 128 * position
    assert kv.planes == kv.arrays[kv.positional[0]].shape[0]

    class Rec(dict):
        def count(self, name, n):
            self[name] = self.get(name, 0) + n
    rec = Rec()
    kv.count_reads(rec, [1, 128, 129, 256])
    assert rec == {"kv_bytes": (1 + 1 + 2 + 2) * 128 * position}


def test_the_new_cell_counts_both_classes_at_its_widths():
    from benchmarks.lib import registry as registry_mod
    reg = registry_mod.Registry([REPO])
    adapter = reg.module("programs", "laguna")
    mcfg = adapter.window_moe_config(wm.published(), 5)
    kv = KVCache(mcfg, 1, max_len=1024, block_size=512)
    # 4,096 B a token a plane: two full planes, three rings
    assert kv.kv_block_bytes(128) == 128 * 2 * 4096
    assert kv.ring_block_bytes(128) == 128 * 3 * 4096
    assert kv.planes == 5


def test_an_engine_over_a_mesh_is_refused_by_name():
    from horovod_tpu.parallel import mesh as mesh_lib
    cfg, params = tiny()
    mesh = mesh_lib.build_mesh(tp=2)
    with pytest.raises(NotImplementedError,
                       match="WindowMoEConfig serves on one chip"):
        engine_of(cfg, params, mesh=mesh)
    with pytest.raises(NotImplementedError,
                       match="WindowMoEConfig's cache .k, v, k_ring, v_ring. "
                             "has no sharding over a mesh"):
        KVCache(cfg, 2, max_len=32, mesh=mesh)


def test_the_router_is_the_references():
    """``router_score`` (the program's) and ``routing`` (the reference's)
    on the same normed input: the same experts, weights within ROUTE_TOL
    that sum to the routed scale."""
    cfg = wm.tiny_config()
    w = wm.drawn(cfg)
    mcfg, params = wm.model(cfg, w, jnp.float32)
    y = jnp.asarray(np.random.default_rng(2).normal(size=(24, 64)),
                    jnp.float32)
    idx, wts = window_moe.router_score(
        mcfg, y, params["layer_1"]["router"]["kernel"])
    with jax.default_matmul_precision("highest"):
        ridx, rwts, scores = wm.ref.routing(w, "layers.1.", y, cfg)
    np.testing.assert_array_equal(np.sort(np.asarray(idx), -1),
                                  np.sort(np.asarray(ridx), -1))
    dense = np.zeros((2, 24, 16), np.float32)
    np.put_along_axis(dense[0], np.asarray(idx), np.asarray(wts), -1)
    np.put_along_axis(dense[1], np.asarray(ridx), np.asarray(rwts), -1)
    np.testing.assert_allclose(dense[0], dense[1], atol=wm.ROUTE_TOL)
    np.testing.assert_allclose(dense[0].sum(-1), 2.5, atol=1e-5)
    assert scores.shape == (24, 16)
