"""The decoder-hybrid-decoder in the ONE cache manager (models/sambay.py
through ``ServeEngine`` / ``KVCache`` / ``serving/decode.py``): a plane
that eight layers read is held once and counted once a reader, rings and
recurrent state beside it, fourteen layers that hold nothing; what the
step record counts of it; and the served path against the plain reference
(benchmarks/reference/phi4flash.py) on logits.

Tolerances as tests/test_sambay_model.py states them (F32_TOL 3e-4: the
program in float32 against the float32 reference; BF16_TOL 0.25).
"""

import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import test_sambay_model as sm
from horovod_tpu.models import sambay
from horovod_tpu.serving import decode as serve_decode
from horovod_tpu.serving import engine as engine_mod
from horovod_tpu.serving.kv_cache import KVCache
from horovod_tpu.serving.queue import AdmissionQueue, Request
from horovod_tpu.utils import metrics as hvd_metrics
from horovod_tpu.utils import tracing as hvd_tracing


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
    cfg = sambay.SambaYConfig.tiny(max_seq_len=64, **kw)
    return cfg, sambay.init_params(cfg, jax.random.PRNGKey(0))


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


def test_the_cache_declares_one_plane_rings_and_state():
    """8 layers: ONE full plane with two readers, two rings of 8 + 1, three
    recurrent states and windows; the gmu and the cross layer hold nothing.
    The gauge says the same bytes."""
    cfg, params = tiny()
    kv = engine_of(cfg, params).kv
    assert serve_decode.positional_kinds(cfg) == kv.positional == \
        ("k", "v", "k_ring", "v_ring")
    assert serve_decode.ring_kinds(cfg) == kv.ring == ("k_ring", "v_ring")
    assert kv.recurrent == ("conv", "ssm") and kv.window == 8
    assert serve_decode.plane_readers(cfg) == kv.readers == 2
    assert kv.k.shape == kv.v.shape == (1, 3, 64, 1, 32)
    assert kv.arrays["k_ring"].shape == (2, 3, 9, 1, 32)
    assert kv.arrays["ssm"].shape == (3, 3, 4, 128)
    assert kv.arrays["ssm"].dtype == jnp.float32
    assert kv.arrays["conv"].shape == (3, 3, 3, 128)
    assert kv.planes == 3
    by_kind = kv.bytes_by_kind()
    assert by_kind == {"k": 3 * 64 * 32 * 4, "v": 3 * 64 * 32 * 4,
                       "k_ring": 2 * 3 * 9 * 32 * 4,
                       "v_ring": 2 * 3 * 9 * 32 * 4,
                       "ssm": 3 * 3 * 4 * 128 * 4,
                       "conv": 3 * 3 * 3 * 128 * 4}
    # held ONCE: a position of one slot is K and V of 32 float32
    assert kv.kv_block_bytes(8) == 8 * 2 * 32 * 4
    assert kv.ring_block_bytes(9) == 9 * 2 * 2 * 32 * 4
    assert kv.row_state_bytes() == 3 * (4 + 3) * 128 * 4
    gauge = hvd_metrics.get_registry().gauge("hvd_serve_state_bytes",
                                             labels=("kind",))
    for kind, nbytes in by_kind.items():
        assert gauge.labels(kind=kind).value == nbytes
    # every other model's planes have one reader each
    from test_serving import MODELS
    for name in ("dense", "hybrid", "looped", "latent_moe"):
        cfg2, _ = MODELS[name]()
        assert serve_decode.plane_readers(cfg2) == 1
        assert serve_decode.prefill_extents(cfg2, 64) == {}
        assert KVCache(cfg2, 2, max_len=48, block_size=8).readers == 1


def test_temp0_matches_no_cache_greedy_as_rows_join_and_retire():
    """Three slots, six requests: rows admitted and retired mid-run (a
    slot's ring, state and window are another request's next), prompts
    shorter and longer than the window (8) and than a block (16), contexts
    that wrap a ring five times; every token the plain forward's greedy
    choice over the growing sequence."""
    cfg, params = tiny()
    engine = engine_of(cfg, params)
    requests = [("a", prompt(5, 1), 30), ("b", prompt(20, 2), 25),
                ("c", prompt(3, 3), 4), ("d", prompt(33, 4), 20),
                ("e", prompt(9, 5), 40), ("f", prompt(17, 6), 2)]
    for rid, p, new in requests:
        assert engine.submit(Request(rid, p, max_new_tokens=new))
    results = {r.request_id: r for r in engine.run_to_completion()}
    forward = jax.jit(lambda toks: sambay.forward(cfg, params, toks))
    for rid, p, new in requests:
        assert results[rid].outcome == "completed"
        seq = np.zeros((1, 64), np.int32)
        seq[0, :len(p) + new] = list(p) + list(results[rid].tokens)
        logits = np.asarray(forward(jnp.asarray(seq)))[0]
        want = logits[len(p) - 1:len(p) + new - 1].argmax(-1)
        assert list(results[rid].tokens) == want.tolist(), rid
    assert engine.kv.ledger.blocks_in_use == 0


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, sm.F32_TOL),
                                       (jnp.bfloat16, sm.BF16_TOL)])
@pytest.mark.parametrize("prompt_len", [5, 13])
def test_prefill_then_decode_through_the_cache_is_the_references_forward(
        dtype, tol, prompt_len):
    """Logits, not tokens: one padded prefill BELOW (5) or ABOVE (13: the
    ring is written wrapped) the window of 8, put into a slot by the
    engine's own ``_write_slot`` over what another request left there, then
    a decode step a token to a context of 45 with the other rows masked
    out, against ONE float32 forward of the plain reference over the whole
    sequence: every layer at every position, no cache, no ring."""
    cfg = sm.tiny_config()
    w = sm.drawn(cfg, seed=8)
    mcfg, params = sm.model(cfg, w, dtype)
    tokens, slots, slot, max_len = sm.sequence(46, 8), 3, 1, 64
    first = np.zeros((1, 16), np.int32)
    first[0, :prompt_len] = tokens[:prompt_len]
    row, state_row = jax.jit(serve_decode.prefill, static_argnums=0)(
        mcfg, params, jnp.asarray(first), jnp.int32(prompt_len - 1))
    assert state_row["k"].shape == (1, 1, 16, 1, 32)
    assert state_row["k_ring"].shape == (2, 1, 8, 1, 32)
    # the slot was someone's: junk of every kind, that must not leak
    rng = np.random.default_rng(0)
    state = {k: jnp.asarray(rng.normal(size=a.shape), a.dtype) for k, a in
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
        logits, state = step(mcfg, params, jnp.asarray(toks),
                             jnp.asarray(pos), state, jnp.asarray(mask))
        got.append(np.asarray(logits[slot]))
    want = sm.reference_logits(w, tokens[:-1],
                               np.arange(prompt_len - 1, len(tokens) - 1),
                               cfg)
    err = np.abs(np.stack(got).astype(np.float32) - want).max(-1)
    assert err.max() < tol, err


def test_a_row_outside_the_mask_keeps_its_state_bit_for_bit():
    """A pass whose mask leaves a row out: its ``ssm`` and ``conv`` are,
    bit for bit, what they were; its K/V writes are parked (``max_len - 1``
    of the plane, index ``window`` of a ring, OUTSIDE the ring's 8
    entries)."""
    cfg, params = tiny()
    rng = np.random.default_rng(0)
    state = {k: jnp.asarray(rng.normal(size=a.shape), jnp.float32)
             for k, a in serve_decode.state_shapes(cfg, 3, 32).items()}
    before = {k: np.asarray(a) for k, a in state.items()}
    toks = jnp.asarray([5, 9, 17])
    pos = jnp.asarray([31, 11, 31])          # rows 0 and 2 parked
    mask = jnp.asarray([False, True, False])
    _, after = serve_decode.decode(cfg, params, toks, pos, state, mask)
    after = {k: np.asarray(a) for k, a in after.items()}
    for kind in ("ssm", "conv"):
        for row in (0, 2):
            np.testing.assert_array_equal(after[kind][:, row],
                                          before[kind][:, row])
        assert (after[kind][:, 1] != before[kind][:, 1]).any()
    for kind in ("k_ring", "v_ring"):
        for row in (0, 2):
            np.testing.assert_array_equal(after[kind][:, row, :8],
                                          before[kind][:, row, :8])
        changed = (after[kind][:, 1] != before[kind][:, 1]).any(axis=(0, 2, 3))
        assert changed.tolist() == [i == 3 for i in range(9)]   # 11 mod 8
    for kind in ("k", "v"):
        for row in (0, 2):
            np.testing.assert_array_equal(after[kind][:, row, :31],
                                          before[kind][:, row, :31])
        changed = (after[kind][:, 1] != before[kind][:, 1]).any(axis=(0, 2, 3))
        assert changed.tolist() == [i == 11 for i in range(32)]


def test_the_step_record_counts_every_reader_of_the_one_plane():
    """``kv_bytes``: the plane in whole blocks up to each row's length,
    once a READER (two here), and the rings up to min(length, window);
    ``shared_kv_bytes`` and ``window_kv_bytes`` its two parts;
    ``state_rows`` and ``state_bytes`` as the other family with a recurrent
    mixer has them; a prefill's two token extents."""
    cfg, params = tiny()
    engine = engine_of(cfg, params, num_slots=2, max_len=64)
    position = 2 * 32 * 4                      # K and V of one plane
    assert engine.kv._reads == [(64, 2 * 64 * position, 64),
                                (9, 9 * 2 * position, 8)]
    first = len(hvd_tracing.get_tracer().steps())
    for rid, p, new in (("a", prompt(5, 1), 6), ("b", prompt(19, 2), 6)):
        engine.submit(Request(rid, p, max_new_tokens=new))
    engine.run_to_completion()
    recs = hvd_tracing.get_tracer().steps()[first:]
    decoded = [r for r in recs if r.get("active")]
    assert decoded
    state = engine.kv.row_state_bytes()
    for r in decoded:
        rows = r["active"]
        assert r["shared_kv_bytes"] == rows * 2 * 64 * position
        assert r["window_kv_bytes"] == rows * 9 * 2 * position
        assert r["kv_bytes"] == r["shared_kv_bytes"] + r["window_kv_bytes"]
        assert r["state_rows"] == rows
        assert r["state_bytes"] == (2 * rows + r["admitted"]) * state
    admitted = [r for r in recs if r.get("admitted")]
    assert sum(r["self_tokens"] for r in admitted) == 16 + 32
    assert sum(r["cross_tokens"] for r in admitted) == 2
    assert sum(r["prompt_tokens"] for r in admitted) == 5 + 19
    # no other family's record has the shared plane's count or the extents
    from test_serving import MODELS, _drive, _engine, _prompt
    for name in ("dense", "hybrid"):
        cfg2, params2 = MODELS[name]()
        _, recs2 = _drive(_engine(cfg2, params2), [("x", _prompt(5, 1), 3)])
        assert not any("shared_kv_bytes" in r or "self_tokens" in r
                       for r in recs2)


def test_a_plane_is_counted_once_a_reader_and_held_once():
    """At the published plan and whole blocks of 128 (the decode kernel's,
    as on the chip): a row of 700 tokens reads 6 blocks of the ONE plane
    eight times and 4 of each of eight rings."""
    class Rec:
        def __init__(self):
            self.counts = {}

        def count(self, name, n):
            self.counts[name] = self.counts.get(name, 0) + n
    cfg = sambay.SambaYConfig.tiny(num_layers=32, window=512)
    kv = KVCache(cfg, 2, max_len=1024, block_size=128)
    assert kv.readers == 8
    assert kv.arrays["k"].shape == (1, 2, 1024, 1, 32)
    assert kv.arrays["k_ring"].shape == (8, 2, 640, 1, 32)
    position = 2 * 32 * 2                      # K and V, bfloat16
    assert kv.per_chip_bytes() == 2 * (1024 + 8 * 640) * position \
        + 9 * 2 * (4 * 128 * 4 + 3 * 128 * 2)
    rec = Rec()
    kv.count_reads(rec, [700, 130])
    assert rec.counts["shared_kv_bytes"] == 8 * (6 + 2) * 128 * position
    assert rec.counts["window_kv_bytes"] == 8 * (4 + 2) * 128 * position
    assert rec.counts["kv_bytes"] == rec.counts["shared_kv_bytes"] \
        + rec.counts["window_kv_bytes"]


def test_the_ledger_prices_a_row_in_tokens_not_in_planes():
    """``hvd_serve_kv_blocks_in_use``: blocks of ``kv_block`` TOKENS a row
    reserved, whatever the model's planes: a request of 5 + 6 tokens holds
    one block of 16, one of 19 + 20 three."""
    cfg, params = tiny()
    engine = engine_of(cfg, params, num_slots=2)
    engine.submit(Request("a", prompt(5, 1), max_new_tokens=6))
    engine.submit(Request("b", prompt(19, 2), max_new_tokens=20))
    engine.step()
    assert engine.kv.ledger.blocks_in_use == 1 + 3
    assert hvd_metrics.get_registry().gauge(
        "hvd_serve_kv_blocks_in_use").value == 4
    engine.run_to_completion()
    assert engine.kv.ledger.blocks_in_use == 0


def test_an_engine_over_a_mesh_is_refused_by_name():
    from horovod_tpu.parallel import mesh as mesh_lib
    cfg, params = tiny()
    mesh = mesh_lib.build_mesh(tp=2)
    with pytest.raises(NotImplementedError, match="recurrent state has no "
                                                  "sharding over a mesh"):
        KVCache(cfg, 2, max_len=32, mesh=mesh)
    with pytest.raises(NotImplementedError):
        engine_of(cfg, params, mesh=mesh)
