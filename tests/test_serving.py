"""Serving plane (horovod_tpu/serving/): scheduler join/retire
invariants, KV block-ledger accounting (no leaks, loud double-free),
admission control, SLO metric emission, and the engine end-to-end —
including temp-0 parity between the KV-cached engine and a no-cache
greedy reference over the same model."""

import os

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax
import jax.numpy as jnp

from horovod_tpu.models import transformer as tr
from horovod_tpu.serving.kv_cache import BlockLedger, KVCache
from horovod_tpu.serving.queue import AdmissionQueue, Request
from horovod_tpu.serving.scheduler import SlotScheduler
from horovod_tpu.utils import metrics as hvd_metrics


@pytest.fixture
def reg():
    r = hvd_metrics.reset(enabled=True)
    yield r
    hvd_metrics.reset()



def _value(snap, name, **labels):
    fam = snap["metrics"].get(name)
    if fam is None:
        return None
    for v in fam["values"]:
        if all(v["labels"].get(k) == lv for k, lv in labels.items()):
            return v.get("value", v.get("count"))
    return None


def _events(snap, kind):
    return [e for e in snap["events"] if e["event"] == kind]


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


# ---------------------------------------------------------------------------
# SlotScheduler
# ---------------------------------------------------------------------------

class TestSlotScheduler:
    def test_join_assigns_each_slot_once(self):
        s = SlotScheduler(3)
        slots = [s.join(f"r{i}") for i in range(3)]
        assert sorted(slots) == [0, 1, 2]
        assert not s.can_join()
        with pytest.raises(RuntimeError):
            s.join("overflow")

    def test_retire_frees_for_immediate_reuse(self):
        s = SlotScheduler(2)
        a = s.join("a")
        s.join("b")
        s.retire(a)
        assert s.can_join()
        assert s.join("c") == a
        assert s.active[a] == "c"

    def test_retire_inactive_slot_raises(self):
        s = SlotScheduler(2)
        with pytest.raises(KeyError):
            s.retire(0)

    def test_continuous_joins_mid_wave(self):
        s = SlotScheduler(2, policy="continuous")
        s.join("a")
        s.begin_wave()
        assert s.can_join()  # the whole point of continuous batching

    def test_drain_blocks_joins_until_batch_empties(self):
        s = SlotScheduler(2, policy="drain")
        a = s.join("a")
        s.begin_wave()
        assert not s.can_join()  # wave started, one slot still free
        with pytest.raises(RuntimeError):
            s.join("b")
        s.retire(a)  # batch empty -> next wave may fill
        assert s.can_join()
        s.join("b")

    def test_begin_wave_on_empty_batch_is_noop(self):
        s = SlotScheduler(1, policy="drain")
        s.begin_wave()
        assert s.can_join()

    def test_rejects_bad_policy_and_size(self):
        with pytest.raises(ValueError):
            SlotScheduler(2, policy="paged")
        with pytest.raises(ValueError):
            SlotScheduler(0)


# ---------------------------------------------------------------------------
# BlockLedger
# ---------------------------------------------------------------------------

class TestBlockLedger:
    def test_alloc_grow_free_roundtrip_no_leak(self):
        led = BlockLedger(2, max_len=32, block_size=8)
        slot = led.alloc(5)
        assert slot is not None
        assert led.blocks_in_use == 1  # ceil(5/8)
        assert led.grow(slot, 9)  # crosses into block 2
        assert led.blocks_in_use == 2
        assert led.length(slot) == 9
        led.free(slot)
        assert led.blocks_in_use == 0
        assert led.free_slots == 2

    def test_budget_refuses_oversubscription(self):
        # 2 slots but budget for only 3 blocks of 8
        led = BlockLedger(2, max_len=32, block_size=8, total_blocks=3)
        a = led.alloc(16)  # 2 blocks
        assert a is not None
        assert led.can_alloc(8)
        assert not led.can_alloc(9)  # would need 2, only 1 left
        b = led.alloc(8)
        assert b is not None
        assert not led.grow(b, 9)  # grow refused at budget...
        assert led.length(b) == 8  # ...and state unchanged
        led.free(a)
        assert led.grow(b, 9)  # budget freed -> grow succeeds

    def test_grow_refuses_past_max_len(self):
        led = BlockLedger(1, max_len=16, block_size=8)
        slot = led.alloc(8)
        assert led.grow(slot, 16)
        assert not led.grow(slot, 17)

    def test_double_free_and_unknown_grow_raise(self):
        led = BlockLedger(1, max_len=16, block_size=8)
        slot = led.alloc(4)
        led.free(slot)
        with pytest.raises(KeyError):
            led.free(slot)
        with pytest.raises(KeyError):
            led.grow(slot, 8)

    def test_alloc_at_claims_specific_slot(self):
        led = BlockLedger(3, max_len=16, block_size=8)
        led.alloc_at(1, 4)
        assert led.length(1) == 4
        with pytest.raises(KeyError):
            led.alloc_at(1, 4)  # taken: scheduler/ledger desync
        with pytest.raises(KeyError):
            led.alloc_at(7, 4)  # no such slot
        led2 = BlockLedger(2, max_len=16, block_size=8, total_blocks=1)
        led2.alloc_at(0, 8)
        with pytest.raises(RuntimeError):
            led2.alloc_at(1, 8)  # over budget

    def test_kv_cache_shapes_follow_config(self):
        cfg = tr.TransformerConfig.tiny(dtype=jnp.float32)
        kv = KVCache(cfg, num_slots=3, max_len=32, block_size=8)
        head_dim = cfg.d_model // cfg.num_heads
        assert kv.k.shape == (cfg.num_layers, 3, 32, cfg.num_heads,
                              head_dim)
        assert kv.k.dtype == cfg.dtype
        assert kv.num_slots == 3


# ---------------------------------------------------------------------------
# AdmissionQueue
# ---------------------------------------------------------------------------

class TestAdmissionQueue:
    def test_rejects_loudly_when_full(self, reg):
        clock = FakeClock()
        q = AdmissionQueue(max_depth=1, admission_timeout_s=10.0,
                           clock=clock)
        assert q.submit(Request("a", (1,)))
        assert not q.submit(Request("b", (1,)))
        snap = reg.snapshot()
        assert _value(snap, "hvd_serve_requests_total",
                      outcome="rejected") == 1.0
        assert any(e["reason"] == "queue_full"
                   for e in _events(snap, "serve_reject"))

    def test_pop_rejects_deadline_expired(self, reg):
        clock = FakeClock()
        q = AdmissionQueue(max_depth=8, admission_timeout_s=5.0,
                           clock=clock)
        q.submit(Request("stale", (1,), deadline_s=1.0))
        q.submit(Request("fresh", (1,)))
        clock.t = 2.0  # past stale's own deadline, inside queue timeout
        got = q.pop()
        assert got.request_id == "fresh"
        snap = reg.snapshot()
        assert any(e["request_id"] == "stale" and
                   e["reason"] == "deadline"
                   for e in _events(snap, "serve_reject"))
        assert q.pop() is None

    def test_requeue_goes_to_head(self, reg):
        q = AdmissionQueue(max_depth=2, admission_timeout_s=10.0)
        q.submit(Request("a", (1,)))
        q.submit(Request("b", (1,)))
        first = q.pop()
        q.requeue(first)  # cache pressure: back to the head, not tail
        assert q.pop().request_id == "a"
        assert q.pop().request_id == "b"

    def test_depth_gauge_tracks_queue(self, reg):
        q = AdmissionQueue(max_depth=4, admission_timeout_s=10.0)
        q.submit(Request("a", (1,)))
        q.submit(Request("b", (1,)))
        snap = reg.snapshot()
        assert _value(snap, "hvd_serve_queue_depth") == 2.0


# ---------------------------------------------------------------------------
# ServeEngine end-to-end (CPU, tiny fp32 config)
# ---------------------------------------------------------------------------

def _tiny():
    cfg = tr.TransformerConfig.tiny(dtype=jnp.float32,
                                    attention_impl="full")
    _, params = tr.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _greedy_reference(cfg, params, prompt, n_new):
    """No-cache greedy decode: full forward over the growing sequence
    every step — the oracle the KV-cached engine must match."""
    model = tr.TransformerLM(cfg)
    toks = list(prompt)
    out = []
    for _ in range(n_new):
        logits = model.apply({"params": params},
                             jnp.asarray([toks], jnp.int32))
        nxt = int(jnp.argmax(logits[0, -1]))
        out.append(nxt)
        toks.append(nxt)
    return out


def _engine(cfg, params, **kw):
    from horovod_tpu.serving.engine import ServeEngine
    kw.setdefault("num_slots", 2)
    kw.setdefault("max_len", 48)
    kw.setdefault("kv_block", 8)
    kw.setdefault("queue", AdmissionQueue(max_depth=64,
                                          admission_timeout_s=1e9))
    return ServeEngine(cfg, params, **kw)


class TestServeEngine:
    def test_temp0_matches_no_cache_greedy(self, reg):
        cfg, params = _tiny()
        engine = _engine(cfg, params)
        prompts = [(5, 9, 17), (4, 8, 15, 16, 23, 42)]
        for i, p in enumerate(prompts):
            engine.submit(Request(f"r{i}", p, max_new_tokens=10))
        results = {r.request_id: r
                   for r in engine.run_to_completion()}
        assert len(results) == 2
        for i, p in enumerate(prompts):
            r = results[f"r{i}"]
            assert r.outcome == "completed"
            assert list(r.tokens) == _greedy_reference(cfg, params, p, 10)
        assert engine.kv.ledger.blocks_in_use == 0
        assert engine.active_count == 0

    def test_the_cache_is_updated_in_place(self, reg):
        """The arrays that go into a slot write or a decode step are
        donated: dead after the call, the engine's current ones live,
        and another engine's cache untouched."""
        cfg, params = _tiny()
        engine, other = _engine(cfg, params), _engine(cfg, params)
        other_k, other_v = other.kv.k, other.kv.v
        assert _value(reg.snapshot(), "hvd_serve_kv_in_place") is None
        k0, v0 = engine.kv.k, engine.kv.v  # go into _write_slot
        engine.submit(Request("r", (5, 9, 17), max_new_tokens=6))
        engine.step()
        k1, v1 = engine.kv.k, engine.kv.v  # go into _decode_jit
        engine.step()
        for dead in (k0, v0, k1, v1):
            assert dead.is_deleted()
        for live in (engine.kv.k, engine.kv.v):
            assert not live.is_deleted()
        assert engine.kv.k.shape == k0.shape
        assert engine.kv.per_chip_bytes() == other.kv.per_chip_bytes()
        assert _value(reg.snapshot(), "hvd_serve_kv_in_place") == 1
        assert other.kv.k is other_k and other.kv.v is other_v
        assert not np.asarray(other_k).any()
        assert not np.asarray(other_v).any()
        r, = engine.run_to_completion()
        assert list(r.tokens) == _greedy_reference(cfg, params,
                                                   (5, 9, 17), 6)

    def test_a_dropped_donation_is_named(self, reg, monkeypatch, caplog):
        """A program that copies the cache it was given (here: the slot
        write with its donation taken away) reads 0 on the gauge and
        warns once; the tokens are the same either way."""
        from horovod_tpu.serving import engine as engine_mod
        monkeypatch.setattr(
            engine_mod, "_write_slot",
            jax.jit(engine_mod._write_slot.__wrapped__))
        cfg, params = _tiny()
        engine = _engine(cfg, params)
        k0 = engine.kv.k
        for i in range(2):
            engine.submit(Request(f"r{i}", (5, 9, 17), max_new_tokens=4))
        with caplog.at_level("WARNING", logger="horovod_tpu.serving"):
            results = engine.run_to_completion()
        assert not k0.is_deleted()
        assert _value(reg.snapshot(), "hvd_serve_kv_in_place") == 0
        warned = [r for r in caplog.records
                  if "did not consume the KV cache" in r.getMessage()]
        assert len(warned) == 1 and "write_slot" in warned[0].getMessage()
        want = _greedy_reference(cfg, params, (5, 9, 17), 4)
        assert [list(r.tokens) for r in results] == [want, want]

    def test_continuous_join_mid_stream_and_no_leaks(self, reg):
        cfg, params = _tiny()
        engine = _engine(cfg, params, num_slots=2)
        engine.submit(Request("long", (1, 2, 3), max_new_tokens=20))
        engine.submit(Request("s0", (4, 5), max_new_tokens=3))
        done = []
        for step in range(200):
            if step == 4:  # joins while "long" is mid-decode
                engine.submit(Request("s1", (6, 7), max_new_tokens=3))
            done.extend(engine.step())
            if len(done) == 3 and not engine.active_count:
                break
        by_id = {r.request_id: r for r in done}
        assert set(by_id) == {"long", "s0", "s1"}
        assert all(r.outcome == "completed" for r in done)
        # the short late joiner finished before the long early one:
        # continuous batching's observable win
        order = [r.request_id for r in done]
        assert order.index("s1") < order.index("long")
        assert engine.kv.ledger.blocks_in_use == 0

    def test_drain_policy_completes_in_waves(self, reg):
        cfg, params = _tiny()
        engine = _engine(cfg, params, num_slots=2, policy="drain")
        for i in range(4):
            engine.submit(Request(f"r{i}", (1, 2), max_new_tokens=4))
        results = engine.run_to_completion()
        assert len(results) == 4
        assert all(r.outcome == "completed" for r in results)
        assert engine.kv.ledger.blocks_in_use == 0

    def test_too_long_request_fails_at_admission(self, reg):
        cfg, params = _tiny()
        engine = _engine(cfg, params, max_len=16)
        engine.submit(Request("huge", tuple(range(1, 13)),
                              max_new_tokens=8))  # 12 + 7 > 16
        results = engine.run_to_completion()
        assert [(r.outcome, r.reason) for r in results] == \
            [("failed", "too_long")]
        assert engine.kv.ledger.blocks_in_use == 0

    def test_cache_pressure_requeues_until_blocks_free(self, reg):
        cfg, params = _tiny()
        # budget fits one 2-block request at a time
        engine = _engine(cfg, params, num_slots=2, max_len=16,
                         total_blocks=2)
        engine.submit(Request("a", tuple(range(1, 9)), max_new_tokens=4))
        engine.submit(Request("b", tuple(range(1, 9)), max_new_tokens=4))
        results = engine.run_to_completion()
        assert sorted(r.request_id for r in results) == ["a", "b"]
        assert all(r.outcome == "completed" for r in results)
        assert engine.kv.ledger.blocks_in_use == 0

    def test_deadline_mid_decode_fails_loudly(self, reg):
        cfg, params = _tiny()
        clock = FakeClock()
        queue = AdmissionQueue(max_depth=8, admission_timeout_s=1e9,
                               clock=clock)
        engine = _engine(cfg, params, queue=queue, clock=clock)
        engine.submit(Request("slow", (1, 2), max_new_tokens=20,
                              deadline_s=5.0))
        engine.step()  # prefill + first decode, t=0
        clock.t = 6.0  # blow the deadline mid-stream
        results = []
        for _ in range(5):
            results.extend(engine.step())
            if results:
                break
        assert [(r.outcome, r.reason) for r in results] == \
            [("failed", "deadline")]
        assert engine.kv.ledger.blocks_in_use == 0

    def test_slo_metrics_emitted(self, reg):
        cfg, params = _tiny()
        engine = _engine(cfg, params)
        engine.submit(Request("a", (3, 1, 4), max_new_tokens=5))
        engine.run_to_completion()
        snap = reg.snapshot()
        for want in ("hvd_serve_requests_total",
                     "hvd_serve_tokens_total",
                     "hvd_serve_ttft_seconds",
                     "hvd_serve_intertoken_seconds",
                     "hvd_serve_active_slots",
                     "hvd_serve_kv_blocks_in_use",
                     "hvd_serve_queue_depth"):
            assert want in snap["metrics"], want
        assert _value(snap, "hvd_serve_requests_total",
                      outcome="completed") == 1.0
        assert _value(snap, "hvd_serve_tokens_total",
                      phase="decode") == 5.0
        # histograms carry observations: TTFT once, intertoken 4x
        assert _value(snap, "hvd_serve_ttft_seconds") == 1
        assert _value(snap, "hvd_serve_intertoken_seconds") == 4
        kinds = {e["event"] for e in snap["events"]}
        assert {"serve_admit", "serve_retire"} <= kinds
