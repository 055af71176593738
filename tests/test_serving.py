"""Serving plane (horovod_tpu/serving/): scheduler join/retire
invariants, KV block-ledger accounting (no leaks, loud double-free),
admission control, SLO metric emission, and the engine end-to-end —
including temp-0 parity between the KV-cached engine and a no-cache
greedy reference over the same model."""

import dataclasses
import logging
import os

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax
import jax.numpy as jnp

from horovod_tpu.models import hybrid
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
        # the handler goes on the ``horovod_tpu`` logger itself:
        # ``hvd_logging.get_logger()`` stops it propagating to the root
        # at its first use, so whether the root's handler sees the record
        # depended on which tests ran before on this worker
        hvd_logger = logging.getLogger("horovod_tpu")
        hvd_logger.addHandler(caplog.handler)
        try:
            with caplog.at_level("WARNING", logger="horovod_tpu.serving"):
                results = engine.run_to_completion()
        finally:
            hvd_logger.removeHandler(caplog.handler)
        assert not k0.is_deleted()
        assert _value(reg.snapshot(), "hvd_serve_kv_in_place") == 0
        warned = {id(r): r for r in caplog.records
                  if "did not consume the KV cache" in r.getMessage()}
        assert len(warned) == 1   # (one record, by whichever handlers)
        said, = (r.getMessage() for r in warned.values())
        assert "write_slot" in said and "k, v" in said
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

    def test_continuous_needs_fewer_steps_than_drain(self, reg):
        """Open-loop arrivals (one every other step) with bimodal output
        lengths on 4 slots, examples/serve_lm.py's own load: both
        policies finish the same requests with the same tokens, and
        continuous batching needs at most 2/3 of drain's engine steps
        (every short request of a drain wave waits for the wave's
        longest). Counted in steps, so no clock is read."""
        import sys
        sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                        "examples"))
        from serve_lm import make_workload, run_load

        cfg, params = _tiny()
        workload = make_workload(seed=0, n_requests=48, rate=0.5)

        def serve(policy):
            engine = _engine(
                cfg, params, num_slots=4, max_len=64, policy=policy,
                queue=AdmissionQueue(max_depth=len(workload) + 1,
                                     admission_timeout_s=1e9))
            done, steps, _ = run_load(engine, workload)
            assert engine.kv.ledger.blocks_in_use == 0
            assert all(r.outcome == "completed" for r in done)
            return {r.request_id: r.tokens for r in done}, steps

        continuous, steps_c = serve("continuous")
        drained, steps_d = serve("drain")
        assert len(continuous) == len(workload)
        assert continuous == drained
        assert steps_d >= 1.5 * steps_c, (steps_d, steps_c)

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


# ---------------------------------------------------------------------------
# A model with recurrent state beside its K/V (models/hybrid.py)
# ---------------------------------------------------------------------------

def _tiny_hybrid():
    cfg = hybrid.HybridConfig.tiny(dtype=jnp.float32, max_seq_len=64,
                                   ssm_multipliers=(1.0, 1.0, 1.0, 1.0, 4.0))
    return cfg, hybrid.init_params(cfg, jax.random.PRNGKey(0))


def _prompt(n, seed):
    return tuple(int(t) for t in
                 np.random.default_rng(seed).integers(0, 256, n))


def _serve(engine, requests):
    for rid, prompt, new in requests:
        engine.submit(Request(rid, prompt, max_new_tokens=new))
    return {r.request_id: list(r.tokens)
            for r in engine.run_to_completion()}


class TestRecurrentStateInTheCache:
    def test_the_cache_holds_every_kind_the_model_declares(self, reg):
        cfg, params = _tiny_hybrid()
        engine = _engine(cfg, params, num_slots=3, max_len=32)
        kv = engine.kv
        assert set(kv.arrays) == {"k", "v", "ssm", "conv"}
        assert kv.recurrent == ("conv", "ssm")
        assert kv.k.shape == (2, 3, 32, cfg.num_kv_heads, cfg.head_dim)
        assert kv.arrays["ssm"].shape == (2, 3, 4, 8, 16)
        assert kv.arrays["ssm"].dtype == jnp.float32
        by_kind = kv.bytes_by_kind()
        assert kv.per_chip_bytes() == sum(by_kind.values())
        assert kv.row_state_bytes() * 3 == by_kind["ssm"] + by_kind["conv"]
        snap = reg.snapshot()
        for kind, nbytes in by_kind.items():
            assert _value(snap, "hvd_serve_state_bytes", kind=kind) == nbytes
        # a model with K/V alone: no recurrent kind, the gauge's two kinds
        cfg2, params2 = _tiny()
        plain = _engine(cfg2, params2)
        assert set(plain.kv.arrays) == {"k", "v"}
        assert plain.kv.recurrent == () and plain.kv.row_state_bytes() == 0

    @pytest.mark.parametrize("program", ["write_slot", "decode"])
    def test_every_kind_of_state_is_consumed(self, reg, program):
        """Both cache-writing programs donate EVERY array of the cache:
        what went in is dead, what came out is live, the gauge reads 1."""
        cfg, params = _tiny_hybrid()
        engine = _engine(cfg, params)
        engine.submit(Request("r", _prompt(5, 1), max_new_tokens=6))
        if program == "decode":
            engine.step()
        went_in = dict(engine.kv.arrays)
        engine.step()
        assert sorted(went_in) == ["conv", "k", "ssm", "v"]
        assert all(a.is_deleted() for a in went_in.values())
        assert not any(a.is_deleted() for a in engine.kv.arrays.values())
        engine.run_to_completion()
        assert _value(reg.snapshot(), "hvd_serve_kv_in_place") == 1

    def test_a_dropped_donation_of_the_state_is_named(self, reg,
                                                      monkeypatch, caplog):
        from horovod_tpu.serving import engine as engine_mod
        monkeypatch.setattr(
            engine_mod, "_write_slot",
            jax.jit(engine_mod._write_slot.__wrapped__))
        cfg, params = _tiny_hybrid()
        engine = _engine(cfg, params)
        engine.submit(Request("r", _prompt(5, 1), max_new_tokens=3))
        hvd_logger = logging.getLogger("horovod_tpu")
        hvd_logger.addHandler(caplog.handler)
        try:
            with caplog.at_level("WARNING", logger="horovod_tpu.serving"):
                engine.run_to_completion()
        finally:
            hvd_logger.removeHandler(caplog.handler)
        assert _value(reg.snapshot(), "hvd_serve_kv_in_place") == 0
        said = {r.getMessage() for r in caplog.records
                if "did not consume the KV cache" in r.getMessage()}
        assert len(said) == 1 and "conv, k, ssm, v" in said.pop()

    def test_a_reused_slot_carries_nothing_of_its_last_occupant(self, reg):
        """One slot, two requests in turn: the second's tokens and the
        state its prefill leaves are those of an engine that never held
        the first (every kind is overwritten whole; a recurrence would
        carry a stale state forward for ever)."""
        cfg, params = _tiny_hybrid()
        first, second = _prompt(21, 2), _prompt(9, 3)
        alone = _engine(cfg, params, num_slots=1)
        want = _serve(alone, [("b", second, 8)])["b"]
        engine = _engine(cfg, params, num_slots=1)
        got = _serve(engine, [("a", first, 12), ("b", second, 8)])
        assert got["b"] == want and len(got["a"]) == 12
        # and the state itself, right after the second's prefill
        fresh = _engine(cfg, params, num_slots=1)
        fresh.submit(Request("b", second, max_new_tokens=1))
        fresh.step()
        used = _engine(cfg, params, num_slots=1)
        _serve(used, [("a", first, 12)])
        used.submit(Request("b", second, max_new_tokens=1))
        used.step()
        for kind in ("ssm", "conv"):
            np.testing.assert_array_equal(
                np.asarray(used.kv.arrays[kind]),
                np.asarray(fresh.kv.arrays[kind]))

    def test_a_row_outside_the_pass_keeps_its_state_bit_for_bit(
            self, reg, monkeypatch):
        """A two-cohort step (as during a hot swap): each pass advances
        its own rows' ``ssm`` and ``conv`` and leaves the other cohort's
        bit-identical; the tokens are those of one cohort."""
        from horovod_tpu.serving import engine as engine_mod
        cfg, params = _tiny_hybrid()
        requests = [("a", _prompt(7, 4), 6), ("b", _prompt(12, 5), 6)]
        want = _serve(_engine(cfg, params), requests)

        engine = _engine(cfg, params)
        for rid, prompt, new in requests:
            engine.submit(Request(rid, prompt, max_new_tokens=new))
        engine.step()                      # both admitted, one decode
        assert engine.active_count == 2
        slot_b = next(s for s, st in engine._active.items()
                      if st.request.request_id == "b")
        engine._active[slot_b].generation = 1   # a second cohort,
        engine._params_by_gen[1] = params        # on the same weights
        passes = []
        real = engine_mod._decode_jit

        def spy(cfg_, params_, tokens, positions, state, temps, rows, key,
                count):
            before = {k: np.asarray(a) for k, a in state.items()}
            out = real(cfg_, params_, tokens, positions, state, temps, rows,
                       key, count)
            passes.append((np.asarray(rows), before,
                           {k: np.asarray(a) for k, a in out[2].items()}))
            return out
        monkeypatch.setattr(engine_mod, "_decode_jit", spy)
        done = engine.step()
        assert len(passes) == 2
        for mask, before, after in passes:
            assert mask.sum() == 1
            for kind in ("ssm", "conv"):
                held = ~mask
                np.testing.assert_array_equal(after[kind][:, held],
                                              before[kind][:, held])
                assert (after[kind][:, mask] != before[kind][:, mask]).any()
        rec = hvd_tracing_steps()[-1]
        assert rec["cohorts"] == 2 and rec["state_rows"] == 2
        assert rec["state_bytes"] == 2 * 2 * engine.kv.row_state_bytes()
        got = {r.request_id: list(r.tokens) for r in done}
        got.update({r.request_id: list(r.tokens)
                    for r in engine.run_to_completion()})
        assert got == want

    def test_the_step_record_counts_the_state(self, reg):
        cfg, params = _tiny_hybrid()
        engine = _engine(cfg, params)
        engine.submit(Request("r", _prompt(5, 1), max_new_tokens=4))
        engine.step()
        row = engine.kv.row_state_bytes()
        rec = hvd_tracing_steps()[-1]
        # one admission (its row written once) and one decode pass
        assert rec["admitted"] == 1 and rec["state_rows"] == 1
        assert rec["state_bytes"] == row + 2 * row
        engine.step()
        rec = hvd_tracing_steps()[-1]
        assert rec["state_rows"] == rec["active"] == 1
        assert rec["state_bytes"] == 2 * row
        # K/V alone: the counts are there and read 0
        cfg2, params2 = _tiny()
        plain = _engine(cfg2, params2)
        plain.submit(Request("p", (5, 9, 17), max_new_tokens=3))
        plain.step()
        rec = hvd_tracing_steps()[-1]
        assert rec["state_rows"] == 0 and rec["state_bytes"] == 0


def hvd_tracing_steps():
    from horovod_tpu.utils import tracing as hvd_tracing
    return hvd_tracing.get_tracer().steps()


# ---------------------------------------------------------------------------
# The decode step does not wait for the host (docs/serving.md, "The step's
# order"): a step's ids are read a step late wherever nothing waits for them
# ---------------------------------------------------------------------------

MODELS = {"dense": _tiny, "hybrid": _tiny_hybrid}


def _never_ahead(engine):
    """The synchronous order on the same engine: every pass read in the
    step that launched it (what a free slot makes of every step)."""
    engine._due = lambda launched, cohorts: True
    return engine


def _drive(engine, requests, temperature=0.0, joins=()):
    """Submit ``requests`` [(id, prompt, new)], step to the end; ``joins``
    [(step, (id, prompt, new))] are submitted before that step. Returns
    ({id: RequestResult}, this engine's step records)."""
    first = len(hvd_tracing_steps())
    for rid, prompt, new in requests:
        engine.submit(Request(rid, prompt, max_new_tokens=new,
                              temperature=temperature))
    joins, out = dict(joins), {}
    for step in range(500):
        if step in joins:
            rid, prompt, new = joins.pop(step)
            engine.submit(Request(rid, prompt, max_new_tokens=new,
                                  temperature=temperature))
        out.update((r.request_id, r) for r in engine.step())
        if not engine.active_count and not len(engine.queue) and not joins:
            break
    return out, hvd_tracing_steps()[first:]


def _tokens(results):
    return {rid: list(r.tokens) for rid, r in results.items()}


def _subscriber(new):
    """A fleet subscriber that hands out ``new`` as generation 7 once its
    ``armed_generation`` is set."""
    class Armed:
        generation, params, step = 7, new, 0
        detect_ts = loaded_ts = armed_ts = 0.0

    class Subscriber:
        replica, current_generation, armed_generation = 0, 0, None
        clock = staticmethod(lambda: 0.0)

        def poll(self):
            pass

        def take_armed(self):
            rec, self.armed_generation = self.armed_generation, None
            return rec and Armed

    return Subscriber()


REQUESTS = [("a", (5, 9, 17), 9), ("b", (4, 8, 15, 16, 23, 42), 13),
            ("c", (7, 7, 1), 6)]


class TestAGreedyBatchDrawsNothing:
    """The step record's ``drew`` and ``hvd_serve_decode_draw_steps_total``
    say on which steps the decode program's sampler ran its categorical
    draw: those whose pass held a row with a temperature above 0
    (serving/sampling.py), by the host's own knowledge of its requests."""

    @pytest.mark.parametrize("model", ["dense", "hybrid"])
    def test_a_greedy_run_never_draws(self, reg, model):
        cfg, params = MODELS[model]()
        _, recs = _drive(_engine(cfg, params), REQUESTS)
        assert sum(r["active"] for r in recs) > 20
        assert [r["drew"] for r in recs] == [0] * len(recs)
        assert not _value(reg.snapshot(),
                          "hvd_serve_decode_draw_steps_total")

    @pytest.mark.parametrize("model", ["dense", "hybrid"])
    def test_a_step_draws_while_a_sampling_row_decodes(self, reg, model):
        """One sampling request of four tokens beside a greedy one of
        nine: the three passes it takes part in draw, the passes after it
        has left do not, and the greedy request is served what it is
        served alone."""
        cfg, params = MODELS[model]()
        greedy = ("a", (5, 9, 17), 9)
        alone, _ = _drive(_engine(cfg, params, seed=3), [greedy])
        engine = _engine(cfg, params, seed=3)
        first = len(hvd_tracing_steps())
        engine.submit(Request(*greedy[:2], max_new_tokens=greedy[2]))
        engine.submit(Request("s", (4, 8, 15), max_new_tokens=4,
                              temperature=0.9))
        done = {r.request_id: r for r in engine.run_to_completion()}
        recs = [r for r in hvd_tracing_steps()[first:] if r["active"]]
        assert [r["drew"] for r in recs] == [1] * 3 + [0] * (len(recs) - 3)
        assert [r["active"] for r in recs][:4] == [2, 2, 2, 1]
        assert len(recs) > 3
        assert _value(reg.snapshot(),
                      "hvd_serve_decode_draw_steps_total") == 3
        assert len(done["s"].tokens) == 4
        assert list(done["a"].tokens) == list(alone["a"].tokens)


class TestTheStepDoesNotWaitForTheHost:
    @pytest.fixture(autouse=True)
    def tracer(self):
        from horovod_tpu.utils import tracing as hvd_tracing
        hvd_tracing.reset(enabled=True, rank=0)
        yield
        hvd_tracing.reset()

    @pytest.mark.parametrize("temperature", [0.0, 0.8])
    @pytest.mark.parametrize("model", ["dense", "hybrid", "looped"])
    def test_running_ahead_gives_the_synchronous_orders_tokens(
            self, reg, model, temperature):
        """Two slots, three requests (the third joins when the first
        ends): the engine that runs ahead wherever it may gives, token
        for token and under the same keys, what the same engine gives
        when it reads every pass at once."""
        cfg, params = MODELS[model]()
        ahead, recs = _drive(_engine(cfg, params, seed=3), REQUESTS,
                             temperature)
        sync, sync_recs = _drive(_never_ahead(_engine(cfg, params, seed=3)),
                                 REQUESTS, temperature)
        assert _tokens(ahead) == _tokens(sync)
        assert {rid: len(t) for rid, t in _tokens(ahead).items()} == \
            {rid: new for rid, _, new in REQUESTS}
        assert all(r.outcome == "completed" for r in ahead.values())
        # it did run ahead, and it launched the same passes over the
        # same rows in the same steps
        assert sum(r["ahead"] for r in recs) >= 5
        assert not any(r["ahead"] for r in sync_recs)
        assert [r["active"] for r in recs] == \
            [r["active"] for r in sync_recs]
        assert [r["retired"] for r in recs] == \
            [r["retired"] for r in sync_recs]
        if temperature:   # sampled: not the greedy choice all the way
            greedy, _ = _drive(_engine(cfg, params, seed=3), REQUESTS)
            assert _tokens(greedy) != _tokens(ahead)

    @pytest.mark.parametrize("model", ["dense", "hybrid", "looped"])
    def test_greedy_tokens_are_those_of_an_engine_with_a_slot_kept_free(
            self, reg, model):
        """...and what an engine that never may run ahead gives (three
        slots for two requests at a time), and for the dense model the
        plain per-request reference's."""
        cfg, params = MODELS[model]()
        two = REQUESTS[:2]
        full, recs = _drive(_engine(cfg, params), two)
        spare, spare_recs = _drive(_engine(cfg, params, num_slots=3), two)
        assert sum(r["ahead"] for r in recs) >= 5
        assert not any(r["ahead"] for r in spare_recs)
        assert _tokens(full) == _tokens(spare)
        if model == "dense":
            for rid, prompt, new in two:
                assert _tokens(full)[rid] == \
                    _greedy_reference(cfg, params, prompt, new)

    def test_a_row_ends_on_its_last_token_and_no_pass_is_wasted(
            self, reg, monkeypatch):
        """Every row of every pass is a token somebody gets: the passes
        number the longest request's tokens less its first, the rows
        they advanced the tokens asked for less the prefills'."""
        from horovod_tpu.serving import engine as engine_mod
        cfg, params = _tiny()
        real, calls = engine_mod._decode_jit, []
        monkeypatch.setattr(
            engine_mod, "_decode_jit",
            lambda *a: calls.append(int(np.asarray(a[6]).sum())) or real(*a))
        results, recs = _drive(_engine(cfg, params), REQUESTS[:2])
        assert {rid: len(r.tokens) for rid, r in results.items()} == \
            {"a": 9, "b": 13}
        assert len(calls) == 13 - 1
        assert sum(calls) == (9 - 1) + (13 - 1) == \
            sum(r["active"] for r in recs)

    @pytest.mark.parametrize("case", ["every_slot_busy", "a_free_slot",
                                      "a_finishing_row", "two_cohorts",
                                      "drain_policy_mid_wave"])
    def test_ahead_is_decided_from_what_the_engine_sees(self, reg, case):
        cfg, params = _tiny()
        kw = {"policy": "drain", "num_slots": 3} \
            if case == "drain_policy_mid_wave" else {}
        engine = _engine(cfg, params, **kw)
        engine.submit(Request("a", (5, 9, 17), max_new_tokens=9))
        if case != "a_free_slot":
            engine.submit(Request(
                "b", (4, 8, 15), max_new_tokens=2 if case ==
                "a_finishing_row" else 9))
        if case == "two_cohorts":
            engine.step()
            slot = next(iter(engine._active))
            engine._active[slot].generation = 1
            engine._params_by_gen[1] = params

        def counted():
            return _value(reg.snapshot(),
                          "hvd_serve_steps_ahead_total") or 0
        before = counted()
        engine.step()
        rec = hvd_tracing_steps()[-1]
        want = int(case in ("every_slot_busy", "drain_policy_mid_wave"))
        assert rec["ahead"] == want == counted() - before
        assert rec["active"] >= 1
        assert (engine._unread is not None) == bool(want)
        # whichever way, what a launch decided is true at once: the
        # tokens a row has been given and the cache rows it holds
        for slot, st in engine._active.items():
            assert st.given == len(st.generated) + want
            assert engine.kv.ledger.length(slot) == \
                len(st.request.prompt) + st.given - 1
        snap = engine.load_snapshot()
        assert snap["work_tokens"] == sum(
            st.request.max_new_tokens - st.given
            for st in engine._active.values())
        results = engine.run_to_completion()
        assert all(r.outcome == "completed" for r in results)
        assert engine._unread is None

    def test_a_hot_swap_mid_flight_keeps_each_cohort_on_its_weights(
            self, reg):
        """A generation arrives while the engine runs ahead: the rows in
        flight end on the weights that admitted them, the next request
        decodes on the new ones, and while both are live every pass is
        read at once."""
        cfg, old = _tiny()
        new = jax.tree_util.tree_map(lambda a: a * 1.5, old)
        sub = _subscriber(new)
        engine = _engine(cfg, old, subscriber=sub)
        requests = [("a", (5, 9, 17), 8), ("b", (4, 8, 15, 16), 14),
                    ("c", (7, 7, 1), 6)]
        for rid, prompt, n in requests:
            engine.submit(Request(rid, prompt, max_new_tokens=n))
        results = {}
        for _ in range(3):
            engine.step()
        assert engine._unread is not None     # running ahead on ``old``
        sub.armed_generation = 7
        for _ in range(100):
            results.update((r.request_id, r) for r in engine.step())
            if len(results) == 3:
                break
        assert engine.generation == 7 and engine._unread is None
        assert [results[r].generation for r in "abc"] == [0, 0, 7]
        for rid, prompt, n in requests:
            params = new if rid == "c" else old
            assert list(results[rid].tokens) == \
                _greedy_reference(cfg, params, prompt, n), rid
        mixed = [r for r in hvd_tracing_steps() if r["cohorts"] == 2]
        assert mixed and not any(r["ahead"] for r in mixed)
        assert set(engine._params_by_gen) == {7}

    @pytest.mark.parametrize("order", ["ahead", "synchronous"])
    def test_a_refusing_ledger_ends_the_row_without_that_pass(
            self, reg, order):
        """``kv.ledger.grow`` refuses before the launch: the row goes
        ``kv_exhausted`` with the tokens it has (the last of them read
        from the pass in flight), the other row never notices."""
        cfg, params = _tiny()
        engine = _engine(cfg, params)
        if order == "synchronous":
            _never_ahead(engine)
        grow = engine.kv.ledger.grow
        engine.kv.ledger.grow = lambda slot, n: \
            grow(slot, n) and not (slot == 0 and n > 3 + 4)
        engine.submit(Request("a", (5, 9, 17), max_new_tokens=12))
        engine.submit(Request("b", (4, 8, 15, 16, 23, 42),
                              max_new_tokens=12))
        engine.step()
        assert engine._active[0].request.request_id == "a"
        results = {r.request_id: r for r in engine.run_to_completion()}
        a, b = results["a"], results["b"]
        assert (a.outcome, a.reason) == ("failed", "kv_exhausted")
        # positions 3..6 were written: the first token and four more
        assert list(a.tokens) == _greedy_reference(
            cfg, params, (5, 9, 17), 5)
        assert b.outcome == "completed" and list(b.tokens) == \
            _greedy_reference(cfg, params, (4, 8, 15, 16, 23, 42), 12)
        assert engine.kv.ledger.blocks_in_use == 0
        assert engine._unread is None

    @pytest.mark.parametrize("order", ["ahead", "synchronous"])
    def test_a_blown_deadline_is_seen_at_the_read(self, reg, order):
        """Two busy slots; one row's deadline passes mid-stream. It fails
        ``deadline`` with a prefix of its tokens, never a wrong or an
        extra one (run ahead: at most one row of one pass is wasted); the
        other row and the request that takes the slot are untouched."""
        cfg, params = _tiny()
        clock = FakeClock()
        queue = AdmissionQueue(max_depth=8, admission_timeout_s=1e9,
                               clock=clock)
        engine = _engine(cfg, params, queue=queue, clock=clock)
        if order == "synchronous":
            _never_ahead(engine)
        slow, other, late = (1, 2), (4, 8, 15, 16), (7, 7, 1)
        engine.submit(Request("slow", slow, max_new_tokens=20,
                              deadline_s=5.0))
        engine.submit(Request("other", other, max_new_tokens=14))
        results = {}
        for _ in range(4):
            engine.step()
        clock.t = 6.0
        engine.submit(Request("late", late, max_new_tokens=5))
        for _ in range(60):
            results.update((r.request_id, r) for r in engine.step())
            if len(results) == 3:
                break
        got = results["slow"]
        assert (got.outcome, got.reason) == ("failed", "deadline")
        full = _greedy_reference(cfg, params, slow, 20)
        assert 4 <= len(got.tokens) <= 6
        assert list(got.tokens) == full[:len(got.tokens)]
        assert list(results["other"].tokens) == \
            _greedy_reference(cfg, params, other, 14)
        assert list(results["late"].tokens) == \
            _greedy_reference(cfg, params, late, 5)
        rows = sum(r["active"] for r in hvd_tracing_steps())
        tokens = sum(len(r.tokens) - 1 for r in results.values())
        assert rows - tokens == (1 if order == "ahead" else 0)
        assert engine.kv.ledger.blocks_in_use == 0
        assert engine._unread is None

    @pytest.mark.parametrize("policy", ["continuous", "drain"])
    def test_nothing_is_in_flight_once_no_row_is_active(self, reg, policy):
        """``run_to_completion`` and a draining engine's last step leave
        no pass unread: a last row's last token is always read at once."""
        cfg, params = _tiny()
        engine = _engine(cfg, params, policy=policy)
        for i in range(5):
            engine.submit(Request(f"r{i}", (1 + i, 2, 3),
                                  max_new_tokens=4 + 3 * i))
        seen = False
        for _ in range(6):
            engine.step()
            seen |= engine._unread is not None
        assert seen
        engine.begin_drain()
        assert not engine.submit(Request("refused", (1, 2)))
        done = 0
        while engine.active_count or len(engine.queue):
            done += len(engine.step())
            assert engine.active_count or engine._unread is None
        assert engine._unread is None and engine._feed is None
        assert engine.kv.ledger.blocks_in_use == 0

    def test_the_key_folded_in_the_program_is_the_hosts(self, reg):
        """The sampling keys are what they were when the host folded
        them: ``fold_in`` of a traced int32 count, inside a program,
        gives the bits of ``fold_in`` of the same Python int outside."""
        key = jax.random.PRNGKey(3)
        inside = jax.jit(jax.random.fold_in)
        for count in (0, 1, 77, 2 ** 31 - 1):
            np.testing.assert_array_equal(
                np.asarray(inside(key, np.int32(count))),
                np.asarray(jax.random.fold_in(key, count)))

    def test_one_decode_program_whichever_way_a_step_goes(self, reg):
        """Fed by the host (a row joined or left) or by the pass before,
        read at once or a step late, one cohort or two: one compiled
        ``_decode_jit``, one ``_write_slot`` a prefill shape."""
        from horovod_tpu.serving import engine as engine_mod
        cfg, params = _tiny()
        engine = _engine(cfg, params)
        engine.submit(Request("warm", (9, 9, 9), max_new_tokens=2))
        engine.run_to_completion()
        before = (engine_mod._decode_jit._cache_size(),
                  engine_mod._write_slot._cache_size(),
                  engine_mod._prefill_jit._cache_size())
        _, recs = _drive(engine, REQUESTS)
        assert {r["ahead"] for r in recs} == {0, 1}
        engine.submit(Request("d", (5, 9, 17), max_new_tokens=6))
        engine.submit(Request("e", (4, 8, 15), max_new_tokens=6))
        engine.step()
        engine._active[0].generation = 1
        engine._params_by_gen[1] = params
        engine.run_to_completion()
        assert (engine_mod._decode_jit._cache_size(),
                engine_mod._write_slot._cache_size(),
                engine_mod._prefill_jit._cache_size()) == before


# ---------------------------------------------------------------------------
# An admitting step launches everything before it reads anything
# (docs/serving.md, "The step's order"): prefills, slot writes and the decode
# pass over the new rows too, then each first token in admission order
# ---------------------------------------------------------------------------

def _reads_at_once(engine):
    """The synchronous admission on the same engine: each first token read
    and booked before anything else is launched."""
    launch = engine._prefill

    def prefill(req):
        launch(req)
        engine._read_first_tokens()
    engine._prefill = prefill
    return engine


def _warm(engine):
    """One request through the engine: its decode program and the prefill
    of every prompt of up to 8 tokens are compiled. (A dispatch that
    compiles reads every unread first token before it: no request waits
    seconds for another one's program.)"""
    engine.submit(Request("warm", (9, 9, 9), max_new_tokens=2))
    engine.run_to_completion()
    return engine


def _spy(monkeypatch, log, clock=None):
    """Log every program launch and every host read of the engine's, in
    order; ``clock`` moves on by one at each read."""
    from horovod_tpu.serving import engine as engine_mod
    for name in ("_prefill_jit", "_write_slot", "_decode_jit"):
        real = getattr(engine_mod, name)
        monkeypatch.setattr(
            engine_mod, name,
            lambda *a, _real=real, _name=name: log.append(_name) or _real(*a))
    get = jax.device_get

    def device_get(x):
        log.append("read")
        if clock is not None:
            clock.t += 1.0
        return get(x)
    monkeypatch.setattr(jax, "device_get", device_get)


class TestAnAdmittingStepLaunchesBeforeItReads:
    @pytest.fixture(autouse=True)
    def tracer(self):
        from horovod_tpu.utils import tracing as hvd_tracing
        hvd_tracing.reset(enabled=True, rank=0)
        yield
        hvd_tracing.reset()

    @pytest.mark.parametrize("temperature", [0.0, 0.8])
    @pytest.mark.parametrize("model", ["dense", "hybrid", "looped"])
    def test_launching_first_gives_the_synchronous_admissions_tokens(
            self, reg, model, temperature):
        """Two slots, four requests, the last two joining as rows end:
        token for token and under the same keys what the same engine gives
        when it reads each first token before it launches anything else,
        with the same passes over the same rows in the same steps."""
        cfg, params = MODELS[model]()
        requests = REQUESTS + [("d", (2, 7, 1, 8), 5)]
        first, recs = _drive(_warm(_engine(cfg, params, seed=3)), requests,
                             temperature)
        sync, sync_recs = _drive(
            _reads_at_once(_warm(_engine(cfg, params, seed=3))), requests,
            temperature)
        assert _tokens(first) == _tokens(sync)
        assert {rid: len(t) for rid, t in _tokens(first).items()} == \
            {rid: new for rid, _, new in requests}
        for key in ("admitted", "active", "retired", "ahead"):
            assert [r[key] for r in recs] == [r[key] for r in sync_recs]
        # every admission was read behind its step's decode launch, and
        # in the synchronous order none
        assert [r["admitted_ahead"] for r in recs] == \
            [r["admitted"] for r in recs]
        assert sum(r["admitted"] for r in recs) == 4
        assert not any(r["admitted_ahead"] for r in sync_recs)
        snap = reg.snapshot()
        assert _value(snap, "hvd_serve_admissions_ahead_total") == 4
        assert [e["request_id"] for e in _events(snap, "serve_admit")] == \
            2 * ["warm", "a", "b", "c", "d"]

    def test_two_admissions_are_both_launched_before_either_is_read(
            self, reg, monkeypatch):
        """...and the pass over both rows too; each ``ttft_s`` is stamped
        at its own read."""
        cfg, params = _tiny()
        clock = FakeClock()
        engine = _warm(_engine(cfg, params, clock=clock, queue=AdmissionQueue(
            max_depth=8, admission_timeout_s=1e9, clock=clock)))
        log = []
        _spy(monkeypatch, log, clock)
        engine.submit(Request("a", (5, 9, 17), max_new_tokens=9))
        engine.submit(Request("b", (4, 8, 15), max_new_tokens=9))
        assert engine.step() == []
        assert log == 2 * ["_prefill_jit", "_write_slot"] + [
            "_decode_jit", "read", "read"]
        a, b = (engine._active[slot] for slot in sorted(engine._active))
        assert (a.ttft_s, b.ttft_s) == (1.0, 2.0)
        assert (a.last_token_ts, b.last_token_ts) == (1.0, 2.0)
        assert a.given == b.given == 2 and engine._unread is not None
        rec = hvd_tracing_steps()[-1]
        assert rec["admitted"] == rec["admitted_ahead"] == rec["active"] == 2
        results = {r.request_id: r for r in engine.run_to_completion()}
        assert (results["a"].ttft_s, results["b"].ttft_s) == (1.0, 2.0)
        for rid, prompt in (("a", (5, 9, 17)), ("b", (4, 8, 15))):
            assert list(results[rid].tokens) == \
                _greedy_reference(cfg, params, prompt, 9)

    def test_a_burst_of_admissions_keeps_two_unread_and_the_chip_fed(
            self, reg, monkeypatch):
        """Four requests into four free slots: the third admission reads
        the first one's token before it launches, the fourth the second
        one's, each with the next prefill already queued behind it; the
        last two are read behind the decode launch. So the device holds
        two prefills' output rows at most, and never waits for the host."""
        cfg, params = _tiny()
        engine = _warm(_engine(cfg, params, num_slots=4))
        log = []
        _spy(monkeypatch, log)
        prompts = {"a": (5, 9, 17), "b": (4, 8, 15), "c": (7, 7, 1),
                   "d": (2, 7, 1, 8)}
        for rid, prompt in prompts.items():
            engine.submit(Request(rid, prompt, max_new_tokens=6))
        assert engine.step() == []
        launch = ["_prefill_jit", "_write_slot"]
        assert log == 2 * launch + ["read"] + launch + ["read"] + launch + [
            "_decode_jit", "read", "read"]
        rec = hvd_tracing_steps()[-1]
        assert rec["admitted"] == rec["admitted_ahead"] == 4
        assert rec["active"] == 4 and rec["ahead"] == 1
        results = {r.request_id: r for r in engine.run_to_completion()}
        for rid, prompt in prompts.items():
            assert list(results[rid].tokens) == \
                _greedy_reference(cfg, params, prompt, 6)

    def test_a_dispatch_that_compiles_leaves_no_first_token_unread(
            self, reg, monkeypatch):
        """A new engine's first step: the second prompt pads to a length
        not prefilled before and the pass is the engine's first, so each
        of those dispatches traces, lowers and compiles, seconds on the
        host; what is unread is read before it, as the synchronous order
        would."""
        cfg, params = _tiny()
        engine = _engine(cfg, params)
        log = []
        _spy(monkeypatch, log)
        engine.submit(Request("a", (5, 9, 17), max_new_tokens=6))
        engine.submit(Request("b", tuple(range(1, 12)), max_new_tokens=6))
        assert engine.step() == []
        launch = ["_prefill_jit", "_write_slot"]
        assert log == launch + ["read"] + launch + ["read", "_decode_jit"]
        assert hvd_tracing_steps()[-1]["admitted_ahead"] == 0
        engine.run_to_completion()
        # the same two lengths again: nothing compiles, nothing waits
        del log[:]
        engine.submit(Request("c", (7, 7, 1), max_new_tokens=6))
        engine.submit(Request("d", tuple(range(2, 13)), max_new_tokens=6))
        engine.step()
        assert log == 2 * launch + ["_decode_jit", "read", "read"]
        assert hvd_tracing_steps()[-1]["admitted_ahead"] == 2

    @pytest.mark.parametrize("new,idle", [(1, False), (2, False), (1, True)])
    def test_a_request_that_asks_for_one_token_or_two(self, reg, new, idle):
        """One token: the prefill's, so the row takes no part in the
        step's pass (known before the launch) and is retired at its read.
        Two: it joins the pass as its last row, which is read at once."""
        cfg, params = _tiny()
        engine = _engine(cfg, params)
        if not idle:
            engine.submit(Request("long", (4, 8, 15, 16), max_new_tokens=9))
            engine.step()
        engine.submit(Request("short", (5, 9, 17), max_new_tokens=new))
        (short,) = engine.step()
        assert short.outcome == "completed" and list(short.tokens) == \
            _greedy_reference(cfg, params, (5, 9, 17), new)
        rec = hvd_tracing_steps()[-1]
        assert rec["admitted"] == rec["retired"] == 1 and rec["ahead"] == 0
        assert rec["active"] == (0 if idle else new)
        # read behind the launch, where there was one
        assert rec["admitted_ahead"] == (0 if idle else 1)
        assert engine._unread is None and not engine._joined
        assert engine.active_count == (0 if idle else 1)
        (long,) = engine.run_to_completion() or [None]
        assert idle or list(long.tokens) == _greedy_reference(
            cfg, params, (4, 8, 15, 16), 9)
        assert engine.kv.ledger.blocks_in_use == 0

    def test_a_ledger_that_refuses_the_new_rows_first_pass(self, reg):
        """``grow`` refuses before the launch: the new row's first token is
        read there and then, it goes ``kv_exhausted`` with that token, and
        the row beside it never notices."""
        cfg, params = _tiny()
        engine = _engine(cfg, params)
        grow = engine.kv.ledger.grow
        engine.kv.ledger.grow = lambda slot, n: slot != 1 and grow(slot, n)
        engine.submit(Request("kept", (4, 8, 15, 16), max_new_tokens=7))
        engine.submit(Request("refused", (5, 9, 17), max_new_tokens=7))
        (refused,) = engine.step()
        assert (refused.request_id, refused.outcome, refused.reason) == \
            ("refused", "failed", "kv_exhausted")
        assert list(refused.tokens) == \
            _greedy_reference(cfg, params, (5, 9, 17), 1)
        assert refused.ttft_s is not None
        rec = hvd_tracing_steps()[-1]
        # both read before the launch: the first with the second one's
        # prefill queued behind it, the second with nothing
        assert rec["admitted"] == 2 and rec["admitted_ahead"] == 1
        assert rec["active"] == 1 and rec["retired"] == 1
        (kept,) = engine.run_to_completion()
        assert list(kept.tokens) == \
            _greedy_reference(cfg, params, (4, 8, 15, 16), 7)
        assert engine.kv.ledger.blocks_in_use == 0

    def test_a_deadline_blown_during_the_prefill_is_seen_at_the_passes_read(
            self, reg, monkeypatch):
        """No deadline is looked at where a first token is read, as ever:
        the row joins the pass and fails ``deadline`` where that is read,
        with the two tokens it has."""
        from horovod_tpu.serving import engine as engine_mod
        cfg, params = _tiny()
        clock = FakeClock(1.0)
        engine = _engine(cfg, params, clock=clock, queue=AdmissionQueue(
            max_depth=8, admission_timeout_s=1e9, clock=clock))
        real = engine_mod._prefill_jit

        def slow(*a):
            clock.t = 7.0
            return real(*a)
        monkeypatch.setattr(engine_mod, "_prefill_jit", slow)
        engine.submit(Request("late", (5, 9, 17), max_new_tokens=9,
                              deadline_s=5.0))
        (late,) = engine.run_to_completion()
        assert (late.outcome, late.reason) == ("failed", "deadline")
        assert late.ttft_s == 6.0
        assert list(late.tokens) == \
            _greedy_reference(cfg, params, (5, 9, 17), 2)
        assert engine.kv.ledger.blocks_in_use == 0

    def test_a_hot_swap_in_the_admitting_step(self, reg):
        """A generation is armed and a request waits: the step swaps,
        admits the request on the new weights and runs one pass a cohort,
        all launched before the first token is read; every pass is read at
        once while both generations live."""
        cfg, old = _tiny()
        new = jax.tree_util.tree_map(lambda a: a * 1.5, old)
        sub = _subscriber(new)
        engine = _engine(cfg, old, subscriber=sub)
        engine.submit(Request("a", (5, 9, 17), max_new_tokens=8))
        engine.step()
        sub.armed_generation = 7
        engine.submit(Request("c", (7, 7, 1), max_new_tokens=6))
        engine.step()
        rec = hvd_tracing_steps()[-1]
        assert engine.generation == 7
        assert rec["admitted"] == rec["admitted_ahead"] == 1
        assert rec["cohorts"] == 2 and rec["active"] == 2
        assert rec["ahead"] == 0 and engine._unread is None
        results = {r.request_id: r for r in engine.run_to_completion()}
        assert [results[r].generation for r in "ac"] == [0, 7]
        assert list(results["a"].tokens) == \
            _greedy_reference(cfg, old, (5, 9, 17), 8)
        assert list(results["c"].tokens) == \
            _greedy_reference(cfg, new, (7, 7, 1), 6)

    def test_the_drain_policy_admits_its_wave_the_same_way(self, reg):
        cfg, params = _tiny()
        results, recs = _drive(_warm(_engine(cfg, params, policy="drain")),
                               REQUESTS)
        for rid, prompt, new in REQUESTS:
            assert list(results[rid].tokens) == \
                _greedy_reference(cfg, params, prompt, new)
        # two into the idle batch, the third once the wave has drained
        assert [r["admitted"] for r in recs if r["admitted"]] == [2, 1]
        assert [r["admitted_ahead"] for r in recs] == \
            [r["admitted"] for r in recs]

    @pytest.mark.parametrize("model", ["dense", "hybrid", "looped"])
    def test_the_prefills_key_is_the_hosts_fold_in_of_the_shared_count(
            self, reg, monkeypatch, model):
        """Prefills and passes draw from one count in the order they are
        launched, as they always did: a prefill is handed
        ``fold_in(engine key, count)`` folded on the host, a pass the key
        and the count themselves."""
        from horovod_tpu.serving import engine as engine_mod
        cfg, params = MODELS[model]()
        engine = _warm(_engine(cfg, params, seed=3))
        counts = []
        real_prefill, real_decode = (engine_mod._prefill_jit,
                                     engine_mod._decode_jit)

        def prefill(*a):
            count = engine._step_count - 1    # taken just before the call
            assert np.array_equal(
                np.asarray(a[-1]),
                np.asarray(jax.random.fold_in(engine._rng, count)))
            counts.append(count)
            return real_prefill(*a)

        def decode(*a):
            assert a[-2] is engine._rng
            counts.append(int(a[-1]))
            return real_decode(*a)
        monkeypatch.setattr(engine_mod, "_prefill_jit", prefill)
        monkeypatch.setattr(engine_mod, "_decode_jit", decode)
        start = engine._step_count
        _drive(engine, REQUESTS, temperature=0.8)
        assert counts == list(range(start, start + len(counts)))
        assert len(counts) > len(REQUESTS)

    @pytest.mark.parametrize("words", [
        0, 3, 2**31 - 1, (0xFFFFFFF0, 0x80000001)])  # a seed, or the words
    @pytest.mark.parametrize("count", [0, 1, 2**16, 2**31 - 1, "drawn"])
    def test_the_hosts_fold_is_jaxs_fold_in_bit_for_bit(self, words, count):
        """``host_key.fold_in`` over Python integers gives the 64 bits of
        ``jax.random.fold_in``, as ``uint32[2]``: small and large counts,
        three hundred drawn ones, keys of seeds and one whose two words
        both lie above 2**31."""
        from horovod_tpu.serving import host_key
        if isinstance(words, int):
            key = jax.random.PRNGKey(words)
        else:
            key = jnp.asarray(words, jnp.uint32)
        words = np.asarray(key)
        counts = [count]
        if count == "drawn":
            drawn = np.random.default_rng(int(words[1])).integers(
                0, 2**31, 300)
            counts = [int(c) for c in drawn]
        for c in counts:
            got = host_key.fold_in(words, c)
            assert got.dtype == np.uint32 and got.shape == (2,)
            assert np.array_equal(
                got, np.asarray(jax.random.fold_in(key, c))), (words, c)

    @pytest.mark.parametrize("model", ["dense", "hybrid"])
    def test_sampled_tokens_are_those_of_the_eager_fold(
            self, reg, monkeypatch, model):
        """At temperature 1.0 an engine serves token for token what an
        engine with the parent's eager fold patched back in serves: the
        key handed to the prefill is the same 64 bits."""
        from horovod_tpu.serving import engine as engine_mod
        cfg, params = MODELS[model]()
        served, _ = _drive(_engine(cfg, params, seed=3), REQUESTS,
                           temperature=1.0)
        eager = []

        def fold(words, count):
            eager.append(count)
            return jax.random.fold_in(jnp.asarray(words), count)
        monkeypatch.setattr(engine_mod.host_key, "fold_in", fold)
        parents, _ = _drive(_engine(cfg, params, seed=3), REQUESTS,
                            temperature=1.0)
        assert len(eager) == len(REQUESTS)
        assert _tokens(served) == _tokens(parents)
        assert all(len(r.tokens) > 1 for r in served.values())

    def test_the_engine_reads_its_keys_words_once(self, reg):
        """The two words come to the host at construction; an admission
        reads nothing of the device key."""
        cfg, params = _tiny()
        engine = _engine(cfg, params, seed=3)
        assert isinstance(engine._key_words, np.ndarray)
        assert np.array_equal(engine._key_words,
                              np.asarray(jax.random.PRNGKey(3)))
        words = engine._key_words
        _warm(engine)
        assert engine._key_words is words

    @pytest.mark.parametrize("feed", ["numpy", "jax"])
    def test_prefill_jit_is_the_parents_program_however_it_is_fed(
            self, reg, feed):
        """``_prefill_jit`` keeps its six arguments, and host values of the
        dtypes it was always traced with are the same signature as the
        device values the engine used to convert them to: one lowered
        text, one compiled program a shape."""
        import inspect
        from horovod_tpu.serving import engine as engine_mod
        assert list(inspect.signature(
            engine_mod._prefill_jit).parameters) == [
                "cfg", "params", "tokens", "last_index", "temperature",
                "rng"]
        cfg, params = _tiny()
        _warm(_engine(cfg, params))
        tokens = np.zeros((1, 8), np.int32)
        tokens[0, :3] = (5, 9, 17)
        rng = jax.random.fold_in(jax.random.PRNGKey(3), 5)
        fed = {"numpy": (tokens, np.int32(2), np.float32(0.8),
                         np.asarray(rng)),
               "jax": (jnp.asarray(tokens), jnp.int32(2), jnp.float32(0.8),
                       rng)}
        before = engine_mod._prefill_jit._cache_size()
        tok, _ = engine_mod._prefill_jit(cfg, params, *fed[feed])
        assert engine_mod._prefill_jit._cache_size() == before
        want, _ = engine_mod._prefill_jit(cfg, params, *fed["jax"])
        assert int(tok) == int(want)
        texts = {how: engine_mod._prefill_jit.lower(
            cfg, params, *args).as_text() for how, args in fed.items()}
        assert texts["numpy"] == texts["jax"]
        slot_texts = {
            str(kind): engine_mod._write_slot.lower(
                {"k": jnp.zeros((2, 4, 8))}, {"k": jnp.zeros((2, 1, 8))},
                kind(1), jnp.zeros(4, jnp.int32), jnp.int32(7)).as_text()
            for kind in (np.int32, jnp.int32)}
        assert len(set(slot_texts.values())) == 1

    def test_one_prefill_program_a_shape_whichever_way_a_step_admits(
            self, reg):
        """First admission or hundredth, greedy or sampled, alone in its
        step or one of two, read behind the launch or at once: one compiled
        ``_prefill_jit`` and one ``_write_slot`` a padded prompt length."""
        from horovod_tpu.serving import engine as engine_mod
        cfg, params = _tiny()
        engine = _warm(_engine(cfg, params))

        def compiled():
            return (engine_mod._prefill_jit._cache_size(),
                    engine_mod._write_slot._cache_size(),
                    engine_mod._decode_jit._cache_size())
        before = compiled()
        _drive(engine, REQUESTS, temperature=0.8)
        _drive(_reads_at_once(engine), REQUESTS)
        engine.submit(Request("one", (5, 9, 17), max_new_tokens=1))
        engine.run_to_completion()
        assert compiled() == before


# ---------------------------------------------------------------------------
# Decode attention reads each row's live K/V: the kernel inside the engine
# (ops/flash_attention.py; interpreted here, forced on through the
# selecting predicate: there is no option), and the count that says how
# much there is to read
# ---------------------------------------------------------------------------

def _long_dense():
    cfg = tr.TransformerConfig.tiny(dtype=jnp.float32,
                                    attention_impl="full")
    cfg = dataclasses.replace(cfg, max_seq_len=256)
    _, params = tr.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _long_hybrid():
    cfg = hybrid.HybridConfig.tiny(dtype=jnp.float32, max_seq_len=256,
                                   ssm_multipliers=(1.0, 1.0, 1.0, 1.0, 4.0))
    return cfg, hybrid.init_params(cfg, jax.random.PRNGKey(0))


LONG_MODELS = {"dense": _long_dense, "hybrid": _long_hybrid}
# rows that cross the kernel's 128-position block while they decode, a
# short one beside them, and two that join when a slot comes free
LONG_REQUESTS = [("a", _prompt(126, 1), 8), ("b", _prompt(5, 2), 6),
                 ("c", _prompt(131, 3), 5)]
LONG_JOINS = [(7, ("d", _prompt(3, 4), 9)), (9, ("e", _prompt(120, 5), 12))]


class TestDecodeReadsLiveKV:
    @pytest.fixture(autouse=True)
    def programs(self):
        """``_decode_jit`` is one jit for the process: a program traced
        under a forced predicate must not outlive its test."""
        from horovod_tpu.serving import engine as engine_mod
        from horovod_tpu.utils import tracing as hvd_tracing
        hvd_tracing.reset(enabled=True)
        engine_mod._decode_jit.clear_cache()
        yield engine_mod
        engine_mod._decode_jit.clear_cache()
        hvd_tracing.reset()

    @pytest.mark.parametrize("model", list(LONG_MODELS))
    def test_the_kernel_in_the_engine_emits_the_einsums_tokens(
            self, reg, programs, monkeypatch, model):
        from horovod_tpu.ops import flash_attention as fa
        cfg, params = LONG_MODELS[model]()
        kw = dict(num_slots=3, max_len=256, kv_block=128)
        want, _ = _drive(_engine(cfg, params, **kw), LONG_REQUESTS,
                         joins=LONG_JOINS)
        assert programs._decode_jit._cache_size() == 1
        programs._decode_jit.clear_cache()
        ran = []
        kernel = fa._decode_attention_kernel
        monkeypatch.setattr(fa, "_decode_kernel_selected",
                            lambda shape, sharding: sharding is None)
        monkeypatch.setattr(fa, "_decode_attention_kernel",
                            lambda *a: ran.append(a[0].shape) or kernel(*a))
        got, recs = _drive(_engine(cfg, params, **kw), LONG_REQUESTS,
                           joins=LONG_JOINS)
        assert len(ran) == cfg.num_layers  # traced once, every layer
        assert len(got) == 5 and _tokens(got) == _tokens(want)
        assert all(r.outcome == "completed" for r in got.values())
        # rows joined and retired, a pass was read late and at once
        assert {r["ahead"] for r in recs} == {0, 1}
        assert sum(r["retired"] for r in recs) == 5
        assert programs._decode_jit._cache_size() == 1

    def test_the_step_record_counts_the_kv_a_pass_has_to_stream(
            self, reg, programs):
        """``kv_bytes``: each decoding row's K and V in whole blocks of
        the kernel's 128 positions up to its length, over all layers; on
        a CPU engine too (what is counted is the rows, not the path),
        and on no step that decodes nothing."""
        from benchmarks.readers import step_count_median
        from horovod_tpu.ops.flash_attention import decode_block
        cfg, params = _long_dense()
        engine = _engine(cfg, params, num_slots=4, max_len=256,
                         kv_block=128)
        assert decode_block(256) == 128
        # one position of one row over all layers, K and V, float32
        position = cfg.num_layers * 2 * cfg.num_heads * 16 * 4
        assert engine.kv.kv_block_bytes(128) == 128 * position
        for rid, n in (("a", 126), ("b", 5), ("c", 200)):
            engine.submit(Request(rid, _prompt(n, n), max_new_tokens=9))
        engine.step()
        first = hvd_tracing_steps()[-1]
        # lengths 127, 6 and 201: 1 + 1 + 2 blocks
        assert first["admitted"] == 3 and first["active"] == 3
        assert first["kv_bytes"] == (1 + 1 + 2) * 128 * position
        engine.step()
        engine.step()
        third = hvd_tracing_steps()[-1]
        # 129, 8 and 203: row a has crossed into its second block
        assert third["kv_bytes"] == (2 + 1 + 2) * 128 * position
        engine.run_to_completion()
        engine.step()
        idle = hvd_tracing_steps()[-1]
        assert idle["active"] == 0 and "kv_bytes" not in idle
        # the form the benchmark's reader takes: a number on every
        # decode-only step, its median scaled (attn.kv_bytes_per_step)
        recs = [r for r in hvd_tracing_steps() if r["active"]]
        assert all(isinstance(r["kv_bytes"], int) and r["kv_bytes"] > 0
                   for r in recs)
        read = step_count_median.read(
            {"step_phases": {"window": recs}},
            {"count": "kv_bytes", "scale": 1e-9}, None)
        assert read == pytest.approx(5 * 128 * position * 1e-9)

    def test_a_head_sharded_engine_keeps_the_einsum(self, reg, programs,
                                                    monkeypatch):
        """Over a ``tp`` mesh (8 virtual devices) the cache is sharded by
        heads and the einsum runs under its sharding constraint, whatever
        the backend: the same tokens as the unsharded engine."""
        from horovod_tpu.ops import flash_attention as fa
        from horovod_tpu.parallel import mesh as mesh_lib
        cfg, params = _long_dense()
        kw = dict(num_slots=2, max_len=256, kv_block=128)
        requests = [("a", _prompt(126, 1), 6), ("b", _prompt(5, 2), 4)]
        want, _ = _drive(_engine(cfg, params, **kw), requests)
        programs._decode_jit.clear_cache()
        seen, ran = [], []
        monkeypatch.setattr(fa.jax, "default_backend", lambda: "tpu")
        selected = fa._decode_kernel_selected
        monkeypatch.setattr(
            fa, "_decode_kernel_selected",
            lambda shape, sharding: seen.append(sharding) or
            selected(shape, sharding))
        monkeypatch.setattr(fa, "_decode_attention_kernel",
                            lambda *a: ran.append(1))
        mesh = mesh_lib.build_mesh(tp=2)
        mesh_lib.set_global_mesh(mesh)
        try:
            got, _ = _drive(_engine(cfg, params, mesh=mesh, **kw), requests)
        finally:
            mesh_lib.reset_global_mesh()
        assert len(seen) == cfg.num_layers and None not in seen
        assert not ran and _tokens(got) == _tokens(want)


@pytest.mark.parametrize("heads,kv_heads", [(4, 4), (4, 2), (20, 4), (6, 1)])
def test_grouped_query_decode_attention_is_the_equal_heads_path(heads,
                                                                kv_heads):
    """Fewer key/value heads than query heads: the same values as the
    equal-heads path on K/V repeated per group (query head i reads
    key/value head i // (heads / kv_heads))."""
    from horovod_tpu.ops.flash_attention import decode_attention
    k0, k1, k2 = jax.random.split(jax.random.PRNGKey(heads * 7 + kv_heads), 3)
    b, s, d = 3, 24, 16
    q = jax.random.normal(k0, (b, 1, heads, d))
    k = jax.random.normal(k1, (b, s, kv_heads, d))
    v = jax.random.normal(k2, (b, s, kv_heads, d))
    lengths = jnp.asarray([1, 9, 24])
    rep = heads // kv_heads
    want = decode_attention(q, jnp.repeat(k, rep, axis=2),
                            jnp.repeat(v, rep, axis=2), lengths)
    got = decode_attention(q, k, v, lengths)
    assert got.shape == (b, 1, heads, d)
    # float32 sums in another order
    np.testing.assert_allclose(got, want, atol=1e-5)
    with pytest.raises(ValueError, match="query heads"):
        decode_attention(jnp.zeros((1, 1, 3, d)), jnp.zeros((1, s, 2, d)),
                         jnp.zeros((1, s, 2, d)), jnp.asarray([1]))


# ---------------------------------------------------------------------------
# A stack that runs several times over one set of weights (models/looped.py):
# K/V per (pass, layer) PLANE of the one cache, passes x layers of them
# ---------------------------------------------------------------------------

def _tiny_looped(**kw):
    from horovod_tpu.models import looped
    kw.setdefault("dtype", jnp.float32)
    cfg = looped.LoopedConfig.tiny(max_seq_len=64, rope_theta=1e6, **kw)
    return cfg, looped.init_params(cfg, jax.random.PRNGKey(0))


MODELS["looped"] = _tiny_looped


class TestALoopedStackInTheCache:
    @pytest.fixture(autouse=True)
    def tracer(self):
        from horovod_tpu.utils import tracing as hvd_tracing
        hvd_tracing.reset(enabled=True, rank=0)
        yield
        hvd_tracing.reset()

    def test_the_cache_holds_a_plane_a_pass_and_layer(self, reg):
        cfg, params = _tiny_looped()
        engine = _engine(cfg, params, num_slots=3, max_len=32)
        kv = engine.kv
        assert (cfg.passes, cfg.num_layers, cfg.planes, kv.planes) == \
            (3, 2, 6, 6)
        assert set(kv.arrays) == {"k", "v"} and kv.recurrent == ()
        assert kv.k.shape == kv.v.shape == (6, 3, 32, 4, 16)
        position = 6 * 2 * 4 * 16 * 4    # one token over all planes, f32
        assert kv.kv_block_bytes(8) == 8 * position
        assert kv.per_chip_bytes() == 3 * 32 * position
        snap = reg.snapshot()
        assert _value(snap, "hvd_serve_state_bytes", kind="k") == \
            3 * 32 * position // 2
        # every other model: a plane a layer, one pass
        from horovod_tpu.serving.decode import passes
        assert passes(cfg) == 3
        for name in ("dense", "hybrid"):
            cfg2, params2 = MODELS[name]()
            plain = _engine(cfg2, params2)
            assert plain.kv.planes == cfg2.num_layers
            assert passes(cfg2) == 1

    def test_temp0_matches_no_cache_greedy(self, reg):
        """The engine (prefill, slot write, decode through the planes)
        against a full forward over the growing sequence every token."""
        from horovod_tpu.models import looped
        cfg, params = _tiny_looped()
        prompts = {"a": _prompt(5, 1), "b": _prompt(11, 2),
                   "c": _prompt(3, 3)}
        got = _serve(_engine(cfg, params),
                     [(rid, p, 7) for rid, p in prompts.items()])
        # one program for every length: a causal forward over a padded
        # sequence reads the same logits at the last real position
        forward = jax.jit(lambda toks: looped.forward(cfg, params, toks)[0])
        for rid, prompt in prompts.items():
            toks, want = list(prompt), []
            for _ in range(7):
                padded = np.zeros((1, 24), np.int32)
                padded[0, :len(toks)] = toks
                logits = forward(jnp.asarray(padded))
                want.append(int(jnp.argmax(logits[0, len(toks) - 1])))
                toks.append(want[-1])
            assert got[rid] == want

    @pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-4),
                                           (jnp.bfloat16, 0.2)])
    def test_prefill_then_decode_through_the_cache_is_the_references_forward(
            self, dtype, tol):
        """Logits, not tokens: one padded prefill written into a slot by
        the engine's own ``_write_slot``, then a decode step a token with
        the other rows masked out, against ONE float32 forward of the
        plain reference (benchmarks/reference/ouro.py) over the whole
        sequence. Tolerances as tests/test_looped_model.py states them."""
        import test_looped_model as lm
        from horovod_tpu.serving import decode as serve_decode
        from horovod_tpu.serving import engine as engine_mod
        cfg = lm.tiny_config()
        w = lm.drawn(cfg, seed=11)
        lcfg, params = lm.model(cfg, w, dtype)
        tokens, prompt_len, slots, slot, max_len = lm.sequence(30), 13, 3, 1, 48
        first = np.zeros((1, 16), np.int32)
        first[0, :prompt_len] = tokens[:prompt_len]
        row, state_row = jax.jit(serve_decode.prefill, static_argnums=0)(
            lcfg, params, jnp.asarray(first), jnp.int32(prompt_len - 1))
        assert state_row["k"].shape == (8, 1, 16, 4, 16)
        state = {k: jnp.zeros(a.shape, a.dtype) for k, a in
                 serve_decode.state_shapes(lcfg, slots, max_len).items()}
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
            logits, state = step(lcfg, params, jnp.asarray(toks),
                                 jnp.asarray(pos), state, jnp.asarray(mask))
            got.append(np.asarray(logits[slot]))
        with jax.default_matmul_precision("highest"):
            want = np.asarray(lm.ref.logits_at(
                w, jnp.asarray(tokens[:-1]),
                jnp.arange(prompt_len - 1, len(tokens) - 1), cfg, lm.LAYERS))
        np.testing.assert_allclose(np.stack(got).astype(np.float32), want,
                                   atol=tol)

    def test_plane_t_l_plus_i_holds_pass_ts_kv_and_no_other(self, reg):
        """After a prefill and some decode steps the slot's rows of plane
        ``t * layers + i`` are the rotated K/V that pass t of layer i
        computes in a forward over the whole sequence; the planes of two
        passes of one layer differ; no other slot was written."""
        from horovod_tpu.serving import decode as serve_decode
        cfg, params = _tiny_looped()
        engine = _never_ahead(_engine(cfg, params, num_slots=2, max_len=32))
        prompt = _prompt(9, 4)
        engine.submit(Request("r", prompt, max_new_tokens=6))
        for _ in range(4):
            engine.step()
        (slot, st), = engine._active.items()
        seq = list(prompt) + st.generated[:-1]   # the tokens in the cache
        n = len(seq)
        assert n == engine.kv.ledger.length(slot) == 13
        _, ks, vs = serve_decode.hidden_states(
            cfg, params, jnp.asarray([seq], jnp.int32))
        assert len(ks) == cfg.planes
        k, v = np.asarray(engine.kv.k), np.asarray(engine.kv.v)
        for plane in range(cfg.planes):
            np.testing.assert_allclose(k[plane, slot, :n],
                                       np.asarray(ks[plane][0]), atol=1e-5)
            np.testing.assert_allclose(v[plane, slot, :n],
                                       np.asarray(vs[plane][0]), atol=1e-5)
        layers = cfg.num_layers
        for i in range(layers):
            for t in range(1, cfg.passes):
                assert np.abs(k[t * layers + i, slot, :n]
                              - k[i, slot, :n]).max() > 0.1
        other = 1 - slot
        assert not k[:, other, :31].any() and not v[:, other, :31].any()

    def test_a_row_outside_the_mask_parks_its_write_and_reads_nothing(self):
        """``decode`` over two live rows with one masked out: the row in
        the pass gets the logits and cache rows it gets alone; the other's
        K/V is written only where ``positions`` parks it, in every plane,
        and its live prefix is untouched."""
        from horovod_tpu.serving import decode as serve_decode
        cfg, params = _tiny_looped()
        rng = np.random.default_rng(0)
        shape = serve_decode.state_shapes(cfg, 2, 32)["k"].shape
        state = {kind: jnp.asarray(rng.normal(size=shape), jnp.float32)
                 for kind in ("k", "v")}
        toks = jnp.asarray([7, 9], jnp.int32)
        both = jnp.asarray([5, 8], jnp.int32)
        parked = jnp.asarray([5, 31], jnp.int32)
        want, full = serve_decode.decode(cfg, params, toks, both, state)
        got, masked = serve_decode.decode(cfg, params, toks, parked, state,
                                          jnp.asarray([True, False]))
        np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
        for kind in ("k", "v"):
            before, after = np.asarray(state[kind]), np.asarray(masked[kind])
            np.testing.assert_array_equal(after[:, 0],
                                          np.asarray(full[kind])[:, 0])
            np.testing.assert_array_equal(after[:, 1, :31],
                                          before[:, 1, :31])
            assert (after[:, 1, 31] != before[:, 1, 31]).all(axis=(1, 2)).all()
            assert (after[:, 0, 5] != before[:, 0, 5]).all(axis=(1, 2)).all()

    def test_the_interpreted_kernel_and_the_einsum_agree_on_the_planes(
            self, monkeypatch):
        """One decode step over a ``[passes x layers, ...]`` cache with
        decode attention as the Mosaic kernel (interpreted here), plane
        ``t * layers + i`` handed over as its index: the einsum's logits
        and cache, to float32 rounding. The passes are one loop, so the
        program holds one kernel call a WEIGHT layer, its plane traced."""
        from horovod_tpu.ops import flash_attention as fa
        from horovod_tpu.serving import decode as serve_decode
        cfg, params = _tiny_looped()
        rng = np.random.default_rng(1)
        shape = serve_decode.state_shapes(cfg, 3, 256)["k"].shape
        state = {kind: jnp.asarray(rng.normal(size=shape), jnp.float32)
                 for kind in ("k", "v")}
        toks = jnp.asarray([7, 9, 11], jnp.int32)
        pos = jnp.asarray([130, 255, 4], jnp.int32)
        mask = jnp.asarray([True, False, True])
        want, want_state = serve_decode.decode(cfg, params, toks, pos,
                                               state, mask)
        planes = []
        kernel = fa._decode_attention_kernel
        monkeypatch.setattr(fa, "_decode_kernel_selected",
                            lambda shape, sharding: True)
        monkeypatch.setattr(
            fa, "_decode_attention_kernel",
            lambda q, k, v, lengths, layer, scale:
            planes.append(layer) or kernel(q, k, v, lengths, layer, scale))
        got, got_state = serve_decode.decode(cfg, params, toks, pos, state,
                                             mask)
        assert len(planes) == cfg.num_layers
        assert all(isinstance(p, jax.core.Tracer) for p in planes)
        live = np.asarray(mask)
        np.testing.assert_allclose(np.asarray(got)[live],
                                   np.asarray(want)[live], atol=2e-5)
        for kind in ("k", "v"):
            np.testing.assert_allclose(np.asarray(got_state[kind])[:, live],
                                       np.asarray(want_state[kind])[:, live],
                                       atol=2e-5)

    @pytest.mark.parametrize("entry", ["state_shapes", "engine", "prefill",
                                       "decode"])
    def test_an_exit_threshold_under_one_is_refused_by_name(self, entry):
        from horovod_tpu.serving import decode as serve_decode
        cfg, params = _tiny_looped(exit_threshold=0.5)
        ok, _ = _tiny_looped()
        with pytest.raises(NotImplementedError,
                           match="exit_threshold=0.5.*different passes"):
            if entry == "state_shapes":
                serve_decode.state_shapes(cfg, 2, 32)
            elif entry == "engine":
                _engine(cfg, params)
            elif entry == "prefill":
                serve_decode.prefill(cfg, params,
                                     jnp.zeros((1, 8), jnp.int32), 3)
            else:
                kv = jnp.zeros(serve_decode.state_shapes(ok, 2, 32)["k"].shape)
                serve_decode.decode(cfg, params, jnp.zeros(2, jnp.int32),
                                    jnp.zeros(2, jnp.int32),
                                    {"k": kv, "v": kv})

    def test_an_engine_over_a_mesh_is_refused_by_name(self):
        from horovod_tpu.parallel import mesh as mesh_lib
        cfg, params = _tiny_looped()
        with pytest.raises(NotImplementedError,
                           match="LoopedConfig serves on one chip"):
            _engine(cfg, params, mesh=mesh_lib.build_mesh(tp=2))

    @pytest.mark.parametrize("model,passes", [("dense", 1), ("hybrid", 1),
                                              ("looped", 3)])
    def test_the_step_record_counts_the_passes(self, reg, model, passes):
        """``passes``: stack passes the step's decode program runs each
        row, on every step that decoded and on no other."""
        cfg, params = MODELS[model]()
        engine = _engine(cfg, params)
        engine.submit(Request("r", _prompt(5, 1), max_new_tokens=4))
        engine.run_to_completion()
        engine.step()
        recs = hvd_tracing_steps()
        assert [r["passes"] for r in recs if r["active"]] == [passes] * 3
        assert "passes" not in recs[-1] and recs[-1]["active"] == 0
        if model == "looped":
            # K/V of a step: every pass streams its own planes
            position = cfg.planes * 2 * 4 * 16 * 4
            assert recs[1]["kv_bytes"] == 48 * position


# ---------------------------------------------------------------------------
# Latent attention and dropless experts (models/latent_moe.py): ONE
# positional kind in the cache, declared by the model; what a pass routed
# in the step record
# ---------------------------------------------------------------------------

def _tiny_latent_moe(**kw):
    from horovod_tpu.models import latent_moe
    kw.setdefault("dtype", jnp.float32)
    cfg = latent_moe.LatentMoEConfig.tiny(max_seq_len=64, rope_theta=1e6,
                                          **kw)
    return cfg, latent_moe.init_params(cfg, jax.random.PRNGKey(0))


MODELS["latent_moe"] = _tiny_latent_moe


class TestALatentCacheAndExperts:
    @pytest.fixture(autouse=True)
    def tracer(self):
        import gc
        from horovod_tpu.utils import tracing as hvd_tracing
        hvd_tracing.reset(enabled=True, rank=0)
        yield
        hvd_tracing.reset()
        # an engine dies with its cycles: a float32 cache of 128 lanes is
        # large enough for tests/benchmarks' "no float32 copy is alive"
        gc.collect()

    def test_kinds_are_positional_or_recurrent_by_declaration(self, reg):
        """Not by the names ``k`` and ``v``: the latent is positional (it
        counts as K/V bytes, a row parks its write), and the three other
        families' kinds, planes and bytes are what they were."""
        from horovod_tpu.serving.decode import positional_kinds
        cfg, params = _tiny_latent_moe()
        engine = _engine(cfg, params, num_slots=3, max_len=32)
        kv = engine.kv
        assert positional_kinds(cfg) == kv.positional == ("latent",)
        assert kv.recurrent == () and set(kv.arrays) == {"latent"}
        # 32 + 8 numbers a token a plane, in one 128-lane tile
        assert (cfg.latent_dim, cfg.latent_lanes) == (40, 128)
        assert kv.arrays["latent"].shape == (3, 3, 32, 1, 128)
        assert kv.planes == cfg.num_layers == 3
        position = 3 * 128 * 4           # one token over all planes, f32
        assert kv.kv_block_bytes(8) == 8 * position
        assert kv.per_chip_bytes() == 3 * 32 * position
        assert kv.row_state_bytes() == 0
        snap = reg.snapshot()
        assert _value(snap, "hvd_serve_state_bytes", kind="latent") == \
            3 * 32 * position
        for name, kinds, rec in (("dense", ("k", "v"), ()),
                                 ("looped", ("k", "v"), ()),
                                 ("hybrid", ("k", "v"), ("conv", "ssm"))):
            cfg2, params2 = MODELS[name]()
            other = _engine(cfg2, params2).kv
            assert positional_kinds(cfg2) == other.positional == kinds
            assert other.recurrent == rec
            assert other.planes == other.k.shape[0]
            by_kind = other.bytes_by_kind()
            assert other.kv_block_bytes(8) == \
                (by_kind["k"] + by_kind["v"]) * 8 // (2 * 48)

    def test_temp0_matches_no_cache_greedy(self, reg):
        """Prefill (expanded), slot write, decode (absorbed) with rows
        joining and retiring, against a full forward over the growing
        sequence every token."""
        from horovod_tpu.models import latent_moe
        cfg, params = _tiny_latent_moe()
        prompts = {"a": _prompt(5, 1), "b": _prompt(11, 2),
                   "c": _prompt(3, 3), "d": _prompt(17, 4)}
        new = {"a": 7, "b": 3, "c": 9, "d": 5}
        got = _serve(_engine(cfg, params),
                     [(rid, p, new[rid]) for rid, p in prompts.items()])
        forward = jax.jit(lambda toks: latent_moe.forward(cfg, params,
                                                          toks)[0])
        for rid, prompt in prompts.items():
            toks, want = list(prompt), []
            for _ in range(new[rid]):
                padded = np.zeros((1, 32), np.int32)
                padded[0, :len(toks)] = toks
                logits = forward(jnp.asarray(padded))
                want.append(int(jnp.argmax(logits[0, len(toks) - 1])))
                toks.append(want[-1])
            assert got[rid] == want

    @pytest.mark.parametrize("dtype,tol", [(jnp.float32, 3e-4),
                                           (jnp.bfloat16, 0.15)])
    def test_prefill_then_decode_through_the_cache_is_the_references_forward(
            self, dtype, tol):
        """Logits, not tokens: one padded prefill written into a slot by
        the engine's own ``_write_slot``, then a decode step a token with
        the other rows masked out, against ONE float32 forward of the
        plain reference (benchmarks/reference/glm_moe_lite.py, the
        EXPANDED attention and a loop over the experts) over the whole
        sequence. Tolerances as tests/test_latent_moe_model.py states
        them; seed 8 routes bfloat16 as float32 does over these tokens."""
        import test_latent_moe_model as lm
        from horovod_tpu.serving import decode as serve_decode
        from horovod_tpu.serving import engine as engine_mod
        cfg = lm.tiny_config()
        w = lm.drawn(cfg, seed=8)
        mcfg, params = lm.model(cfg, w, dtype)
        tokens, prompt_len, slots, slot, max_len = \
            lm.sequence(30, 8), 13, 3, 1, 48
        first = np.zeros((1, 16), np.int32)
        first[0, :prompt_len] = tokens[:prompt_len]
        row, state_row = jax.jit(serve_decode.prefill, static_argnums=0)(
            mcfg, params, jnp.asarray(first), jnp.int32(prompt_len - 1))
        assert state_row["latent"].shape == (3, 1, 16, 1, 128)
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
            assert routed.tolist() == [4, 1]   # one row, 2 layers x 2
            got.append(np.asarray(logits[slot]))
        with jax.default_matmul_precision("highest"):
            want = np.asarray(lm.ref.logits_at(
                w, jnp.asarray(tokens[:-1]),
                jnp.arange(prompt_len - 1, len(tokens) - 1), cfg, lm.LAYERS))
        np.testing.assert_allclose(np.stack(got).astype(np.float32), want,
                                   atol=tol)

    def test_a_row_outside_the_mask_parks_its_latent_and_reads_nothing(self):
        """``decode`` over two live rows with one masked out: the row in
        the pass gets the logits and the cache row it gets alone; the
        other's latent is written only where ``positions`` parks it, in
        every plane, its live prefix is untouched, and it is routed to no
        expert."""
        from horovod_tpu.serving import decode as serve_decode
        cfg, params = _tiny_latent_moe()
        rng = np.random.default_rng(0)
        shape = serve_decode.state_shapes(cfg, 2, 32)["latent"].shape
        state = {"latent": jnp.asarray(rng.normal(size=shape), jnp.float32)}
        toks = jnp.asarray([7, 9], jnp.int32)
        both = jnp.asarray([5, 8], jnp.int32)
        parked = jnp.asarray([5, 31], jnp.int32)
        want, full, both_routed = serve_decode.decode(cfg, params, toks,
                                                      both, state)
        got, masked, routed = serve_decode.decode(
            cfg, params, toks, parked, state, jnp.asarray([True, False]))
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                                   atol=1e-6)
        before, after = np.asarray(state["latent"]), \
            np.asarray(masked["latent"])
        np.testing.assert_array_equal(after[:, 0],
                                      np.asarray(full["latent"])[:, 0])
        np.testing.assert_array_equal(after[:, 1, :31], before[:, 1, :31])
        assert (after[:, 1, 31, 0, :40] != before[:, 1, 31, 0, :40]).all()
        assert (after[:, 0, 5, 0, :40] != before[:, 0, 5, 0, :40]).all()
        assert not after[:, :, [5, 31], 0, 40:][:, [0, 1], [0, 1]].any()
        # one row of two routed: 2 experts a layer, not 3 or 4
        assert routed.tolist() == [4, 1]
        assert 4 <= int(both_routed[0]) <= 8

    def test_the_cache_holds_what_the_expanded_forward_leaves(self, reg):
        """After a prefill and some decode steps a slot's rows are the
        latent of a forward over the whole sequence, no other slot was
        written, and the cache has no per-head key or value anywhere."""
        from horovod_tpu.models import latent_moe
        cfg, params = _tiny_latent_moe()
        engine = _never_ahead(_engine(cfg, params, num_slots=2, max_len=32))
        prompt = _prompt(9, 4)
        engine.submit(Request("r", prompt, max_new_tokens=6))
        for _ in range(4):
            engine.step()
        (slot, st), = engine._active.items()
        seq = list(prompt) + st.generated[:-1]
        n = len(seq)
        assert n == engine.kv.ledger.length(slot) == 13
        _, latent, _ = latent_moe.hidden_states(
            cfg, params, jnp.asarray([seq], jnp.int32))
        held = np.asarray(engine.kv.arrays["latent"])
        np.testing.assert_allclose(held[:, slot, :n],
                                   np.asarray(latent)[:, 0], atol=1e-5)
        assert not held[:, 1 - slot, :31].any()
        assert all(a.shape[3] == 1 for a in engine.kv.arrays.values())

    def test_the_step_record_counts_what_a_pass_routed(self, reg):
        """``experts_touched`` and ``expert_tokens_max`` come back with
        the pass's ids: on the step that READS the pass, which with a
        pass in flight is the step after its launch; ``kv_bytes`` counts
        the latent; no other family's record has the counts."""
        cfg, params = _tiny_latent_moe()
        engine = _engine(cfg, params, num_slots=2, max_len=32)
        _warm(engine)
        results, recs = _drive(engine, [("a", _prompt(5, 1), 6),
                                        ("b", _prompt(9, 2), 6)])
        assert all(r.outcome == "completed" for r in results.values())
        decoded = [r for r in recs if r["active"]]
        position = 3 * 128 * 4
        for r in decoded:
            assert r["kv_bytes"] % (8 * position) == 0 and r["kv_bytes"] > 0
        ahead = [r for r in decoded if r["ahead"]]
        assert ahead                       # both slots busy: passes in flight
        for r in recs:
            reads_a_pass = any(
                p[0] == "decode_readback" for p in r["phases"])
            assert ("experts_touched" in r) == reads_a_pass
            if reads_a_pass:
                # two expert layers, two rows of two experts each
                assert 4 <= r["experts_touched"] <= 8
                assert 1 <= r["expert_tokens_max"] <= 2
        # a step that ran ahead read the pass of the step before it
        first = recs.index(ahead[0])
        assert "experts_touched" in recs[first + 1]
        for name in ("dense", "hybrid", "looped"):
            cfg2, params2 = MODELS[name]()
            _, recs2 = _drive(_engine(cfg2, params2),
                              [("x", _prompt(5, 1), 3)])
            assert not any("experts_touched" in r for r in recs2)

    def test_an_engine_over_a_mesh_is_refused_by_name(self):
        from horovod_tpu.parallel import mesh as mesh_lib
        cfg, params = _tiny_latent_moe()
        mesh = mesh_lib.build_mesh(tp=2)
        with pytest.raises(NotImplementedError,
                           match="LatentMoEConfig serves on one chip"):
            _engine(cfg, params, mesh=mesh)
        with pytest.raises(NotImplementedError,
                           match="LatentMoEConfig's cache .latent. has no "
                                 "sharding over a mesh"):
            KVCache(cfg, 2, max_len=32, mesh=mesh)
