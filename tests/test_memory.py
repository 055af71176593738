"""Memory & compile observability plane (docs/memory.md,
utils/memory.py): HBM-ledger attribution against hand-computed bytes,
the pre-flight planner validated against the measured ledger on real
placed state (dp-only and dp×tp=2), the recompile-storm escalation
ladder (event → warning → deduped flight dump), the GSPMD resharding
sentinel (mis-specced drill + the clean make_gspmd_step negative arm),
and the flight-dump/postmortem surfacing. Runs on the conftest
8-device virtual CPU mesh; no coordinator."""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu import trainer
from horovod_tpu.models import transformer as tr
from horovod_tpu.parallel import mesh as mesh_lib
from horovod_tpu.utils import memory as hvd_memory
from horovod_tpu.utils import metrics as hvd_metrics
from horovod_tpu.utils import tracing as hvd_tracing

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"))
import hvd_postmortem  # noqa: E402

# the planner's accuracy contract (docs/memory.md §2, ISSUE 18)
PLAN_RTOL = 0.15


@pytest.fixture(autouse=True)
def _fresh_memory_plane():
    """Every test starts with the plane force-enabled and fresh
    singletons, and ends back at the env default — ledger/tracker
    leakage between tests is exactly what reset() exists to prevent."""
    hvd_memory.reset(enabled=True)
    mesh_lib.reset_global_mesh()
    yield
    mesh_lib.reset_global_mesh()
    hvd_memory.reset()


@pytest.fixture
def reg():
    r = hvd_metrics.reset(enabled=True)
    yield r
    hvd_metrics.reset()


def _values(snap, name):
    return {tuple(sorted(v["labels"].items())): v["value"]
            for v in snap["metrics"].get(name, {}).get("values", [])}


# ---------------------------------------------------------------------------
# the HBM ledger: attribution vs hand-computed bytes
# ---------------------------------------------------------------------------

class TestLedger:
    def test_account_tree_matches_hand_computed(self):
        ledger = hvd_memory.HBMLedger(capacity_bytes=1 << 20)
        params = {"w": jnp.zeros((16, 32), jnp.float32),
                  "b": jnp.zeros((32,), jnp.float32)}
        ledger.account_tree("params", params)
        want = 16 * 32 * 4 + 32 * 4
        snap = ledger.snapshot()
        assert snap["components"]["params"] == want
        assert snap["total_bytes"] == want
        assert snap["headroom_bytes"] == (1 << 20) - want

    def test_account_is_absolute_not_cumulative(self):
        ledger = hvd_memory.HBMLedger(capacity_bytes=None)
        ledger.account("grads", 100)
        ledger.account("grads", 40)  # re-statement, not accumulation
        assert ledger.snapshot()["components"]["grads"] == 40
        assert ledger.total_bytes() == 40

    def test_sharded_leaf_counts_shard_bytes(self):
        mesh = mesh_lib.build_mesh(tp=2)
        w = jax.device_put(jnp.zeros((8, 16), jnp.float32),
                           NamedSharding(mesh, P("tp", None)))
        # committed sharding: one chip holds 4×16 of the 8×16 leaf
        assert hvd_memory.tree_per_chip_bytes({"w": w}) == 4 * 16 * 4

    def test_abstract_tree_shards_by_spec_math(self):
        mesh = mesh_lib.build_mesh(tp=2)
        abstract = {"w": jax.ShapeDtypeStruct((8, 16), jnp.float32),
                    "b": jax.ShapeDtypeStruct((16,), jnp.float32)}
        specs = {"w": P("tp", None), "b": P()}
        got = hvd_memory.tree_per_chip_bytes(abstract, specs, mesh)
        assert got == 4 * 16 * 4 + 16 * 4

    def test_opt_state_bytes_are_adams_two_x(self):
        params = {"w": jnp.zeros((16, 32), jnp.float32)}
        opt = optax.adam(1e-3).init(params)
        ledger = hvd_memory.HBMLedger(capacity_bytes=None)
        ledger.account_tree("opt_state", opt)
        pb = 16 * 32 * 4
        # mu + nu in param dtype, plus the int32 count scalar
        assert ledger.snapshot()["components"]["opt_state"] == 2 * pb + 4

    def test_account_kv_rides_per_chip_bytes(self):
        from horovod_tpu.serving.kv_cache import KVCache
        cfg = tr.TransformerConfig.tiny()
        kv = KVCache(cfg, num_slots=2, max_len=32)
        ledger = hvd_memory.HBMLedger(capacity_bytes=None)
        ledger.account_kv(kv)
        head_dim = cfg.d_model // cfg.num_heads
        want = (2 * cfg.num_layers * 2 * 32 * cfg.num_heads * head_dim
                * jnp.dtype(cfg.dtype).itemsize)
        assert ledger.snapshot()["components"]["kv_cache"] == want

    def test_publish_refreshes_gauges(self, reg):
        ledger = hvd_memory.HBMLedger(capacity_bytes=1000)
        ledger.account("params", 600)
        ledger.account("grads", 100)
        snap = reg.snapshot()
        by_comp = _values(snap, "hvd_hbm_bytes")
        assert by_comp[(("component", "params"),)] == 600
        assert by_comp[(("component", "grads"),)] == 100
        assert _values(snap, "hvd_hbm_capacity_bytes")[()] == 1000
        assert _values(snap, "hvd_hbm_headroom_bytes")[()] == 300


# ---------------------------------------------------------------------------
# plan vs measured: the ≤15% contract on real placed state
# ---------------------------------------------------------------------------

def _measured_components(cfg, mesh):
    """Place real params + adam state through the spec tree and account
    them — the same calls the trainer makes."""
    _, params = tr.init_params(cfg, jax.random.PRNGKey(0))
    specs = tr.param_specs(params)
    tx = optax.adam(1e-3)
    p = trainer.place(params, mesh, specs)
    opt = trainer.init_opt_state(tx, p, mesh, specs)
    ledger = hvd_memory.HBMLedger(capacity_bytes=None)
    ledger.account_tree("params", p)
    ledger.account_tree("opt_state", opt)
    return ledger.snapshot()["components"]


@pytest.mark.parametrize("layout", [dict(), dict(tp=2)],
                         ids=["dp_only", "dp_x_tp2"])
def test_plan_within_15pct_of_measured(layout):
    cfg = tr.TransformerConfig.tiny(dtype=jnp.float32)
    mesh = mesh_lib.build_mesh(**layout)
    measured = _measured_components(cfg, mesh)
    plan = hvd_memory.plan_memory(
        cfg, dp=mesh.shape.get("dp", 1), tp=mesh.shape.get("tp", 1))
    for comp in ("params", "opt_state"):
        got, want = plan["components"][comp], measured[comp]
        assert abs(got - want) <= PLAN_RTOL * want, \
            f"{comp}: planned {got} vs measured {want}"
    # grads mirror params by construction; the plan must say so too
    assert plan["components"]["grads"] == plan["components"]["params"]


def test_plan_tp_shards_params_and_fits_verdict():
    cfg = tr.TransformerConfig.tiny()
    flat = hvd_memory.plan_memory(cfg, chip="cpu")
    split = hvd_memory.plan_memory(cfg, tp=2, chip="cpu")
    assert split["components"]["params"] < flat["components"]["params"]
    assert flat["capacity_bytes"] and flat["fits"] is True
    assert flat["headroom_bytes"] == \
        flat["capacity_bytes"] - flat["total_bytes"]


def test_plan_optimizer_factor_and_kv_math():
    cfg = tr.TransformerConfig.tiny()
    adam = hvd_memory.plan_memory(cfg, optimizer="adam")
    sgd = hvd_memory.plan_memory(cfg, optimizer="sgd")
    none = hvd_memory.plan_memory(cfg, optimizer="none")
    pb = adam["components"]["params"]
    assert adam["components"]["opt_state"] == 2 * pb
    assert sgd["components"]["opt_state"] == pb
    assert none["components"]["opt_state"] == 0
    kv = hvd_memory.plan_memory(cfg, kv_slots=4, kv_max_len=64)
    head_dim = cfg.d_model // cfg.num_heads
    assert kv["components"]["kv_cache"] == (
        2 * cfg.num_layers * 4 * 64 * cfg.num_heads * head_dim
        * jnp.dtype(cfg.dtype).itemsize)


# ---------------------------------------------------------------------------
# compile observability: hit/miss + the storm ladder
# ---------------------------------------------------------------------------

def _args_of_len(n):
    return (jnp.zeros((1, n), jnp.int32),)


class TestCompileTracker:
    def test_hit_miss_accounting(self, reg):
        t = hvd_memory.CompileTracker(min_misses=10 ** 6)
        assert t.observe("train:unit", _args_of_len(8)) == "miss"
        assert t.observe("train:unit", _args_of_len(8)) == "hit"
        assert t.observe("train:unit", _args_of_len(9)) == "miss"
        s = t.site_summary()["train:unit"]
        assert s["hits"] == 1 and s["misses"] == 2
        assert not s["storming"]
        by_outcome = _values(reg.snapshot(), "hvd_compile_total")
        assert by_outcome[(("outcome", "hit"),
                           ("site", "train:unit"))] == 1
        assert by_outcome[(("outcome", "miss"),
                           ("site", "train:unit"))] == 2

    def test_abstract_key_formats_dtype_and_shape(self):
        key = hvd_memory.abstract_key((jnp.zeros((2, 3), jnp.float32),
                                       jnp.zeros((4,), jnp.int32)))
        assert hvd_memory.format_key(key) == "float32[2,3] int32[4]"
        long = hvd_memory.abstract_key(
            tuple(jnp.zeros((i + 1,)) for i in range(10)))
        assert hvd_memory.format_key(long).endswith("...+2")

    def test_first_compile_is_free(self):
        t = hvd_memory.CompileTracker(decay=0.5, threshold=0.1,
                                      min_misses=1)
        t.observe("train:unit", _args_of_len(8))
        assert not t.site_summary()["train:unit"]["storming"]

    def test_storm_escalation_names_site_and_key(self, reg):
        # the escalation evidence is asserted on the EVENT, not caplog:
        # the repo's logging bootstrap puts a handler on the horovod_tpu
        # logger, so caplog capture is suite-order-dependent while the
        # metrics event ring is not
        t = hvd_memory.CompileTracker(decay=0.5, threshold=0.4,
                                      min_misses=3)
        for n in range(6):
            t.observe("serve_prefill", _args_of_len(16 + n))
        s = t.site_summary()["serve_prefill"]
        assert s["storming"] and s["misses"] == 6
        assert "int32[1,21]" in s["last_key"]
        storm = [e for e in reg.events()
                 if e["event"] == "recompile_storm"]
        assert len(storm) == 1
        assert storm[0]["site"] == "serve_prefill"
        assert "int32[1," in storm[0]["key"]
        assert _values(reg.snapshot(), "hvd_recompile_storms_total")[
            (("site", "serve_prefill"),)] == 1

    def test_storm_flight_dump_deduped_per_site(self, reg, tmp_path):
        tracer = hvd_tracing.reset(enabled=True, rank=0)
        tracer._dump_dir = str(tmp_path)
        try:
            t = hvd_memory.CompileTracker(decay=0.5, threshold=0.4,
                                          min_misses=3)
            for n in range(4):  # storm #1 → the one dump
                t.observe("serve_prefill", _args_of_len(16 + n))
            for _ in range(4):  # hits decay the EMA; the storm clears
                t.observe("serve_prefill", _args_of_len(16))
            assert not t.site_summary()["serve_prefill"]["storming"]
            for n in range(4):  # storm #2: event again, dump deduped
                t.observe("serve_prefill", _args_of_len(64 + n))
            assert t.site_summary()["serve_prefill"]["storming"]
            snap = reg.snapshot()
            assert _values(snap, "hvd_recompile_storms_total")[
                (("site", "serve_prefill"),)] == 2
            assert _values(snap, "hvd_flight_dumps_total")[
                (("reason", "recompile_storm"),)] == 1
        finally:
            hvd_tracing.reset()

    def test_instrument_compiles_wrapper(self, reg):
        calls = []
        wrapped = hvd_memory.instrument_compiles(
            lambda x: calls.append(x) or x, site="train:unit")
        wrapped(jnp.zeros((2,)))
        wrapped(jnp.zeros((3,)))
        assert len(calls) == 2  # the wrapped fn always runs
        s = hvd_memory.get_tracker().site_summary()["train:unit"]
        assert s["misses"] == 2

    def test_trainer_step_reports_compile_site(self, reg):
        step = trainer.instrument_step(lambda x: x, name="unit")
        step(jnp.zeros((4,)))
        step(jnp.zeros((4,)))
        s = hvd_memory.get_tracker().site_summary()["train:unit"]
        assert s["misses"] == 1 and s["hits"] == 1


# ---------------------------------------------------------------------------
# GSPMD resharding sentinel
# ---------------------------------------------------------------------------

class TestReshardingSentinel:
    def test_mis_specced_jit_names_leaf_and_axis(self, reg):
        mesh = mesh_lib.build_mesh(tp=2)
        w = jax.device_put(jnp.zeros((8, 16), jnp.float32),
                           NamedSharding(mesh, P("tp", None)))
        # the drill: declared row-sharded, consumed replicated — GSPMD
        # inserts the all-gather the spec tree says shouldn't exist
        bad = jax.jit(lambda x: x * 2.0,
                      in_shardings=NamedSharding(mesh, P("tp", None)),
                      out_shardings=NamedSharding(mesh, P()))
        findings = hvd_memory.scan_jit_resharding(
            bad, (w,), {"w": w}, {"w": P("tp", None)}, mesh,
            site="drill")
        assert len(findings) == 1
        f = findings[0]
        assert f["leaf"] == "['w']" and f["axis"] == "tp"
        assert f["op"] in ("all-gather", "collective-permute")
        assert f["full_shape"] == [8, 16]
        assert f["shard_shape"] == [4, 16]
        events = [e for e in reg.events()
                  if e["event"] == "resharding_finding"]
        assert events and events[0]["leaf"] == "['w']"
        assert _values(reg.snapshot(),
                       "hvd_resharding_findings_total")[
            (("site", "drill"),)] == 1

    def test_clean_gspmd_step_negative_arm(self, reg):
        # the real training step with CORRECT specs must scan silent:
        # activation collectives (psum over dp, tp matmul gathers that
        # match the declared layout) never pair a param leaf's
        # (full, shard) shapes
        cfg = tr.TransformerConfig.tiny(dtype=jnp.float32,
                                        attention_impl="full")
        model, params = tr.init_params(cfg, jax.random.PRNGKey(0))
        mesh = mesh_lib.build_mesh(tp=2)
        specs = tr.param_specs(params)
        tx = optax.adam(1e-3)
        p = trainer.place(params, mesh, specs)
        opt = trainer.init_opt_state(tx, p, mesh, specs)
        step, _, batch_shard = trainer.make_gspmd_step(
            tr.lm_loss_fn(model), tx, mesh, specs, tr.batch_spec(),
            donate=False, params=p)
        toks = jax.device_put(
            np.zeros((8, 32), np.int32), batch_shard)
        findings = hvd_memory.scan_jit_resharding(
            step, (p, opt, toks), p, specs, mesh, site="gspmd_step")
        assert findings == []
        assert "hvd_resharding_findings_total" not in \
            reg.snapshot()["metrics"]

    def test_hlo_text_parser_matches_param_pair_only(self):
        mesh = mesh_lib.build_mesh(tp=2)
        params = {"w": jax.ShapeDtypeStruct((8, 16), jnp.float32)}
        specs = {"w": P("tp", None)}
        hlo = "\n".join([
            # operands are named, not typed: shapes come from these
            "%p0 = f32[4,16]{1,0} parameter(0)",
            "%p1 = f32[8,16]{1,0} parameter(1)",
            "%p2 = f32[32,32]{1,0} parameter(2)",
            # gathers w's shard back to full: the finding
            "%ag = f32[8,16]{1,0} all-gather(%p0), "
            "replica_groups={{0,1}}, dimensions={0}",
            # an activation all-reduce: same result shape family, no
            # (full, shard) param pair — silent
            "%ar = f32[8,16]{1,0} all-reduce(%p1)",
            # a batch-shaped gather matching no param leaf — silent
            "%bg = f32[64,32]{1,0} all-gather(%p2), "
            "dimensions={0}",
        ])
        findings = hvd_memory.scan_resharding(hlo, params, specs, mesh,
                                              site="unit")
        assert [f["leaf"] for f in findings] == ["['w']"]
        assert findings[0]["dim"] == 0 and findings[0]["axis"] == "tp"


# ---------------------------------------------------------------------------
# flight dumps + postmortem surfacing
# ---------------------------------------------------------------------------

class TestFlightAndPostmortem:
    def test_flight_snapshot_carries_memory_section(self, reg):
        tracer = hvd_tracing.reset(enabled=True, rank=0)
        try:
            hvd_memory.get_ledger().account("params", 4096)
            hvd_memory.get_tracker().observe("train:unit",
                                             _args_of_len(8))
            snap = tracer.flight_snapshot("unit_test")
            mem = snap["memory"]
            assert mem["hbm"]["components"]["params"] == 4096
            assert mem["compile"]["train:unit"]["misses"] == 1
            import json
            json.dumps(snap)  # dump sections must stay serializable
        finally:
            hvd_tracing.reset()

    def test_flight_section_absent_when_off_or_empty(self):
        assert hvd_memory.flight_section() is None  # nothing accounted
        hvd_memory.get_ledger().account("params", 1)
        assert hvd_memory.flight_section() is not None
        hvd_memory.reset(enabled=False)
        assert hvd_memory.flight_section() is None

    def test_postmortem_surfaces_storms_and_memory(self):
        dump = {
            "version": 1, "rank": 0, "reason": "recompile_storm",
            "ts_us": 10_000, "epoch_us_at_ts0": 1_000_000,
            "spans": [], "open_spans": [], "cycles": [],
            "spans_dropped": 0,
            "events": [
                {"event": "recompile_storm", "site": "serve_prefill",
                 "misses": 9, "key": "int32[1,96]"},
                {"event": "resharding_finding", "site": "gspmd_step",
                 "leaf": "['w']", "op": "all-gather", "axis": "tp"},
            ],
            "memory": {
                "hbm": {"components": {"params": 900},
                        "total_bytes": 900, "capacity_bytes": 1000,
                        "headroom_bytes": 50},
                "compile": {},
            },
            "_path": "flight-rank0.json",
        }
        base = hvd_postmortem.rebase([dump])
        verdict = hvd_postmortem.analyze([dump])
        (storm,) = verdict["recompile_storms"]
        assert storm["site"] == "serve_prefill" and storm["misses"] == 9
        (resh,) = verdict["resharding_findings"]
        assert resh["leaf"] == "['w']" and resh["axis"] == "tp"
        assert verdict["memory_by_rank"][0]["hbm"]["headroom_bytes"] == 50
        text = " ".join(verdict["reasons"])
        assert "serve_prefill" in text and "['w']" in text
        assert "OOM territory" in text
        report = hvd_postmortem.render_report(
            [dump], [], verdict, hvd_postmortem.last_cycles([dump], 8),
            base)
        assert "serve_prefill" in report and "memory at dump time" \
            in report
