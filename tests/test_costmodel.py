"""Roofline cost model (utils/costmodel.py): chip table lookup,
analytic FLOPs/bytes vs hand-computed values for a small LM config,
per-class verdicts, and the achievable-MFU decomposition."""

import math
import types

import pytest

from horovod_tpu.utils import costmodel


def _cfg(num_layers=2, d_model=8, d_ff=16, vocab_size=32):
    return types.SimpleNamespace(num_layers=num_layers, d_model=d_model,
                                 d_ff=d_ff, vocab_size=vocab_size)


# hand-computed for the _cfg defaults:
# p_matmul = 2*(4*8^2 + 3*8*16) + 8*32 = 2*(256+384) + 256 = 1536
P_MATMUL = 1536


class TestChipSpec:
    def test_longest_prefix_wins(self):
        assert costmodel.chip_spec("TPU v5 lite").peak_flops == 197e12
        assert costmodel.chip_spec("TPU v5").peak_flops == 459e12
        assert costmodel.chip_spec("TPU v5p").peak_flops == 459e12
        assert costmodel.chip_spec("TPU v4").peak_flops == 275e12

    def test_device_object_and_unknown(self):
        dev = types.SimpleNamespace(device_kind="TPU v6e")
        assert costmodel.chip_spec(dev).peak_flops == 918e12
        assert costmodel.chip_spec("GPU A100") is None
        assert costmodel.chip_spec(None) is None

    def test_peak_flops_none_for_cpu_and_unknown(self):
        # cpu has a spec row (CI exercises the full path) but no
        # meaningful MFU denominator
        assert costmodel.chip_spec("cpu") is not None
        assert costmodel.peak_flops("cpu") is None
        assert costmodel.peak_flops("GPU A100") is None
        assert costmodel.peak_flops("TPU v4") == 275e12

    def test_ridge_point(self):
        spec = costmodel.ChipSpec("t", 200e12, 1e12, 1e11)
        assert spec.ridge_flops_per_byte == pytest.approx(200.0)


class TestProgramCosts:
    def test_dict_and_list_forms(self):
        ca = {"flops": 10.0, "bytes accessed": 4.0}
        c = types.SimpleNamespace(cost_analysis=lambda: ca)
        assert costmodel.program_costs(c) == {"flops": 10.0, "bytes": 4.0}
        c = types.SimpleNamespace(cost_analysis=lambda: [ca])
        assert costmodel.program_costs(c) == {"flops": 10.0, "bytes": 4.0}

    def test_missing_or_failing(self):
        c = types.SimpleNamespace(
            cost_analysis=lambda: (_ for _ in ()).throw(RuntimeError()))
        assert costmodel.program_costs(c) is None
        c = types.SimpleNamespace(cost_analysis=lambda: [])
        assert costmodel.program_costs(c) is None
        c = types.SimpleNamespace(cost_analysis=lambda: {"other": 1})
        assert costmodel.program_costs(c) is None


class TestAnalyticLMCosts:
    def test_matches_transformer_convention(self):
        # the model's P_matmul must be THE p_matmul of the headline MFU
        from horovod_tpu.models import transformer as tr
        cfg = tr.TransformerConfig()
        seq = 128
        assert (6 * costmodel.lm_matmul_params(cfg) +
                12 * cfg.num_layers * seq * cfg.d_model ==
                tr.matmul_flops_per_token(cfg, seq))

    def test_hand_computed_small_config(self):
        # seq=4, batch_per_chip=3 → 12 tokens; 4 chips → ring 3/4
        costs = costmodel.analytic_lm_costs(_cfg(), seq=4,
                                            batch_per_chip=3, n_chips=4)
        assert costmodel.lm_matmul_params(_cfg()) == P_MATMUL
        assert costs["matmul"]["flops"] == 6 * P_MATMUL * 12       # 110592
        assert costs["matmul"]["hbm_bytes"] == 3 * P_MATMUL * 2    # 9216
        assert costs["matmul"]["wire_bytes"] == 0.0
        assert costs["attention"]["flops"] == 12 * 2 * 4 * 8 * 12  # 9216
        assert costs["attention"]["hbm_bytes"] == 10 * 2 * 12 * 8 * 2
        assert costs["collective"]["flops"] == 0.0
        assert costs["collective"]["wire_bytes"] == pytest.approx(
            2 * P_MATMUL * 2.0 * 0.75)                             # 4608
        assert costs["collective"]["hbm_bytes"] == 2 * P_MATMUL * 2

    def test_single_chip_has_no_wire(self):
        costs = costmodel.analytic_lm_costs(_cfg(), seq=4,
                                            batch_per_chip=3, n_chips=1)
        assert costs["collective"]["wire_bytes"] == 0.0
        assert costs["collective"]["hbm_bytes"] == 0.0

    def test_int8_wire_width_halves_bytes(self):
        bf16 = costmodel.analytic_lm_costs(_cfg(), 4, 3, n_chips=4)
        int8 = costmodel.analytic_lm_costs(_cfg(), 4, 3, n_chips=4,
                                           wire_bytes_per_param=1.0)
        assert int8["collective"]["wire_bytes"] == pytest.approx(
            bf16["collective"]["wire_bytes"] / 2)


SPEC = costmodel.ChipSpec("test", 1e6, 1e6, 1e5)


class TestRoofline:
    def test_verdicts_and_bounds(self):
        costs = costmodel.analytic_lm_costs(_cfg(), 4, 3, n_chips=4)
        rl = costmodel.roofline(costs, SPEC)
        # matmul: 110592 flops / 1e6 = 110.592 ms compute vs 9.216 mem
        assert rl["matmul"]["verdict"] == "compute-bound"
        assert rl["matmul"]["bound_ms"] == pytest.approx(110.592)
        assert rl["matmul"]["arith_intensity"] == pytest.approx(12.0)
        assert rl["matmul"]["ridge_flops_per_byte"] == pytest.approx(1.0)
        assert rl["attention"]["verdict"] == "compute-bound"
        assert rl["attention"]["bound_ms"] == pytest.approx(9.216)
        # collective: 4608 wire bytes / 1e5 = 46.08 ms > 6144/1e6 hbm
        assert rl["collective"]["verdict"] == "comm-bound"
        assert rl["collective"]["bound_ms"] == pytest.approx(46.08)
        assert rl["collective"]["arith_intensity"] == pytest.approx(0.0)

    def test_memory_bound_class(self):
        rl = costmodel.roofline(
            {"copyish": {"flops": 10.0, "hbm_bytes": 1e6}}, SPEC)
        assert rl["copyish"]["verdict"] == "memory-bound"
        assert rl["copyish"]["bound_ms"] == pytest.approx(1000.0)


class TestMFUDecomposition:
    COSTS = None

    def setup_method(self):
        self.costs = costmodel.analytic_lm_costs(_cfg(), 4, 3, n_chips=4)

    def test_measured_vs_roofline(self):
        dec = costmodel.mfu_decomposition(200.0, self.costs, SPEC)
        # total flops 119808; roofline_ms = 110.592+9.216+46.08
        assert dec["flops_per_step"] == pytest.approx(119808)
        assert dec["roofline_ms_per_step"] == pytest.approx(165.888)
        assert dec["measured_mfu"] == pytest.approx(0.599, abs=1e-3)
        assert dec["roofline_mfu"] == pytest.approx(0.7222, abs=1e-3)
        assert dec["mfu_gap"] == pytest.approx(
            dec["roofline_mfu"] - dec["measured_mfu"], abs=1e-4)

    def test_gap_attribution_by_class(self):
        by_class = {"matmul": 120.0, "attention": 12.0,
                    "collective": 50.0}
        dec = costmodel.mfu_decomposition(200.0, self.costs, SPEC,
                                          measured_ms_by_class=by_class)
        gap = dec["gap_by_class"]
        # excess: matmul 9.408, attention 2.784, collective 3.92,
        # residual 200-182=18 → shares of the total gap
        total_excess = 9.408 + 2.784 + 3.92 + 18.0
        assert gap["matmul"] == pytest.approx(
            dec["mfu_gap"] * 9.408 / total_excess, abs=1e-4)
        assert gap["residual"] == pytest.approx(
            dec["mfu_gap"] * 18.0 / total_excess, abs=1e-4)
        assert sum(gap.values()) == pytest.approx(dec["mfu_gap"],
                                                  abs=1e-3)

    def test_zero_measured_ms_guarded(self):
        dec = costmodel.mfu_decomposition(0.0, self.costs, SPEC)
        assert dec["measured_mfu"] is None
        assert "mfu_gap" not in dec


class TestMeasuredClassMs:
    def test_folds_profile_classes(self):
        dec = {"classes": [
            {"class": "flash_fwd", "ms_per_step": 1.0},
            {"class": "flash_dq", "ms_per_step": 2.0},
            {"class": "flash_dkv", "ms_per_step": 3.0},
            {"class": "flash_bwd", "ms_per_step": 1.5},
            {"class": "matmul", "ms_per_step": 10.0},
            {"class": "collective", "ms_per_step": 4.0},
            {"class": "copy", "ms_per_step": 0.5},
            {"class": "fusion", "ms_per_step": 0.5},
        ]}
        ms = costmodel.measured_class_ms(dec)
        assert ms == {"attention": 7.5, "matmul": 10.0,
                      "collective": 4.0, "other": 1.0}

    def test_empty(self):
        assert costmodel.measured_class_ms(None) == {}
        assert costmodel.measured_class_ms({}) == {}


class TestLMAttribution:
    def test_end_to_end_wrapper(self):
        dec = {"classes": [{"class": "matmul", "ms_per_step": 120.0},
                           {"class": "collective", "ms_per_step": 50.0}]}
        out = costmodel.lm_attribution(_cfg(), 4, 3, SPEC, 200.0,
                                       decomposition=dec, n_chips=4)
        assert out["chip"]["kind"] == "test"
        assert out["n_chips"] == 4
        assert out["classes"]["collective"]["verdict"] == "comm-bound"
        assert out["measured_mfu"] is not None
        assert "gap_by_class" in out
