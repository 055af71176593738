"""Device-trace summarization (utils/profiling.py): aggregation,
filtering of host-side spans, group totals, and file discovery."""

import gzip
import json

import pytest

from horovod_tpu.utils import profiling


def _write_trace(tmp_path, events, gz=True):
    d = tmp_path / "plugins" / "profile" / "2026_01_01"
    d.mkdir(parents=True)
    payload = json.dumps({"traceEvents": events})
    p = d / ("t.trace.json.gz" if gz else "t.trace.json")
    if gz:
        with gzip.open(p, "wt") as f:
            f.write(payload)
    else:
        p.write_text(payload)
    return tmp_path


def _ev(name, dur, ts=0, pid=1, tid=1, **args):
    e = {"ph": "X", "name": name, "dur": dur, "ts": ts,
         "pid": pid, "tid": tid}
    if args:
        e["args"] = args
    return e


class TestSummarizeTrace:
    def test_aggregates_and_filters(self, tmp_path):
        root = _write_trace(tmp_path, [
            _ev("fusion.1", 1000, long_name="%fusion.1 = f32[8]"),
            _ev("fusion.1", 500),
            _ev("fusion.2", 2000),
            _ev("attn.3", 4000),
            _ev("$python_span", 99999),        # host-side: excluded
            _ev("jit_step(123)", 99999),       # dispatch wrapper: excluded
            _ev("2", 99999),                   # step-group lane: excluded
            {"ph": "M", "name": "meta"},       # not a complete event
        ])
        s = profiling.summarize_trace(str(root))
        by_name = {r.name: r for r in s.rows}
        assert set(by_name) == {"fusion.1", "fusion.2", "attn.3"}
        assert by_name["fusion.1"].total_ms == pytest.approx(1.5)
        assert by_name["fusion.1"].count == 2
        assert by_name["fusion.1"].long_name.startswith("%fusion.1")
        assert s.total_ms == pytest.approx(7.5)
        # sorted by total, groups aggregate fusion.1 + fusion.2
        assert s.rows[0].name == "attn.3"
        assert dict(s.by_group()) == pytest.approx(
            {"fusion": 3.5, "attn": 4.0})

    def test_find_trace_file_errors(self, tmp_path):
        with pytest.raises(FileNotFoundError, match="trace.json"):
            profiling.find_trace_file(str(tmp_path))

    def test_uncompressed_trace_discovered(self, tmp_path):
        root = _write_trace(tmp_path, [
            _ev("f.1", 250, long_name=""),      # args-less long_name...
            _ev("f.1", 250, long_name="%f.1"),  # ...backfilled later
        ], gz=False)
        s = profiling.summarize_trace(str(root))
        (row,) = s.rows
        assert row.total_ms == pytest.approx(0.5)
        assert row.long_name == "%f.1"

    def test_cli_main(self, tmp_path, capsys):
        root = _write_trace(tmp_path, [_ev("fusion.9", 1500)])
        profiling.main([str(root), "-n", "5"])
        out = capsys.readouterr().out
        assert "device-op total: 1.5 ms" in out
        assert "fusion.9" in out

    def test_retains_lane_intervals_and_names(self, tmp_path):
        root = _write_trace(tmp_path, [
            {"ph": "M", "name": "thread_name", "pid": 1, "tid": 7,
             "args": {"name": "TPU core 0 compute"}},
            _ev("fusion.1", 1000, ts=500, pid=1, tid=7),
            _ev("all-reduce.2", 2000, ts=1000, pid=1, tid=9),
        ])
        s = profiling.summarize_trace(str(root))
        assert s.lane_names == {"1/7": "TPU core 0 compute"}
        by_name = {e.name: e for e in s.events}
        assert by_name["fusion.1"].lane == "1/7"
        assert by_name["fusion.1"].start_ms == pytest.approx(0.5)
        assert by_name["fusion.1"].end_ms == pytest.approx(1.5)
        assert by_name["all-reduce.2"].lane == "1/9"


class TestClassifyOp:
    # representative XLA HLO / Pallas custom-call names → expected class;
    # pins the _OP_CLASSES table against silent rot
    CASES = [
        ("%all-reduce.1", "", "collective"),
        ("all-reduce-start.7", "", "collective"),
        ("reduce-scatter.3", "", "collective"),
        ("all-gather.12", "", "collective"),
        ("all-to-all.2", "", "collective"),
        ("%dot.42", "", "matmul"),
        ("dot_general.5", "", "matmul"),
        ("convolution.8", "", "matmul"),
        ("custom-call.3", "%custom-call.3 = ... fwd_kernel", "flash_fwd"),
        ("custom-call.4", "%custom-call.4 = ... dq_kernel", "flash_dq"),
        ("custom-call.5", "%custom-call.5 = ... dkv_kernel", "flash_dkv"),
        ("flash_backward.2", "%flash_backward.2 = ... custom-call(",
         "flash_bwd"),
        ("copy.9", "", "copy"),
        ("transpose.1", "", "copy"),
        ("dynamic-update-slice.6", "", "copy"),
        ("bitcast.2", "", "copy"),
        # note: bitcast-CONVert / input_CONCATENATE_fusion would land in
        # matmul/copy via substring first-match — the table is ordered,
        # not exact; keep needles honest when extending it
        ("fusion.123", "", "fusion"),
        ("loop_add_fusion.4", "", "fusion"),
        ("output_tanh_fusion", "", "fusion"),
        ("broadcast.77", "", "other"),
        ("rng-bit-generator.1", "", "other"),
    ]

    @pytest.mark.parametrize("name,long_name,expected", CASES)
    def test_table(self, name, long_name, expected):
        row = profiling.OpRow(name, name.split(".")[0], 1.0, 1, long_name)
        assert profiling.classify_op(row) == expected

    def test_first_match_wins_over_long_name(self):
        # a fusion whose long_name mentions a dot: collective/flash
        # classes are checked first, then matmul — "dot" in the
        # long_name promotes it to matmul before the fusion fallback
        row = profiling.OpRow("fusion.1", "fusion", 1.0, 1,
                              "%fusion.1 = fusion(dot.3)")
        assert profiling.classify_op(row) == "matmul"


class TestOverlapAccounting:
    def _mixed_root(self, tmp_path):
        # lane 1/1 = compute, lane 1/2 = async collective stream.
        # compute busy [0,4)ms and [6,8)ms; comm busy [2,7)ms
        # → hidden = [2,4)+[6,7) = 3ms, exposed = [4,6) = 2ms
        return _write_trace(tmp_path, [
            _ev("fusion.1", 4000, ts=0, tid=1),
            _ev("dot.2", 2000, ts=6000, tid=1),
            _ev("all-reduce.3", 5000, ts=2000, tid=2),
        ])

    def test_hidden_vs_exposed(self, tmp_path):
        s = profiling.summarize_trace(str(self._mixed_root(tmp_path)))
        ov = profiling.overlap_accounting(s)
        assert ov["comm_ms_per_step"] == pytest.approx(5.0)
        assert ov["compute_ms_per_step"] == pytest.approx(6.0)
        assert ov["hidden_comm_ms"] == pytest.approx(3.0)
        assert ov["exposed_comm_ms"] == pytest.approx(2.0)
        assert ov["overlap_frac"] == pytest.approx(0.6)
        assert ov["span_ms_per_step"] == pytest.approx(8.0)
        lanes = {l["lane"]: l for l in ov["lanes"]}
        assert lanes["1/1"]["busy_ms_per_step"] == pytest.approx(6.0)
        assert lanes["1/1"]["busy_frac"] == pytest.approx(0.75)
        assert lanes["1/2"]["busy_ms_per_step"] == pytest.approx(5.0)
        assert lanes["1/2"]["busy_frac"] == pytest.approx(0.625)

    def test_fully_hidden_comm(self, tmp_path):
        root = _write_trace(tmp_path, [
            _ev("fusion.1", 8000, ts=0, tid=1),
            _ev("all-reduce.2", 3000, ts=2000, tid=2),
        ])
        ov = profiling.overlap_accounting(str(root))
        assert ov["hidden_comm_ms"] == pytest.approx(3.0)
        assert ov["exposed_comm_ms"] == pytest.approx(0.0)
        assert ov["overlap_frac"] == pytest.approx(1.0)

    def test_fully_exposed_comm_and_steps(self, tmp_path):
        # comm strictly after compute, over 2 steps → per-step halves
        root = _write_trace(tmp_path, [
            _ev("fusion.1", 4000, ts=0, tid=1),
            _ev("all-reduce.2", 6000, ts=4000, tid=2),
        ])
        ov = profiling.overlap_accounting(str(root), steps=2)
        assert ov["hidden_comm_ms"] == pytest.approx(0.0)
        assert ov["exposed_comm_ms"] == pytest.approx(3.0)
        assert ov["overlap_frac"] == pytest.approx(0.0)
        assert ov["comm_ms_per_step"] == pytest.approx(3.0)

    def test_no_comm_gives_none_frac(self, tmp_path):
        root = _write_trace(tmp_path, [_ev("fusion.1", 1000, tid=1)])
        ov = profiling.overlap_accounting(str(root))
        assert ov["comm_ms_per_step"] == pytest.approx(0.0)
        assert ov["overlap_frac"] is None

    def test_overlapping_same_class_intervals_union(self, tmp_path):
        # two overlapping collectives must not double-count
        root = _write_trace(tmp_path, [
            _ev("all-reduce.1", 4000, ts=0, tid=2),
            _ev("all-reduce.2", 4000, ts=2000, tid=3),
        ])
        ov = profiling.overlap_accounting(str(root))
        assert ov["comm_ms_per_step"] == pytest.approx(6.0)
        assert ov["exposed_comm_ms"] == pytest.approx(6.0)

    def test_rows_only_summary_returns_none(self):
        rows = [profiling.OpRow("fusion.1", "fusion", 1.0, 1, "")]
        assert profiling.overlap_accounting(
            profiling.TraceSummary(rows)) is None


class TestProfileDecomposition:
    def test_classes_wall_and_overlap(self, tmp_path):
        root = _write_trace(tmp_path, [
            _ev("fusion.1", 4000, ts=0, tid=1),
            _ev("all-reduce.3", 5000, ts=2000, tid=2),
        ])
        dec = profiling.profile_decomposition(str(root), wall_ms=10.0)
        assert dec["device_ms_per_step"] == pytest.approx(9.0)
        assert dec["wall_ms_per_step"] == pytest.approx(10.0)
        assert dec["residual_ms_per_step"] == pytest.approx(1.0)
        assert dec["device_busy_frac"] == pytest.approx(0.9)
        by_cls = {c["class"]: c for c in dec["classes"]}
        assert by_cls["collective"]["ms_per_step"] == pytest.approx(5.0)
        assert by_cls["fusion"]["ms_per_step"] == pytest.approx(4.0)
        assert dec["overlap"]["hidden_comm_ms"] == pytest.approx(2.0)
        assert dec["overlap"]["exposed_comm_ms"] == pytest.approx(3.0)

    def test_wall_ms_zero_guarded(self, tmp_path):
        # wall_ms=0 used to emit residual=-device_ms with frac None;
        # now both are None and the wall is reported as 0
        root = _write_trace(tmp_path, [_ev("fusion.1", 1000)])
        dec = profiling.profile_decomposition(str(root), wall_ms=0.0)
        assert dec["wall_ms_per_step"] == 0.0
        assert dec["residual_ms_per_step"] is None
        assert dec["device_busy_frac"] is None

    def test_wall_ms_none_omits_wall_keys(self, tmp_path):
        root = _write_trace(tmp_path, [_ev("fusion.1", 1000)])
        dec = profiling.profile_decomposition(str(root))
        assert "wall_ms_per_step" not in dec
        assert "residual_ms_per_step" not in dec

    def test_cli_overlap_flag(self, tmp_path, capsys):
        root = _write_trace(tmp_path, [
            _ev("fusion.1", 4000, ts=0, tid=1),
            _ev("all-reduce.2", 2000, ts=1000, tid=2),
        ])
        profiling.main([str(root), "--overlap"])
        out = json.loads(capsys.readouterr().out)
        assert out["hidden_comm_ms"] == pytest.approx(2.0)
