"""Perf-regression ledger (tools/hvd_perf.py): history ingestion in
both schemas, context-gated comparisons, noise bands, and the gate
tripping on a synthetic 10% slowdown."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir,
                                "tools"))

import hvd_perf  # noqa: E402


def _parsed(value=2350.0, value_pm=None, tokens=119000.0, mfu=0.62,
            ms=137.5, ms_pm=None, batch=16, model="gpt2-small-tpu-flash",
            **extra):
    lm = {"model": model, "tokens_per_sec_per_chip": tokens, "mfu": mfu,
          "seq_len": 1024, "batch_per_chip": batch, "ms_per_step": ms}
    if ms_pm is not None:
        lm["ms_per_step_pm"] = ms_pm
    p = {"metric": "resnet50_synthetic_images_per_sec_per_chip",
         "value": value, "unit": "images/sec/chip",
         "transformer_lm": lm}
    if value_pm is not None:
        p["value_pm"] = value_pm
    p.update(extra)
    return p


def _write(tmp_path, name, parsed, n=None, wrapper=True):
    p = tmp_path / name
    doc = {"n": n, "cmd": "python bench.py", "rc": 0, "tail": "",
           "parsed": parsed} if wrapper else parsed
    p.write_text(json.dumps(doc))
    return str(p)


class TestLoading:
    def test_wrapper_and_raw_schemas(self, tmp_path):
        a = _write(tmp_path, "a.json", _parsed(), n=1)
        b = _write(tmp_path, "b.json", _parsed(), wrapper=False)
        runs = hvd_perf.load_history([a, b])
        assert len(runs) == 2
        assert runs[0].parsed["value"] == 2350.0

    def test_captured_stdout_last_json_line(self, tmp_path):
        p = tmp_path / "run.log"
        p.write_text("warmup chatter\nnot json {\n" +
                     json.dumps(_parsed(value=2400.0)) + "\n")
        (run,) = hvd_perf.load_history([str(p)])
        assert run.parsed["value"] == 2400.0

    def test_rejects_garbage(self, tmp_path):
        p = tmp_path / "x.json"
        p.write_text("{\"unrelated\": 1}")
        with pytest.raises(ValueError, match="neither"):
            hvd_perf.load_run(str(p), 0)

    def test_ordering_provenance_beats_round_number(self, tmp_path):
        old = _write(tmp_path, "z_old.json", _parsed(value=1000.0), n=3)
        new = _write(tmp_path, "a_new.json", _parsed(
            value=2000.0, provenance={"unix_ms": 5, "label": "fresh"}))
        runs = hvd_perf.load_history([new, old])
        assert [r.parsed["value"] for r in runs] == [1000.0, 2000.0]
        assert runs[-1].label == "fresh"


class TestCompare:
    def test_within_threshold_ok(self, tmp_path):
        files = [_write(tmp_path, "r1.json", _parsed(value=2350.0), n=1),
                 _write(tmp_path, "r2.json", _parsed(value=2330.0), n=2)]
        runs = hvd_perf.load_history(files)
        rows, regs = hvd_perf.compare(runs, 5.0)
        assert not regs
        by_leg = {r["leg"]: r for r in rows}
        assert by_leg["resnet50_img_per_sec_per_chip"]["status"] == "ok"
        assert by_leg["resnet50_img_per_sec_per_chip"][
            "worse_pct"] == pytest.approx(0.85, abs=0.01)

    def test_synthetic_10pct_slowdown_trips_gate(self, tmp_path):
        # copy of the real-schema history + a run 10% worse everywhere
        files = [
            _write(tmp_path, "r1.json", _parsed(), n=1),
            _write(tmp_path, "r2.json",
                   _parsed(value=2350.0 * 0.9, tokens=119000.0 * 0.9,
                           mfu=0.62 * 0.9, ms=137.5 / 0.9), n=2),
        ]
        assert hvd_perf.main(["--check"] + files) == 1
        runs = hvd_perf.load_history(files)
        _, regs = hvd_perf.compare(runs, 5.0)
        assert {r["leg"] for r in regs} == {
            "resnet50_img_per_sec_per_chip", "lm_tokens_per_sec_per_chip",
            "lm_mfu", "lm_ms_per_step"}
        assert all(r["worse_pct"] > 5.0 for r in regs)

    def test_config_change_suppresses_comparison(self, tmp_path):
        files = [
            _write(tmp_path, "r1.json", _parsed(batch=8, ms=70.0), n=1),
            _write(tmp_path, "r2.json", _parsed(batch=16, ms=140.0), n=2),
        ]
        runs = hvd_perf.load_history(files)
        rows, regs = hvd_perf.compare(runs, 5.0)
        assert not regs
        by_leg = {r["leg"]: r for r in rows}
        assert by_leg["lm_ms_per_step"]["status"] == "config-changed"

    def test_noise_band_raises_threshold(self, tmp_path):
        # 4% slowdown vs a 1% threshold, but the pm half-ranges cover
        # 6% of the baseline → inside noise, no trip
        files = [
            _write(tmp_path, "r1.json",
                   _parsed(ms=100.0, ms_pm=3.0), n=1),
            _write(tmp_path, "r2.json",
                   _parsed(ms=104.0, ms_pm=3.0), n=2),
        ]
        runs = hvd_perf.load_history(files)
        rows, regs = hvd_perf.compare(runs, 1.0)
        assert not regs
        by_leg = {r["leg"]: r for r in rows}
        assert by_leg["lm_ms_per_step"]["noise_pct"] == pytest.approx(6.0)
        assert by_leg["lm_ms_per_step"]["status"] == "ok"

    def test_new_leg_never_trips(self, tmp_path):
        base = _parsed()
        withserve = _parsed(serve={"speedup_tokens_per_step": 1.99})
        files = [_write(tmp_path, "r1.json", base, n=1),
                 _write(tmp_path, "r2.json", withserve, n=2)]
        runs = hvd_perf.load_history(files)
        rows, regs = hvd_perf.compare(runs, 5.0)
        assert not regs
        by_leg = {r["leg"]: r for r in rows}
        assert by_leg["serve_speedup"]["status"] == "new"

    def test_skips_runs_missing_the_leg(self, tmp_path):
        # leg compares against the most recent run that HAS it
        no_lm = {"metric": "resnet50_synthetic_images_per_sec_per_chip",
                 "value": 2340.0, "unit": "images/sec/chip"}
        files = [
            _write(tmp_path, "r1.json", _parsed(tokens=120000.0), n=1),
            _write(tmp_path, "r2.json", no_lm, n=2),
            _write(tmp_path, "r3.json", _parsed(tokens=100000.0), n=3),
        ]
        runs = hvd_perf.load_history(files)
        _, regs = hvd_perf.compare(runs, 5.0)
        assert "lm_tokens_per_sec_per_chip" in {r["leg"] for r in regs}


class TestCLI:
    def test_report_renders(self, tmp_path, capsys):
        files = [_write(tmp_path, "r1.json", _parsed(), n=1),
                 _write(tmp_path, "r2.json", _parsed(value=2360.0), n=2)]
        assert hvd_perf.main(["--report"] + files) == 0
        out = capsys.readouterr().out
        assert "resnet50_img_per_sec_per_chip" in out
        assert "latest run" in out

    def test_json_output(self, tmp_path, capsys):
        files = [_write(tmp_path, "r1.json", _parsed(), n=1),
                 _write(tmp_path, "r2.json",
                        _parsed(value=2000.0), n=2)]
        assert hvd_perf.main(["--json", "--check"] + files) == 1
        doc = json.loads(capsys.readouterr().out)
        assert "resnet50_img_per_sec_per_chip" in doc["regressions"]
        assert len(doc["runs"]) == 2

    def test_missing_file_exits_2(self, capsys):
        assert hvd_perf.main(["--check", "/nonexistent/x.json"]) == 2
        assert "hvd_perf" in capsys.readouterr().err

    def test_threshold_flag(self, tmp_path):
        files = [_write(tmp_path, "r1.json", _parsed(value=2000.0), n=1),
                 _write(tmp_path, "r2.json", _parsed(value=1940.0), n=2)]
        assert hvd_perf.main(["--check", "--threshold", "2"] + files) == 1
        assert hvd_perf.main(["--check", "--threshold", "10"] + files) == 0
