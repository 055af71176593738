"""``BENCHMARK.json`` against the contract's own limits, and the rule that
everything is found by name in a file of its own."""

import json
import os
import re

import pytest

from benchmarks.lib import registry as registry_mod
from bench_tiny import REPO

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
REG = registry_mod.Registry([REPO])
BENCH = REG.benchmark()
CELLS = [c["name"] for c in BENCH["workloads"]]


def test_it_has_exactly_the_contracts_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "benchmarks/run.py"]
    assert BENCH["paths"] == ["benchmarks", "tests/benchmarks"]
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 * 1024


def test_the_issues_names_letter_for_letter():
    # the issue's fourth cell, baichuan7b-train-dp2tp2 (4 chips), waits in
    # PERF.md's Open questions with its files: none was proved on a chip
    assert CELLS == ["baichuan7b-train-s4096", "resnet50-train-b256",
                     "baichuan7b-serve-closed"]
    assert [c["traffic"] for c in BENCH["workloads"]] == [
        "train-s4096", "train-b256", "serve-closed16"]
    assert {c["name"] for c in BENCH["configs"]} == {"baichuan-7b",
                                                     "resnet50"}


def test_run_seconds_fits_the_full_check_with_24_cells():
    rs = BENCH["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_at_most_a_quarter_of_the_cells_and_always_one_may_take_four_chips():
    four = [c for c in BENCH["workloads"] if c["chips"] == 4]
    assert all(c["chips"] in (1, 4) for c in BENCH["workloads"])
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)


@pytest.mark.parametrize("section", ["end_to_end", "per_layer"])
def test_metric_entries_keep_to_their_keys(section):
    names = set()
    for m in BENCH[section]:
        keys = {"name", "unit", "better", "source"} | (
            {"bound"} if section == "end_to_end" else {"layer", "moves"})
        assert keys <= set(m) <= keys | {"workloads"}, m
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert m["name"] not in names
        names.add(m["name"])
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    if section == "end_to_end":
        assert "setup_s" in names
        assert all(m["source"] in ("host_clock", "device_trace") and
                   0.01 <= m["bound"] <= 0.1 for m in BENCH[section])


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=CELLS)
def test_every_cell_finds_its_files_and_reports_enough(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert 1 <= len(cell["why"]) <= 200 and "\n" not in cell["why"]
    conf = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
    assert conf["file"].startswith("benchmarks/configs/")
    with open(os.path.join(REPO, conf["file"])) as f:
        config = json.load(f)
    assert set(conf["reduced"]) == set(config["reduced"])
    traffic = REG.data("traffic", cell["traffic"])
    for kind, name in (("generators", traffic["generator"]),
                       ("programs", traffic["family"]),
                       ("reference", traffic["family"]),
                       ("counts", traffic["family"])):
        assert os.path.isfile(REG.path(kind, name + ".py"))
    assert config["family"] == traffic["family"]
    assert REG.data("limits", cell["name"])
    e2e = registry_mod.metrics_of(BENCH, "end_to_end", cell["name"])
    assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
    assert registry_mod.metrics_of(BENCH, "per_layer", cell["name"])


@pytest.mark.parametrize("metric", BENCH["per_layer"],
                         ids=[m["name"] for m in BENCH["per_layer"]])
def test_every_per_layer_metric_has_a_file_and_a_reader_of_its_own(metric):
    spec = REG.data("metrics", metric["name"])
    for key in ("layer", "unit", "moves", "better", "source"):
        assert spec[key] == metric[key], key
    # which cells report it is said in one place, BENCHMARK.json
    assert "workloads" not in spec
    assert hasattr(REG.module("readers", spec["reader"]), "read")
    moved = next(m for m in BENCH["end_to_end"]
                 if m["name"] == metric["moves"])
    # reported only in cells that report the metric it should move
    cells = [c for c in CELLS if metric in registry_mod.metrics_of(
        BENCH, "per_layer", c)]
    assert cells and set(cells) <= set(moved.get("workloads", CELLS))
    assert set(metric.get("workloads", cells)) == set(cells)


def test_a_cell_a_later_pr_adds_gets_the_unlisted_metrics_without_an_edit():
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append({"name": "new-train", "config": "x",
                               "traffic": "y", "chips": 1, "why": "z"})
    next(m for m in bench["end_to_end"] if m["name"] == "train_rate")[
        "workloads"].append("new-train")
    got = {m["name"] for m in registry_mod.metrics_of(
        bench, "per_layer", "new-train")}
    assert {"step.mfu", "step.stall_share", "step.block_rate_p50",
            "device.idle_share.train", "entry.compiles.train"} <= got
    # a metric that names its cells stays with them; serving's stay away
    assert not {m for m in got if m.startswith(("kernel.", "engine."))
                or m.endswith(".serve")}


def test_no_file_of_an_unproved_cell_is_shipped():
    for kind, name in (("traffic", "train-s4096-g8.json"),
                       ("metrics", "collective.exposed_share.json"),
                       ("readers", "collective_exposed.py")):
        with pytest.raises(FileNotFoundError):
            REG.path(kind, name)
    traffic = {c["traffic"] for c in BENCH["workloads"]}
    shipped = {f[:-5] for f in os.listdir(
        os.path.join(REPO, "benchmarks", "traffic"))}
    assert shipped == traffic


def test_a_layer_is_named_the_same_letter_for_letter_everywhere():
    layers = {m["layer"] for m in BENCH["per_layer"]}
    assert len(layers) == 6 and all("\n" not in x and len(x) <= 200
                                    for x in layers)


def test_peaks_has_the_v5e_with_its_source_and_no_default():
    row = REG.peaks("TPU v5 lite")
    assert row["bf16_flops"] == 197e12 and row["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        REG.peaks("cpu")


def test_names_that_are_not_names_are_refused():
    for bad in ("../x", "a b", "", "x" * 65, "a/b"):
        with pytest.raises(ValueError):
            REG.data("traffic", bad)
