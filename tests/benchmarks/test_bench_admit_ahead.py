"""``engine.admit_ahead_share`` (PR 39): the share of a window's admissions
whose first token the engine read behind its step's decode launch, from
the program's own step records (count ``admitted_ahead`` over count
``admitted``) through the reader ``step_count_share``."""

import json
import os

import pytest

from bench_tiny import REPO
from benchmarks.lib import registry as registry_mod
from benchmarks.lib import step_phases

METRIC = "engine.admit_ahead_share"


@pytest.fixture
def reader():
    return registry_mod.Registry([REPO]).module("readers",
                                                "step_count_share")


def _spec():
    return registry_mod.Registry([REPO]).data("metrics", METRIC)


def _records(pairs):
    return [{"phases": [], "admitted": a, "admitted_ahead": b}
            for a, b in pairs]


@pytest.mark.parametrize("pairs,want", [
    ([(0, 0), (1, 1), (2, 2), (0, 0)], 100.0),     # the closed cells
    ([(1, 0), (0, 0), (2, 2), (1, 1)], 75.0),      # one read before a launch
    ([(1, 0), (2, 0)], 0.0),                       # the synchronous order
])
def test_the_share_is_the_sums_ratio_over_all_the_windows_steps(
        reader, monkeypatch, pairs, want):
    monkeypatch.setattr(step_phases, "analysis",
                        lambda obs, run: {"window": _records(pairs)})
    assert reader.read({}, _spec()["args"], None) == pytest.approx(want)


@pytest.mark.parametrize("why", ["no_step_records", "no_window",
                                 "records_lack_the_count",
                                 "nothing_admitted"])
def test_the_reader_reports_nothing_where_there_is_nothing_to_read(
        reader, monkeypatch, why):
    """A program that keeps no records, or whose records lack the count
    (the parent commit): ``None``, and the result line leaves the metric
    out, as ``attn.kv_bytes_per_step`` was at PR 31."""
    records = _records([(1, 1), (0, 0)])
    if why == "records_lack_the_count":
        for r in records:
            del r["admitted_ahead"]
    got = {"no_step_records": None, "no_window": {},
           "records_lack_the_count": {"window": records},
           "nothing_admitted": {"window": _records([(0, 0)])}}[why]
    monkeypatch.setattr(step_phases, "analysis", lambda obs, run: got)
    assert reader.read({}, _spec()["args"], None) is None


def test_the_metric_is_every_serving_cells_and_no_training_cells():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = bench["per_layer"][-1]
    assert entry["name"] == METRIC and "workloads" not in entry
    spec = _spec()
    assert spec["reader"] == "step_count_share"
    assert spec["args"] == {"count": "admitted_ahead", "over": "admitted"}
    for cell in bench["workloads"]:
        names = {m["name"] for m in registry_mod.metrics_of(
            bench, "per_layer", cell["name"])}
        assert (METRIC in names) == ("serve" in cell["name"])


def test_the_count_is_one_the_program_keeps():
    from horovod_tpu.serving import tracing as serve_tracing
    spec = _spec()
    assert spec["args"]["count"] in serve_tracing.STEP_COUNTS
    assert spec["args"]["over"] in serve_tracing.STEP_COUNTS
