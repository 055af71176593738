"""``BENCHMARK.json`` against the contract's own limits, and the rule that
everything is found by name in a file of its own.

Every check is a function of a benchmark and the registry that finds its
files, and runs on the repository's benchmark, on the EXTENDED one of
``bench_tiny.make_extended_root`` - the repository's plus what a
``model_config`` PR brings as added files alone (a configuration of a
second serving family, traffic, limits, a cell, a per-layer metric on a
new layer with its reader) - and on the same additions over a repository
that has GROWN by a cell, a configuration and a layer since
(``bench_tiny.make_grown_root``), as it will have when the next such PR
arrives.  What the repository's benchmark has accepted is a floor: its
cells lead, in their order, and its configurations and layers are all
there; what comes after them is free, held by the per-cell and per-metric
checks, and counted only against the benchmark it was added to.
"""

import copy
import json
import os
import re

import pytest

import bench_tiny
from benchmarks.lib import registry as registry_mod
from bench_tiny import REPO

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
REG = registry_mod.Registry([REPO])
GROWN = bench_tiny.grown_benchmark()
BENCHES = {"repo": REG.benchmark(),
           "extended": bench_tiny.extended_benchmark(),
           "grown-extended": bench_tiny.extended_benchmark(GROWN)}
ADDED = "tiny-wrapped-serve-closed"

# the floor: what accepted PRs brought, letter for letter
ACCEPTED_CELLS = [("baichuan7b-train-s4096", "train-s4096"),
                  ("resnet50-train-b256", "train-b256"),
                  ("baichuan7b-serve-closed", "serve-closed16")]
ACCEPTED_CONFIGS = {"baichuan-7b", "resnet50"}
ACCEPTED_LAYERS = {
    "entry: hvd.init, parallel/mesh.build_mesh, utils/compile_cache",
    "train step: trainer.make_gspmd_step, make_data_parallel_step, optim",
    "model: models/transformer.py, models/resnet.py",
    "kernels: ops/flash_attention.py",
    "serving engine: serving/engine.py, scheduler.py, queue.py, kv_cache.py",
    "device"}


@pytest.fixture(scope="module")
def registries(tmp_path_factory):
    def new(name):
        return str(tmp_path_factory.mktemp(name))
    grown = bench_tiny.make_grown_root(new("grown"))
    return {"repo": REG,
            "extended": registry_mod.Registry(
                bench_tiny.make_extended_root(new("extended"))),
            "grown-extended": registry_mod.Registry(
                bench_tiny.make_extended_root(new("later"), grown, GROWN))}


each_benchmark = pytest.mark.parametrize("which", list(BENCHES))


def _over(section):
    """(which, entry) for every entry of ``section`` in both benchmarks."""
    pairs = [(w, e) for w, b in BENCHES.items() for e in b[section]]
    return pytest.mark.parametrize(
        "which,entry", pairs, ids=[f"{w}:{e['name']}" for w, e in pairs])


def _cells(bench):
    return [c["name"] for c in bench["workloads"]]


def _layers(bench):
    return {m["layer"] for m in bench["per_layer"]}


@pytest.mark.parametrize("base,ext,config", [
    (BENCHES["repo"], BENCHES["extended"], "tiny-wrapped"),
    (BENCHES["repo"], GROWN, "tiny-lm"),
    (GROWN, BENCHES["grown-extended"], "tiny-wrapped"),
], ids=["extended", "grown", "grown-extended"])
def test_each_step_only_adds_a_cell_a_configuration_and_a_layer(
        base, ext, config):
    """Counted against the benchmark it was added to, never in all."""
    for section in ("configs", "workloads", "per_layer"):
        n = len(base[section])
        assert ext[section][:n] == base[section]
        assert len(ext[section]) == n + 1
    assert len(_layers(ext)) == len(_layers(base)) + 1
    assert {c["name"] for c in ext["configs"]} - \
        {c["name"] for c in base["configs"]} == {config}
    added = ext["workloads"][-1]["name"]
    for old, new in zip(base["end_to_end"], ext["end_to_end"]):
        assert {k: v for k, v in new.items() if k != "workloads"} == \
            {k: v for k, v in old.items() if k != "workloads"}
        assert new.get("workloads", []) in (
            old.get("workloads", []), old.get("workloads", []) + [added])
    assert [k for k in ext if k not in ("configs", "workloads", "per_layer",
                                        "end_to_end")] == \
        ["command", "paths", "run_seconds"]
    assert all(ext[k] == base[k] for k in ("command", "paths",
                                           "run_seconds"))


@pytest.mark.parametrize("which", ["extended", "grown-extended"])
def test_every_added_file_is_new(which, registries):
    """A root shadows no file of the roots below it, the checkout last."""
    reg = registries[which]
    assert reg.benchmark() == BENCHES[which]
    assert reg.roots[-1] == REPO and len(reg.roots) == \
        {"extended": 2, "grown-extended": 3}[which]
    for i, top in enumerate(reg.roots[:-1]):
        for folder, _, files in os.walk(os.path.join(top, "benchmarks")):
            for name in files:
                rel = os.path.relpath(os.path.join(folder, name), top)
                assert not [r for r in reg.roots[i + 1:]
                            if os.path.exists(os.path.join(r, rel))], rel


@each_benchmark
def test_it_has_exactly_the_contracts_keys(which, registries):
    bench = BENCHES[which]
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"] == ["python3", "benchmarks/run.py"]
    assert bench["paths"] == ["benchmarks", "tests/benchmarks"]
    assert os.path.getsize(
        registries[which].top("BENCHMARK.json")) < 64 * 1024
    assert 1 <= len(bench["configs"]) <= 24
    assert 1 <= len(bench["workloads"]) <= 24
    used = {c["config"] for c in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}
    pairs = [(c["config"], c["traffic"]) for c in bench["workloads"]]
    assert len(set(pairs)) == len(pairs)


def check_the_accepted_names(bench):
    """The accepted cells lead, in their order, with their traffic; the
    accepted configurations are there.  What follows them is free."""
    # the issue's fourth cell, baichuan7b-train-dp2tp2 (4 chips), waits in
    # PERF.md's Open questions with its files: none was proved on a chip
    n = len(ACCEPTED_CELLS)
    assert [(c["name"], c["traffic"]) for c in bench["workloads"][:n]] == \
        ACCEPTED_CELLS
    assert ACCEPTED_CONFIGS <= {c["name"] for c in bench["configs"]}
    names = _cells(bench) + [c["name"] for c in bench["configs"]]
    assert all(NAME.match(x) for x in names)
    assert len(set(_cells(bench))) == len(bench["workloads"])
    assert len({c["name"] for c in bench["configs"]}) == \
        len(bench["configs"])


def check_the_layers(bench):
    """The accepted layer strings are all there, letter for letter; any
    layer keeps to one line of at most 200 characters."""
    layers = {m["layer"] for m in bench["per_layer"]}
    assert ACCEPTED_LAYERS <= layers
    assert all(1 <= len(x) <= 200 and "\n" not in x and "\t" not in x
               for x in layers)


@each_benchmark
def test_the_issues_names_letter_for_letter(which):
    check_the_accepted_names(BENCHES[which])


@each_benchmark
def test_a_layer_is_named_the_same_letter_for_letter_everywhere(which):
    check_the_layers(BENCHES[which])


def _rename_cell(b):
    b["workloads"][2]["name"] = "baichuan7b-serve-closed16"


def _swap_first_two(b):
    b["workloads"][:2] = b["workloads"][1::-1]


def _remove_a_cell(b):
    del b["workloads"][1]


def _new_cell_first(b):
    b["workloads"].insert(0, dict(b["workloads"][0], name="newer"))


def _rename_traffic(b):
    b["workloads"][0]["traffic"] = "train-s2048"


def _remove_a_config(b):
    b["configs"] = [c for c in b["configs"] if c["name"] != "resnet50"]


def _rename_a_config(b):
    b["configs"][0]["name"] = "baichuan-7B"


def _rename_a_layer(b):
    for m in b["per_layer"]:
        if m["layer"] == "device":
            m["layer"] = "the device"


def _drop_a_layer(b):
    b["per_layer"] = [m for m in b["per_layer"]
                      if not m["layer"].startswith("kernels:")]


def _a_layer_on_two_lines(b):
    b["per_layer"][-1]["layer"] = "one\ntwo"


@pytest.mark.parametrize("which", list(BENCHES))
@pytest.mark.parametrize("check,mutate", [
    (check_the_accepted_names, _rename_cell),
    (check_the_accepted_names, _swap_first_two),
    (check_the_accepted_names, _remove_a_cell),
    (check_the_accepted_names, _new_cell_first),
    (check_the_accepted_names, _rename_traffic),
    (check_the_accepted_names, _remove_a_config),
    (check_the_accepted_names, _rename_a_config),
    (check_the_layers, _rename_a_layer),
    (check_the_layers, _drop_a_layer),
    (check_the_layers, _a_layer_on_two_lines),
], ids=lambda x: x.__name__.lstrip("_") if callable(x) else x)
def test_the_floor_refuses_an_accepted_name_renamed_reordered_or_removed(
        which, check, mutate):
    bench = copy.deepcopy(BENCHES[which])
    check(bench)
    mutate(bench)
    with pytest.raises(AssertionError):
        check(bench)


@each_benchmark
def test_run_seconds_fits_the_full_check_with_24_cells(which):
    rs = BENCHES[which]["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


@each_benchmark
def test_at_most_a_quarter_of_the_cells_and_always_one_may_take_four_chips(
        which):
    bench = BENCHES[which]
    four = [c for c in bench["workloads"] if c["chips"] == 4]
    assert all(c["chips"] in (1, 4) for c in bench["workloads"])
    assert len(four) <= max(1, len(bench["workloads"]) // 4)


@each_benchmark
@pytest.mark.parametrize("section", ["end_to_end", "per_layer"])
def test_metric_entries_keep_to_their_keys(section, which):
    bench = BENCHES[which]
    cells = _cells(bench)
    names = set()
    for m in bench[section]:
        keys = {"name", "unit", "better", "source"} | (
            {"bound"} if section == "end_to_end" else {"layer", "moves"})
        assert keys <= set(m) <= keys | {"workloads"}, m
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert m["name"] not in names
        names.add(m["name"])
        assert set(m.get("workloads", cells)) <= set(cells)
    if section == "end_to_end":
        assert "setup_s" in names
        assert all(m["source"] in ("host_clock", "device_trace") and
                   0.01 <= m["bound"] <= 0.1 for m in bench[section])
    assert not names & {m["name"] for m in bench[
        "per_layer" if section == "end_to_end" else "end_to_end"]}


@_over("workloads")
def test_every_cell_finds_its_files_and_reports_enough(which, entry,
                                                       registries):
    bench, reg, cell = BENCHES[which], registries[which], entry
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert 1 <= len(cell["why"]) <= 200 and "\n" not in cell["why"]
    assert NAME.match(cell["traffic"])
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert set(conf) == {"name", "source", "file", "reduced", "why"}
    assert conf["file"].startswith("benchmarks/configs/")
    assert [c["file"] for c in bench["configs"]].count(conf["file"]) == 1
    with open(reg.top(conf["file"])) as f:
        config = json.load(f)
    assert set(conf["reduced"]) == set(config["reduced"])
    traffic = reg.data("traffic", cell["traffic"])
    for kind, name in (("generators", traffic["generator"]),
                       ("programs", traffic["family"]),
                       ("reference", traffic["family"]),
                       ("counts", traffic["family"])):
        assert os.path.isfile(reg.path(kind, name + ".py"))
    assert config["family"] == traffic["family"]
    assert reg.data("limits", cell["name"])
    e2e = registry_mod.metrics_of(bench, "end_to_end", cell["name"])
    assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
    assert registry_mod.metrics_of(bench, "per_layer", cell["name"])


@_over("per_layer")
def test_every_per_layer_metric_has_a_file_and_a_reader_of_its_own(
        which, entry, registries):
    bench, reg, metric = BENCHES[which], registries[which], entry
    cells = _cells(bench)
    spec = reg.data("metrics", metric["name"])
    for key in ("layer", "unit", "moves", "better", "source"):
        assert spec[key] == metric[key], key
    # which cells report it is said in one place, BENCHMARK.json
    assert "workloads" not in spec
    assert hasattr(reg.module("readers", spec["reader"]), "read")
    moved = next(m for m in bench["end_to_end"]
                 if m["name"] == metric["moves"])
    # reported only in cells that report the metric it should move
    reporting = [c for c in cells if metric in registry_mod.metrics_of(
        bench, "per_layer", c)]
    assert reporting and set(reporting) <= set(moved.get("workloads", cells))
    assert set(metric.get("workloads", reporting)) == set(reporting)


@each_benchmark
def test_a_cell_a_later_pr_adds_gets_the_unlisted_metrics_without_an_edit(
        which):
    bench = copy.deepcopy(BENCHES[which])
    bench["workloads"].append({"name": "new-train", "config": "x",
                               "traffic": "y", "chips": 1, "why": "z"})
    next(m for m in bench["end_to_end"] if m["name"] == "train_rate")[
        "workloads"].append("new-train")
    got = registry_mod.metrics_of(bench, "per_layer", "new-train")
    assert {"step.mfu", "step.stall_share", "step.block_rate_p50",
            "device.idle_share.train", "entry.compiles.train"} <= \
        {m["name"] for m in got}
    # a metric that names its cells stays with them, and so does one that
    # moves what this cell does not report: serving's stay away
    assert all("workloads" not in m and m["moves"] in ("train_rate",
                                                       "setup_s")
               for m in got)


@pytest.mark.parametrize("which", ["extended", "grown-extended"])
def test_the_added_serving_cell_gets_the_unlisted_serving_metrics(which):
    """The added cell lists itself under the three serving metrics and
    under its own per-layer metric, and gets every UNLISTED metric that
    moves one of them, as the accepted serving cell does:
    ``model.decode_roofline`` among them, which is why its count has to be
    told the rows.  A metric that names its cells stays with them."""
    bench = BENCHES[which]
    mine = registry_mod.metrics_of(bench, "per_layer", ADDED)
    theirs = registry_mod.metrics_of(bench, "per_layer",
                                     "baichuan7b-serve-closed")
    assert {m["name"] for m in mine if "workloads" in m} == \
        {"state.row_bytes_share"}
    assert [m for m in mine if "workloads" not in m] == \
        [m for m in theirs if "workloads" not in m]
    assert "model.decode_roofline" in {m["name"] for m in mine}
    assert {m["name"] for m in registry_mod.metrics_of(
        bench, "end_to_end", ADDED)} == {
            "serve_tokens_per_s", "ttft_p90", "tpot_p90", "setup_s"}
    assert "state.row_bytes_share" not in {m["name"] for m in theirs}


@each_benchmark
def test_no_file_of_an_unproved_cell_is_shipped(which, registries):
    reg = registries[which]
    for kind, name in (("traffic", "train-s4096-g8.json"),
                       ("metrics", "collective.exposed_share.json"),
                       ("readers", "collective_exposed.py")):
        with pytest.raises(FileNotFoundError):
            reg.path(kind, name)
    # every traffic, limits and metric file belongs to a cell or a metric
    # of the benchmark, and each root ships none besides
    bench = BENCHES[which]
    for kind, wanted in (
            ("traffic", {c["traffic"] for c in bench["workloads"]}),
            ("limits", set(_cells(bench))),
            ("metrics", {m["name"] for m in bench["per_layer"]})):
        shipped = set()
        for root in reg.roots:
            shipped |= {f[:-5] for f in os.listdir(
                os.path.join(root, "benchmarks", kind))}
        assert shipped == wanted, kind


def test_peaks_has_the_v5e_with_its_source_and_no_default():
    row = REG.peaks("TPU v5 lite")
    assert row["bf16_flops"] == 197e12 and row["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        REG.peaks("cpu")


def test_names_that_are_not_names_are_refused():
    for bad in ("../x", "a b", "", "x" * 65, "a/b"):
        with pytest.raises(ValueError):
            REG.data("traffic", bad)
