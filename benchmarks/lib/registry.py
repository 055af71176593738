"""Finds everything by name, so that a later PR adds files and edits none.

A run is described by ``BENCHMARK.json`` (cells, configurations, metric
names) and by files under the benchmark's directories that the harness
looks up by the names found there:

  configs/<config>.json      sizes, source, ``reduced``, ``assumed``
  traffic/<mix>.json         generator name and its parameters
  generators/<kind>.py       drives one kind of traffic (``Generator``)
  programs/<family>.py       builds the system under test for a family
  reference/<family>.py      the family's plain reference
  counts/<family>.py         required operations and bytes, from shapes
  metrics/<metric>.json      layer, unit, ``moves``, reader and arguments
                             (which cells: ``BENCHMARK.json`` alone says)
  readers/<reader>.py        one reduction each (``read(obs, args, run)``)
  limits/<cell>.json         the limits ``correct`` is held to
  peaks.json                 chip peaks by ``device_kind``

``roots`` is a search path of directories that hold such a tree; the
first hit wins.  The command uses the checkout alone; the self-tests put
a temporary directory first to add one of each without touching a file.
"""

import importlib.util
import json
import os
import re

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


class Registry:
    def __init__(self, roots):
        self.roots = [os.path.abspath(r) for r in roots]
        self._modules = {}

    def path(self, kind, filename):
        for root in self.roots:
            p = os.path.join(root, "benchmarks", kind, filename)
            if os.path.isfile(p):
                return p
        raise FileNotFoundError(
            f"no benchmarks/{kind}/{filename} under {self.roots}")

    def top(self, filename):
        for root in self.roots:
            p = os.path.join(root, filename)
            if os.path.isfile(p):
                return p
        raise FileNotFoundError(f"no {filename} under {self.roots}")

    def data(self, kind, name):
        _check_name(name)
        with open(self.path(kind, name + ".json")) as f:
            return json.load(f)

    def module(self, kind, name):
        """The module ``benchmarks/<kind>/<name>.py`` (names may hold
        ``-`` and ``.``, so it is loaded by path)."""
        _check_name(name)
        path = self.path(kind, name + ".py")
        if path not in self._modules:
            modname = "benchmarks_%s_%s" % (kind, re.sub(r"\W", "_", name))
            spec = importlib.util.spec_from_file_location(modname, path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            self._modules[path] = mod
        return self._modules[path]

    def benchmark(self):
        with open(self.top("BENCHMARK.json")) as f:
            return json.load(f)

    def peaks(self, device_kind):
        with open(self.path("", "peaks.json")) as f:
            table = json.load(f)["chips"]
        if device_kind not in table:
            raise KeyError(
                f"benchmarks/peaks.json has no row for device_kind "
                f"{device_kind!r}; known: {sorted(table)}")
        return table[device_kind]


def _check_name(name):
    if not NAME.match(name):
        raise ValueError(f"not a benchmark name: {name!r}")


def cell_of(bench, workload):
    for cell in bench["workloads"]:
        if cell["name"] == workload:
            return cell
    raise KeyError(f"BENCHMARK.json has no workload {workload!r}; it has "
                   f"{[c['name'] for c in bench['workloads']]}")


def metrics_of(bench, section, workload):
    """The metrics of ``section`` that this cell reports.  An end-to-end
    metric: those that list the cell under ``workloads`` or list nothing.
    A per-layer metric: those that list the cell, and those that list
    nothing and move an end-to-end metric the cell reports - so a cell
    that a later PR adds gets every such metric without an edit."""
    def listed(m):
        return "workloads" not in m or workload in m["workloads"]
    if section == "end_to_end":
        return [m for m in bench[section] if listed(m)]
    moved = {m["name"] for m in bench["end_to_end"] if listed(m)}
    return [m for m in bench[section]
            if (workload in m["workloads"] if "workloads" in m
                else m["moves"] in moved)]
