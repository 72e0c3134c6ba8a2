"""Finds, by the names in BENCHMARK.json, the files a cell is made of.

A cell names a configuration and a traffic mix; both are data files under
this directory.  The mix names its kind, which is the driver's module
(``drivers/<kind>.py``).  The limits of a cell's comparison are the cell's
own file (``checks/<cell>.json``).  A per-layer metric is
``metrics/<name>.json`` and names its reader (``metrics/<reader>.py``).
Nothing here is edited to add a cell: a later PR adds files and entries.
"""
import importlib
import importlib.util
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class BenchmarkError(Exception):
    """The benchmark's files contradict each other or the contract."""


def read_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(path, name):
    """Import one file of the benchmark by path: metric names hold dots."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_benchmark(root=ROOT):
    return read_json(os.path.join(root, "BENCHMARK.json"))


def rehearsal_of(config):
    """The configuration with its tiny CPU preset laid over it."""
    out = dict(config)
    out.update(config.get("rehearsal", {}))
    return out


class Cell:
    """One entry of ``workloads`` with everything its names point at."""

    def __init__(self, bench, name, root=ROOT, rehearse=False):
        rows = [w for w in bench["workloads"] if w["name"] == name]
        if len(rows) != 1:
            raise BenchmarkError(f"no workload {name!r} in BENCHMARK.json")
        row = rows[0]
        self.bench, self.name = bench, name
        self.chips = int(row["chips"])
        cfg_row = [c for c in bench["configs"] if c["name"] == row["config"]]
        if len(cfg_row) != 1:
            raise BenchmarkError(f"no config {row['config']!r}")
        self.config_name = cfg_row[0]["name"]
        self.config = read_json(os.path.join(root, cfg_row[0]["file"]))
        self.traffic = read_json(os.path.join(
            HERE, "traffic", row["traffic"] + ".json"))
        self.checks = read_json(os.path.join(HERE, "checks", name + ".json"))
        self.rehearse = rehearse
        if rehearse:
            self.config = rehearsal_of(self.config)
            self.traffic = rehearsal_of(self.traffic)
            self.checks = rehearsal_of(self.checks)

    def end_to_end(self):
        """The cell's end-to-end metrics: those that list it, and those
        with no list (``setup_s``)."""
        return [m for m in self.bench["end_to_end"]
                if self.name in m.get("workloads", (self.name,))]

    def per_layer(self):
        """The cell's per-layer metrics: those that list it.  Every
        per-layer metric of this benchmark carries its list, so that a later
        cell joins a metric by adding its name there."""
        for m in self.bench["per_layer"]:
            if "workloads" not in m:
                raise BenchmarkError(
                    f"per-layer metric {m['name']!r} lists no workloads")
        return [m for m in self.bench["per_layer"]
                if self.name in m["workloads"]]

    def driver(self):
        """``drivers/<kind>.py`` of the mix's kind: ``run(cell, args, rec,
        clock, devices, ctx)`` -> the harness's result parts."""
        kind = self.traffic["kind"]
        if not NAME_RE.match(kind) or not os.path.exists(
                os.path.join(HERE, "drivers", kind + ".py")):
            raise BenchmarkError(f"no driver for traffic kind {kind!r}")
        return importlib.import_module("benchmark.drivers." + kind)

    def reference(self):
        return load_module(os.path.join(
            HERE, "references", self.config_name + ".py"),
            "benchmark_reference_" + self.config_name)

    def limits(self):
        """The limits of this cell's comparison, as read on the chip at the
        cell's own size (``checks/<cell>.json``; its ``rehearsal`` group
        holds the CPU preset's)."""
        return self.checks["limits"]


def metric_reader(name):
    """``metrics/<name>.json`` -> (spec, read function)."""
    spec = read_json(os.path.join(HERE, "metrics", name + ".json"))
    reader = spec.get("reader", name)
    mod = load_module(os.path.join(HERE, "metrics", reader + ".py"),
                      "benchmark_metric_" + re.sub(r"\W", "_", reader))
    return spec, mod.read


def factory(dotted):
    """'package.module:attr' -> the attribute."""
    mod, attr = dotted.split(":")
    return getattr(importlib.import_module(mod), attr)
