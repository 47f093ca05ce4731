"""Finds every file of the benchmark by the name ``BENCHMARK.json`` gives.

A later PR adds a configuration, a traffic mix, a cell or a per-layer
metric by adding files (and an entry in ``BENCHMARK.json``); it never edits
this one. The layout under ``<root>/perf``:

    configs/<config>.json        cells/<cell>.json (optional extras)
    traffic/<mix>.json           drivers/<kind>.py
    reference/<config>.py        layer_metrics/<metric>.py
    ops_counts/<name>.py
"""

import importlib.util
import json
import os
import re

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


class BenchmarkError(SystemExit):
    """A benchmark file outside the contract: the run exits nonzero and
    prints no result."""

    def __init__(self, msg):
        super().__init__(f"perf: {msg}")


def check_name(name, what="name"):
    if not isinstance(name, str) or not NAME.match(name):
        raise BenchmarkError(
            f"{what} {name!r} is not made of at most 64 letters, digits, "
            "'_', '.' and '-'")
    return name


def check_unit(unit):
    if not isinstance(unit, str) or not UNIT.match(unit):
        raise BenchmarkError(
            f"unit {unit!r} is not made of 1 to 16 letters, digits, '_', "
            "'/', '%', '.' and '-'")
    return unit


def _read_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(path):
    """A Python file by its path: names with '-' or '.' cannot be imported
    by name, and the file's place says what it is."""
    if not os.path.isfile(path):
        raise BenchmarkError(f"no such file: {path}")
    name = "perf_file_" + re.sub(r"\W", "_", os.path.relpath(path))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Benchmark:
    """``BENCHMARK.json`` and the files it names under ``<root>/perf``."""

    def __init__(self, root):
        self.root = root
        self.dir = os.path.join(root, "perf")
        path = os.path.join(root, "BENCHMARK.json")
        if not os.path.isfile(path):
            raise BenchmarkError(f"no BENCHMARK.json in {root}")
        self.manifest = m = _read_json(path)
        for group in ("configs", "workloads", "end_to_end", "per_layer"):
            seen = set()
            for entry in m.get(group, []):
                check_name(entry["name"], f"{group} name")
                if entry["name"] in seen:
                    raise BenchmarkError(
                        f"{group} name {entry['name']!r} appears twice")
                seen.add(entry["name"])
        for entry in m.get("end_to_end", []) + m.get("per_layer", []):
            check_unit(entry["unit"])
        for w in m.get("workloads", []):
            check_name(w["config"], "config")
            check_name(w["traffic"], "traffic")

    # ----------------------------------------------------------- cells
    def cell(self, workload):
        """A cell by its name in ``BENCHMARK.json``, or by the path of a
        cell file that is in no manifest (a rehearsal's). A file
        ``cells/<name>.json`` adds what the manifest has no key for
        (mesh, sharding); where both give a key they must agree."""
        if workload.endswith(".json") and os.path.isfile(workload):
            cell = _read_json(workload)
            cell.setdefault("name", os.path.basename(workload)[:-5])
            cell["listed"] = False
        else:
            check_name(workload, "workload")
            listed = [w for w in self.manifest["workloads"]
                      if w["name"] == workload]
            if not listed:
                raise BenchmarkError(
                    f"no workload {workload!r} in BENCHMARK.json")
            cell = dict(listed[0], listed=True)
            extra = os.path.join(self.dir, "cells", workload + ".json")
            if os.path.isfile(extra):
                for k, v in _read_json(extra).items():
                    if k in cell and cell[k] != v:
                        raise BenchmarkError(
                            f"cells/{workload}.json says {k}={v!r}, "
                            f"BENCHMARK.json says {cell[k]!r}")
                    cell[k] = v
        for key in ("name", "config", "traffic"):
            check_name(cell[key], key)
        if cell.get("chips") not in (1, 4):
            raise BenchmarkError(f"cell {cell['name']!r}: chips must be 1 "
                                 f"or 4, not {cell.get('chips')!r}")
        return cell

    # --------------------------------------------------- files by name
    def config(self, name):
        check_name(name, "config")
        listed = [c for c in self.manifest["configs"] if c["name"] == name]
        path = os.path.join(self.root, listed[0]["file"]) if listed else \
            os.path.join(self.dir, "configs", name + ".json")
        if not os.path.isfile(path):
            raise BenchmarkError(f"no configuration file {path}")
        cfg = _read_json(path)
        cfg["name"] = name
        return cfg

    def traffic(self, name):
        check_name(name, "traffic")
        path = os.path.join(self.dir, "traffic", name + ".json")
        if not os.path.isfile(path):
            raise BenchmarkError(f"no traffic file {path}")
        mix = _read_json(path)
        mix["name"] = name
        return mix

    def driver(self, kind):
        check_name(kind, "driver")
        return load_module(os.path.join(self.dir, "drivers", kind + ".py"))

    def reference(self, config_name):
        return load_module(
            os.path.join(self.dir, "reference", config_name + ".py"))

    def ops_counts(self, name):
        check_name(name, "ops_counts")
        return load_module(os.path.join(self.dir, "ops_counts", name + ".py"))

    def layer_metric(self, name):
        check_name(name, "metric")
        return load_module(
            os.path.join(self.dir, "layer_metrics", name + ".py"))

    # ------------------------------------------- what a cell reports
    def end_to_end(self, cell):
        """The end-to-end metrics the manifest lists for this cell (one
        with no ``workloads`` key is every cell's); for a cell in no
        manifest, None: it reports what its driver measures."""
        if not cell["listed"]:
            return None
        return [m for m in self.manifest["end_to_end"]
                if cell["name"] in m.get("workloads", [cell["name"]])]

    def per_layer(self, cell):
        """``(name, unit)`` of the per-layer metrics to read in this cell:
        those that list it, and those with no ``workloads`` key whose
        ``moves`` this cell reports. For a cell in no manifest, every
        reader under ``layer_metrics/`` with no unit to check: one that
        finds nothing to read returns nothing."""
        if not cell["listed"]:
            names = sorted(
                f[:-3] for f in
                os.listdir(os.path.join(self.dir, "layer_metrics"))
                if f.endswith(".py") and not f.startswith("_"))
            return [(n, None) for n in names]
        reported = {m["name"] for m in self.end_to_end(cell)}
        out = []
        for m in self.manifest["per_layer"]:
            cells = m.get("workloads")
            if (cell["name"] in cells) if cells is not None \
                    else (m["moves"] in reported):
                out.append((m["name"], m["unit"]))
        return out
