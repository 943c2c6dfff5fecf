"""`BENCHMARK.json` and the files it names, found by name: a cell's
configuration file, its traffic mix `traffic/<traffic>.json`, the model
the configuration's checker names `models/<model>.py`, the chip peaks
`peaks.json`, and each per-layer metric's reader `metrics/<metric>.py`.
Adding a cell, a mix, a model or a metric is adding files and an entry;
nothing here changes."""

from __future__ import annotations

import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def load(root=ROOT):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def cell(spec, name, root=ROOT):
    """(workload entry, configuration, traffic) of the cell ``name``."""
    wl = {w["name"]: w for w in spec["workloads"]}.get(name)
    if wl is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cfg = {c["name"]: c for c in spec["configs"]}[wl["config"]]
    with open(os.path.join(root, cfg["file"])) as f:
        config = json.load(f)
    bench = os.path.join(root, os.path.basename(BENCH))
    with open(os.path.join(bench, "traffic", wl["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return wl, config, traffic


def metrics(spec, name, traced):
    """The metrics a run of cell ``name`` reports: the end-to-end ones
    untraced, the per-layer ones traced; each listed for the cell or
    listing no cells."""
    group = spec["per_layer"] if traced else spec["end_to_end"]
    return [m for m in group if name in m.get("workloads", [name])]


def _load(kind, name, root):
    """The module of ``<kind>/<name>.py`` under the benchmark, by file
    location; a missing file is a KeyError that names it."""
    bench = os.path.join(root, os.path.basename(BENCH))
    path = os.path.join(bench, kind, name + ".py")
    if not os.path.isfile(path):
        raise KeyError(f"no file {os.path.relpath(path, root)} for "
                       f"{kind} {name!r}")
    sp = importlib.util.spec_from_file_location(
        f"bench_{kind}_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    return mod


def reader(metric, root=ROOT):
    """The `read(ctx)` function of a per-layer metric's own file."""
    return _load("metrics", metric["name"], root).read


def model(config, root=ROOT):
    """The module of the model a configuration's checker names (its
    ``checker.model``, the program's model, which `core/sut.py`
    builds): its generator, corruption, reference step and control, and
    the bits of its state (`models/cas_register.py` shows each).  One
    name picks both sides, so the reference cannot check another model
    than the program's.  A configuration that names none, or a model
    with no file, is an error, not a default."""
    return _load("models", config["checker"]["model"], root)


def peaks(kind, root=ROOT):
    """The published peaks of a device kind; an unknown kind is an
    error, not a default."""
    bench = os.path.join(root, os.path.basename(BENCH))
    with open(os.path.join(bench, "peaks.json")) as f:
        table = json.load(f)
    if kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table["devices"][kind]
