"""CPU tests of the per-layer metrics that read the checker's phase
spans (`benchmark/metrics/`): each reader's value on a hand-built
context, its silence where the program records no such span, and a
small traced run that reports every one its cell lists.

    JAX_PLATFORMS=cpu python3 -m pytest tests/benchmark_harness -q
"""

from __future__ import annotations

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "benchmark"))
sys.path.insert(0, HERE)

from core import spec  # noqa: E402
from test_benchmark_harness import (  # noqa: E402,F401
    _leave_the_process_as_found, small_run)

PHASE_METRICS = ("split_share", "encode_share", "recheck_share",
                 "confirm_share", "report_share", "confirm_us_per_config")


def _span(name, t0, t1, **args):
    return {"name": name, "t0": t0, "t1": t1, "args": args}


def _ctx(spans):
    return {"window": (10.0, 20.0), "spans": spans}


def _reader(name):
    (m,) = [m for m in spec.load()["per_layer"] if m["name"] == name]
    return spec.reader(m)


#: (metric, spans in a window [10, 20), value)
CASES = [
    # [11, 13) of two overlapping splits, and [19, 20) of one that
    # outlives the window; one after it counts nothing
    ("split_share", [_span("independent.split", 11, 12),
                     _span("independent.split", 11.5, 13),
                     _span("independent.split", 19, 21),
                     _span("independent.split", 22, 23)], 30.0),
    # lint and encode [10, 11.5), prep and witness [12, 13.5), the keys'
    # encode [15, 16); a slice is no part of it
    ("encode_share", [_span("linearizable.lint", 10, 11),
                      _span("linearizable.encode", 10.5, 11.5),
                      _span("linearizable.prep", 12, 13),
                      _span("linearizable.witness", 12.5, 13.5),
                      _span("independent.encode", 15, 16),
                      _span("device.slice", 16, 19)], 40.0),
    ("recheck_share", [_span("independent.key", 10, 15, why="recheck"),
                       _span("independent.key", 12, 13, why="small")],
     50.0),
    ("confirm_share", [_span("linearizable.confirm", 11, 12),
                       _span("independent.key", 10, 20)], 10.0),
    ("report_share", [_span("linearizable.shrink", 11, 12),
                      _span("linearizable.report", 12, 14)], 30.0),
    # 1.5 s over 1,500 configurations; a confirmation that ran no
    # re-check, and one begun before the window, count nothing
    ("confirm_us_per_config",
     [_span("linearizable.confirm", 11, 12, configs=1000, valid=False),
      _span("linearizable.confirm", 13, 13.5, configs=500, valid=False),
      _span("linearizable.confirm", 14, 15, prefix_ops=4000),
      _span("linearizable.confirm", 9, 11, configs=10, valid=False)],
     1000.0),
]


@pytest.mark.parametrize("name,spans,want", CASES,
                         ids=[c[0] for c in CASES])
def test_phase_metric_by_hand(name, spans, want):
    assert _reader(name)(_ctx(spans)) == pytest.approx(want)


@pytest.mark.parametrize("name", PHASE_METRICS)
def test_phase_metric_is_silent_without_its_spans(name):
    """A program that records no phase spans (a version older than
    them) gives no value, and no error."""
    read = _reader(name)
    assert read(_ctx([])) is None
    assert read(_ctx([_span("device.slice", 11, 12),
                      _span("bucket.prep", 12, 13)])) is None


@pytest.mark.parametrize("name", PHASE_METRICS)
def test_phase_metric_entry(name):
    (m,) = [m for m in spec.load()["per_layer"] if m["name"] == name]
    assert (m["source"], m["layer"], m["better"], m["moves"]) == (
        "program_span", "entry points", "lower", "verified_ops_per_s")
    # the independent-keys phases exist only in the etcdemo cells, and
    # the phases of an invalid key's re-check only where keys are invalid
    want = {"split_share": ["etcdemo-stale", "etcdemo-clean"],
            "recheck_share": ["etcdemo-stale"],
            "encode_share": ["etcdemo-stale", "zk1k-stale", "etcdemo-clean"]
            }.get(name, ["etcdemo-stale", "zk1k-stale"])
    assert m["workloads"] == want


@pytest.mark.parametrize("cell", ["etcdemo-stale", "zk1k-stale",
                                  "etcdemo-clean"])
def test_a_traced_run_reports_every_phase_metric_of_its_cell(cell):
    r = small_run(cell, trace=1)
    assert r["correct"], r["checks"]
    listed = [m["name"] for m in spec.metrics(spec.load(), cell,
                                              traced=True)
              if m["name"] in PHASE_METRICS]
    assert listed and all(r["metrics"][n]["value"] is not None
                          for n in listed)
    # the phases leave little of the window to no span at all
    assert r["metrics"]["unspanned_share"]["value"] < 15.0
