"""CPU tests of the `hazelcast-lock` cell (`benchmark/models/mutex.py`,
`configs/hazelcast-mutex.json`, `traffic/hazelcast-lock.json`) and of
the two readers of its `linearizable.certify` spans: the generator, the
knossos mutex, the sound run, the control, planted faults, and a small
traced run.

    JAX_PLATFORMS=cpu python3 -m pytest tests/benchmark_harness -q
"""

from __future__ import annotations

import os
import random
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "benchmark"))
sys.path.insert(0, HERE)

import run  # noqa: E402
from core import gen, reference, spec, sut  # noqa: E402
from test_benchmark_harness import (  # noqa: E402,F401
    SEED, Planted, _always_invalid, _bad_certificate, _flip_one,
    _leave_the_process_as_found, _unchanged)
from test_phase_metrics import _ctx, _reader, _span  # noqa: E402

CELL = "hazelcast-lock"
#: 2 histories of 120 ops, of the middle of such histories' work
#: (243-481 configurations over 40 histories, median 347)
SMALL = ({"encoded_ops": 120}, {"pool": 2, "work": [300, 450]})
#: the model of the lock cell
LOCK = spec.model({"checker": {"model": "mutex"}})
CERTIFY_METRICS = ("certify_share", "certify_us_per_config")


def small_run(*, seed=SEED, sut=None, trace=0, control=0):
    return run.run(["--workload", CELL, "--seed", str(seed),
                    "--seconds", "1", "--trace", str(trace),
                    "--control", str(control)],
                   require_chip=False, config_override=SMALL[0],
                   traffic_override=SMALL[1], sut=sut)


def cell_parts():
    _wl, config, traffic = spec.cell(spec.load(), CELL)
    return {**config, **SMALL[0]}, {**traffic, **SMALL[1]}


# --- generator -------------------------------------------------------------


def test_generator_is_deterministic_per_seed():
    config, traffic = cell_parts()
    a = gen.make_pool(SEED, config, traffic, LOCK)
    assert a == gen.make_pool(SEED, config, traffic, LOCK)
    assert a != gen.make_pool(SEED + 1, config, traffic, LOCK)


def test_every_seed_gets_the_same_sizes():
    config, traffic = cell_parts()
    lo, hi = traffic["work"]
    for seed in (1, SEED, 2 ** 40 + 7):
        for events, _truth in gen.make_pool(seed, config, traffic, LOCK):
            assert gen.encoded_count(events) == config["encoded_ops"]
            rows = reference.rows_of(events)
            assert sum(op["type"] == "info" for op in rows) \
                == traffic["crashes"]
            assert lo <= reference.check(LOCK, rows, config["init"])[1] <= hi


@pytest.mark.parametrize("n_ops", [60, 120, 200])
def test_lock_histories_are_valid_and_no_completion_order_replays(n_ops):
    rng = random.Random(SEED + n_ops)
    p = {"procs": 5, "overlap": 5, "crashes": 12}
    for _ in range(6):
        events = LOCK.history(rng, p, n_ops, True)
        assert gen.encoded_count(events) == n_ops
        rows = reference.rows_of(events)
        assert sum(op["type"] == "info" for op in rows) == 12
        assert reference.check(LOCK, rows, None)[0] is True
        assert not LOCK.completion_order_replays(events)
        # dropping the crashed ops leaves no linearization: a crashed
        # release took effect and another worker acquired after it
        oks = [op for op in rows if op["type"] == "ok"]
        assert reference.check(LOCK, oks, None)[0] is False
    assert LOCK.corrupt(events, p) is None


@pytest.mark.parametrize("exact", [True, False])
def test_lock_crashes_spread_over_the_whole_history(exact):
    """The crashes fall at slots drawn over the ops the history counts
    (ok + info where ``exact``, invocations otherwise), so the last of
    them lies past the first third of the history, not in its first
    ``n_ops`` invocations."""
    rng = random.Random(SEED)
    n_ops, crashes = 600, 12
    lasts = []
    for _ in range(8):
        events = LOCK.lock_history(rng, n_ops=n_ops, n_procs=5, overlap=5,
                                   crashes=crashes, exact=exact)
        done = [e for e in events if e[1] != "invoke"]
        counted = [e for e in done if e[1] != "fail"] if exact else done
        info = [i for i, e in enumerate(counted) if e[1] == "info"]
        assert len(info) == crashes
        lasts.append(info[-1] / len(counted))
    assert min(lasts) > 1 / 3
    assert sum(lasts) / len(lasts) > 0.8


def test_lock_step_is_knossos_mutex():
    acq, rel = {"f": "acquire", "value": None}, {"f": "release",
                                                 "value": None}
    free = LOCK.start(None, False)
    assert LOCK.step(free, acq, False) == 1
    assert LOCK.step(free, rel, False) is None
    assert LOCK.step(1, acq, False) is None
    assert LOCK.step(1, rel, False) == free
    assert LOCK.state_bits({}) == 1


def test_lock_program_agrees_with_the_reference(tmp_path):
    """The program's device checker on the lock cell's histories: valid,
    with a linearization that the reference replays."""
    config, traffic = cell_parts()
    chk, _model = sut.build(config)
    test = {"name": "lock", "store_base": str(tmp_path), "start_time": "t"}
    for events, _truth in gen.make_pool(SEED, config, traffic, LOCK):
        rows = reference.rows_of(events)
        r = chk.check(test, sut.history(events))
        assert r["valid"] is True
        assert reference.replay(LOCK, rows, r["linearization"], None)


# --- correct: the sound run, the control, the planted faults ---------------


def test_a_sound_run_is_correct():
    r = small_run()
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert list(r)[-1] == "checks"
    assert r["metrics"]["verified_ops_per_s"]["value"] > 0


@pytest.mark.parametrize("seed", [11, 2 ** 33 + 5, SEED])
def test_the_control_in_the_programs_place_is_not_correct(seed):
    r = small_run(seed=seed, control=1)
    assert not r["correct"]
    assert r["checks"]["verdict_mismatches"]["value"] >= 1


#: (fault, the number that has to catch it).  Every lock history is
#: valid, so a search stopped early answers its truth, and there is no
#: batch to leave half of out
FAULTS = [(_flip_one, "verdict_mismatches"),
          (_unchanged, "uncertified_valid_pct"),
          (_always_invalid, "verdict_mismatches"),
          (_bad_certificate, "bad_certificates")]


@pytest.mark.parametrize("fault,number", FAULTS,
                         ids=[f"{CELL}-{f.__name__}" for f, _n in FAULTS])
def test_a_planted_fault_is_not_correct(fault, number):
    r = small_run(sut=Planted(fault))
    assert not r["correct"], r["checks"]
    assert r["checks"][number]["value"] >= 1, r["checks"]


# --- the readers of the certification's spans ------------------------------


#: (metric, spans in a window [10, 20), value)
CASES = [
    # [12, 14) of two overlapping certifications; a confirmation is no
    # part of it
    ("certify_share", [_span("linearizable.certify", 12, 13),
                       _span("linearizable.certify", 12.5, 14),
                       _span("linearizable.confirm", 15, 16)], 20.0),
    # 0.5 s over 2,000 configurations; one begun before the window, and
    # one that records no configurations, count nothing
    ("certify_us_per_config",
     [_span("linearizable.certify", 11, 11.25, rows=900, configs=1000,
            valid=True),
      _span("linearizable.certify", 12, 12.25, rows=900, configs=1000,
            valid=True),
      _span("linearizable.certify", 13, 14, rows=900),
      _span("linearizable.certify", 9, 11, rows=900, configs=10,
            valid=True)],
     250.0),
]


@pytest.mark.parametrize("name,spans,want", CASES,
                         ids=[c[0] for c in CASES])
def test_certify_metric_by_hand(name, spans, want):
    assert _reader(name)(_ctx(spans)) == pytest.approx(want)


@pytest.mark.parametrize("name", CERTIFY_METRICS)
def test_certify_metric_is_silent_without_its_spans(name):
    """A program that certifies no valid verdict (a version older than
    the certification) gives no value, and no error."""
    read = _reader(name)
    assert read(_ctx([])) is None
    assert read(_ctx([_span("device.slice", 11, 12),
                      _span("linearizable.confirm", 12, 13,
                            configs=10, valid=False)])) is None


@pytest.mark.parametrize("name", CERTIFY_METRICS)
def test_certify_metric_entry(name):
    (m,) = [m for m in spec.load()["per_layer"] if m["name"] == name]
    assert (m["source"], m["layer"], m["better"], m["moves"]) == (
        "program_span", "entry points", "lower", "verified_ops_per_s")
    # a valid device verdict is certified only in the lock cell
    assert m["workloads"] == [CELL]


def test_a_traced_lock_run_reports_its_certify_metrics():
    r = small_run(trace=1)
    assert r["correct"], r["checks"]
    assert r["checks"]["uncertified_valid_pct"]["value"] == 0
    assert set(r["metrics"]) == set(CERTIFY_METRICS)
    assert 0 < r["metrics"]["certify_share"]["value"] < 100
    assert r["metrics"]["certify_us_per_config"]["value"] > 0
