"""CPU tests of the `rabbitmq-queue` cell (`benchmark/models/
unordered_queue.py`, `configs/rabbitmq-unordered-queue.json`,
`traffic/rabbitmq-queue.json`) and of its two readers: the generator at
small sizes and at the cell's own, knossos's unordered-queue, the sound
run, the control, planted faults, and a small traced run.

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
from core import gen, readers, reference, spec, sut  # noqa: E402
from core.xtrace import MODULES_LINE  # noqa: E402
from test_benchmark_harness import (  # noqa: E402,F401
    SEED, Planted, _always_invalid, _flip_one, _leave_the_process_as_found,
    _unchanged)
from test_phase_metrics import _ctx, _reader, _span  # noqa: E402

CELL = "rabbitmq-queue"
#: 2 histories of 120 ops, capacity 64, a drain of 3-6, of the middle
#: of such histories' work (238-341 configurations over 40 histories,
#: median 290)
SMALL = ({"encoded_ops": 120, "init": 64},
         {"pool": 2, "drain": [3, 6], "work": [275, 310]})
#: the model of the queue cell
QUEUE = spec.model({"checker": {"model": "unordered_queue"}})
METRICS = ("queue_step_roofline", "frontier_per_level")


def small_run(*, seed=SEED, sut=None, trace=0, control=0):
    return run.run(["--workload", CELL, "--seed", str(seed),
                    "--seconds", "1", "--trace", str(trace),
                    "--control", str(control)],
                   require_chip=False, config_override=SMALL[0],
                   traffic_override=SMALL[1], sut=sut)


def cell_parts(small=True):
    _wl, config, traffic = spec.cell(spec.load(), CELL)
    if not small:
        return config, traffic
    return {**config, **SMALL[0]}, {**traffic, **SMALL[1]}


def _drain(events):
    """The size of an expanded drain of at least one element: the k
    dequeue invocations, then their k :ok completions, that end the
    history (every other op completed before the drain began), nemesis
    events aside."""
    events = [e for e in events if e[0] != "nemesis"]
    k = r = 0
    while 2 * r + 2 <= len(events) \
            and events[-r - 1][1:3] == ("ok", "dequeue"):
        r += 1
        if all(e[1:3] == ("invoke", "dequeue")
               for e in events[-2 * r:-r]):
            k = r
    ends = events[len(events) - 2 * k:]
    assert sorted(e[0] for e in ends[:k]) == sorted(e[0] for e in ends[k:])
    return k


def _valid_and_searched(events, init):
    rows = reference.rows_of(events)
    assert reference.check(QUEUE, rows, init)[0] is True
    assert not QUEUE.completion_order_replays(events)
    # dropping the crashed ops leaves no linearization: a crashed
    # enqueue took effect and its value was dequeued or drained
    oks = [op for op in rows if op["type"] == "ok"]
    assert reference.check(QUEUE, oks, init)[0] is False


# --- generator -------------------------------------------------------------


def test_generator_is_deterministic_per_seed():
    config, traffic = cell_parts()
    a = gen.make_pool(SEED, config, traffic, QUEUE)
    assert a == gen.make_pool(SEED, config, traffic, QUEUE)
    assert a != gen.make_pool(SEED + 1, config, traffic, QUEUE)


def test_every_seed_gets_the_same_sizes():
    config, traffic = cell_parts()
    lo, hi = traffic["work"]
    for seed in (1, SEED, 2 ** 40 + 7):
        for events, truth in gen.make_pool(seed, config, traffic, QUEUE):
            assert truth == {None: True}
            assert gen.encoded_count(events) == config["encoded_ops"]
            rows = reference.rows_of(events)
            assert sum(op["type"] == "info" for op in rows) \
                == traffic["crashes"]
            assert traffic["drain"][0] <= _drain(events) \
                <= traffic["drain"][1]
            assert QUEUE.sized(events, config["init"])
            assert lo <= reference.check(QUEUE, rows, None)[1] <= hi


@pytest.mark.parametrize("n_ops,cap,drain", [(60, 32, [0, 2]),
                                             (120, 64, [3, 6]),
                                             (200, 128, [5, 8])])
def test_queue_histories_are_valid_and_searched(n_ops, cap, drain):
    rng = random.Random(SEED + n_ops)
    p = {"procs": 5, "overlap": 5, "crashes": 12, "enqueue_share": 0.5,
         "drain": drain, "init": cap}
    for _ in range(6):
        events = QUEUE.history(rng, p, n_ops, True)
        assert gen.encoded_count(events) == n_ops
        assert sum(e[1] == "info" for e in events) == 12
        assert QUEUE.sized(events, cap)
        _valid_and_searched(events, cap)
        if drain[0]:
            assert drain[0] <= _drain(events) <= drain[1]
    assert QUEUE.corrupt(events, p) is None


def test_the_cells_own_histories():
    """At the cell's size: 512-1,023 enqueues (so `QueueLinearizable`
    sizes the model at 1,024), at most 2,000 invocations with the drain
    (its default gate), a drain of 8-12, every history valid, searched,
    and failed by the control."""
    config, traffic = cell_parts(small=False)
    p = {**config, **traffic}
    rng = random.Random(SEED)
    for _ in range(2):
        events = QUEUE.history(rng, p, config["encoded_ops"], True)
        assert 512 <= QUEUE.enqueues(events) <= 1023
        assert QUEUE.sized(events, 1024)
        assert sum(e[1] == "invoke" for e in events) <= 2000
        assert gen.encoded_count(events) == 1800
        assert 8 <= _drain(events) <= 12
        _valid_and_searched(events, None)


def test_the_program_chains_the_probes_drain():
    assert QUEUE.program_chains_the_drain.__wrapped__() is True


def test_a_program_that_does_not_chain_the_drain_is_refused(monkeypatch):
    """A queue prepass without the canonical edges (the program before
    them) fails the probe, and the generator then refuses to start, so
    that a run stops in set-up instead of searching without end."""
    from jepsen_tpu.analyze import constraints

    monkeypatch.setattr(constraints, "_queue_canon_edges",
                        lambda *a, **k: [])
    assert QUEUE.program_chains_the_drain.__wrapped__() is False
    monkeypatch.setattr(QUEUE, "program_chains_the_drain", lambda: False)
    config, traffic = cell_parts()
    with pytest.raises(RuntimeError, match="does not chain"):
        QUEUE.history(random.Random(SEED), {**config, **traffic},
                      config["encoded_ops"], True)


def test_the_drain_is_expanded_as_the_suite_expands_it():
    """`expand_drains` over plain events, against the program's
    `QueueLinearizable._expand_drains` over the same history's ops."""
    from jepsen_tpu.checker.basic import QueueLinearizable

    rng = random.Random(SEED)
    raw = None
    while raw is None:
        raw = QUEUE.queue_history(rng, n_ops=120, n_procs=5, overlap=5,
                                  crashes=12, enqueue_share=0.5,
                                  drain=[3, 6], exact=True)
    ops, lossy = QueueLinearizable._expand_drains(sut.history(raw))
    assert lossy
    assert ops == sut.history(QUEUE.expand_drains(raw))


def test_queue_step_is_knossos_unordered_queue():
    def op(f, v):
        return {"f": f, "value": v}

    empty = QUEUE.start(1024, False)
    one = QUEUE.step(empty, op("enqueue", 7), False)
    assert QUEUE.step(empty, op("dequeue", 7), False) is None
    assert QUEUE.step(one, op("dequeue", 7), False) == empty
    two = QUEUE.step(one, op("enqueue", 7), False)
    assert QUEUE.step(QUEUE.step(two, op("dequeue", 7), False),
                      op("dequeue", 7), False) == empty
    # a crashed dequeue returned no value: a no-op
    assert QUEUE.step(one, op("dequeue", None), False) == one
    assert QUEUE.state_bits({"init": 1024}) == 1024


def test_queue_program_agrees_with_the_reference(tmp_path):
    """The program's device checker on the cell's histories: valid, with
    a linearization that the reference replays."""
    config, traffic = cell_parts()
    chk, _model = sut.build(config)
    test = {"name": "queue", "store_base": str(tmp_path),
            "start_time": "t"}
    for events, _truth in gen.make_pool(SEED, config, traffic, QUEUE):
        rows = reference.rows_of(events)
        r = chk.check(test, sut.history(events))
        assert r["valid"] is True
        assert reference.replay(QUEUE, rows, r["linearization"], None)


# --- correct: the sound run, the control, the planted faults ---------------


def test_a_sound_run_is_correct():
    r = small_run()
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert r["checks"]["uncertified_valid_pct"]["value"] == 0
    assert r["metrics"]["verified_ops_per_s"]["value"] > 0


@pytest.mark.parametrize("seed", [11, 2 ** 33 + 5, SEED])
def test_the_control_in_the_programs_place_is_not_correct(seed):
    r = small_run(seed=seed, control=1)
    assert not r["correct"]
    assert r["checks"]["verdict_mismatches"]["value"] >= 1


def _last_placed_first(res):
    # a linearization altered where it is produced: its last op placed
    # first.  (`_bad_certificate`'s swap of the last two is no fault
    # here: they are the drain's dequeues, which commute)
    lin = res.get("linearization")
    if res["valid"] is True and lin is not None and len(lin) > 1:
        res["linearization"] = lin[-1:] + lin[:-1]
    return res


#: (fault, the number that has to catch it): every queue history is
#: valid, as every lock history is
FAULTS = [(_flip_one, "verdict_mismatches"),
          (_unchanged, "uncertified_valid_pct"),
          (_always_invalid, "verdict_mismatches"),
          (_last_placed_first, "bad_certificates")]


@pytest.mark.parametrize("fault,number", FAULTS,
                         ids=[f"{CELL}-{f.__name__}" for f, _n in FAULTS])
def test_a_planted_fault_is_not_correct(fault, number):
    r = small_run(sut=Planted(fault))
    assert not r["correct"], r["checks"]
    assert r["checks"][number]["value"] >= 1, r["checks"]


# --- the two readers --------------------------------------------------------


def test_frontier_per_level_by_hand():
    # two slices of packed 1,027-word configurations, 4 and 2 levels;
    # the second's last level span folds two levels.  A slice begun
    # before the window and its level count nothing
    spans = [_span("device.slice", 11, 12, state_words=1027,
                   levels_run=4),
             *[_span("device.level", 11 + i / 8, 11 + (i + 1) / 8,
                     next_count=n) for i, n in enumerate((3, 5, 8, 2))],
             _span("device.slice", 13, 14, state_words=1027,
                   levels_run=2),
             _span("device.level", 13, 14, next_count=12),
             _span("device.slice", 9, 9.5, state_words=1027,
                   levels_run=7),
             _span("device.level", 9, 9.5, next_count=100)]
    assert _reader("frontier_per_level")(_ctx(spans)) \
        == pytest.approx(30 / 6)


def test_frontier_per_level_is_silent_without_state_words():
    """A program older than the `state_words` arg gives no value, and no
    error."""
    read = _reader("frontier_per_level")
    assert read(_ctx([])) is None
    assert read(_ctx([_span("device.slice", 11, 12, levels_run=4),
                      _span("device.level", 11, 12, next_count=3)])) \
        is None


def test_queue_step_roofline_by_hand():
    """The least bytes of the level loop (the encoded history once a
    slice, a level's frontier in and out at the fewest bytes a queue
    configuration takes) over 819 GB/s, over the level programs' device
    time: `level_step_roofline`'s reading, with the queue's 1,024 bits
    of state."""
    rows = [{"f": "enqueue", "value": 1, "inv": 0, "ret": 1,
             "type": "ok"},
            {"f": "dequeue", "value": 1, "inv": 2, "ret": 3,
             "type": "ok"},
            {"f": "enqueue", "value": 2, "inv": 4, "ret": gen.INF,
             "type": "info"}]
    spans = [_span("device.slice", 11, 12, state_words=1027),
             _span("device.level", 11, 11.5, occupancy=4, next_count=6),
             _span("device.level", 11.5, 12, occupancy=6, next_count=2)]
    ctx = {**_ctx(spans), "calls": [(0, {}, 10.5, 12.5)],
           "pool": [{"rows": {None: rows}}], "state_bits": 1024,
           "device_kind": "TPU v5 lite",
           "events": [{"line": MODULES_LINE, "name": "jit_step(1a)",
                       "plane": 0, "t0": 11.0, "dur": 2e-6}]}
    cfg = readers.config_bytes(rows, 1024)
    assert cfg == 129    # 2 + 1 + 1 + 1,024 bits
    least = cfg * 18 + readers.table_bytes(rows, 1024)
    want = 100.0 * least / 819e9 / 2e-6
    assert _reader("queue_step_roofline")(ctx) == pytest.approx(want)
    assert _reader("level_step_roofline")(ctx) == pytest.approx(want)
    assert 0 < want < 100


@pytest.mark.parametrize("name", METRICS)
def test_queue_metric_entry(name):
    (m,) = [m for m in spec.load()["per_layer"] if m["name"] == name]
    assert (m["layer"], m["moves"], m["workloads"]) == (
        "kernels", "verified_ops_per_s", [CELL])


def test_a_traced_queue_run_reports_its_frontier():
    """On the CPU the trace holds no chip's programs, so the roofline
    reads nothing; the frontier reads the program's spans."""
    r = small_run(trace=1)
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) <= set(METRICS)
    assert r["metrics"]["frontier_per_level"]["value"] > 0
