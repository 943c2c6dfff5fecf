"""The RabbitMQ queue deployment (`benchmark/configs/rabbitmq-unordered-
queue.json`) at small sizes on the CPU: the device search over knossos's
unordered-queue against the plain WGL search (`checker/seq.py`, no
prepass) and the benchmark's reference on the cell's own histories,
planted duplicate deliveries, the suite's `QueueLinearizable` entry
against the cell's direct call, and the queue prepass's canonical edges
against random small histories.

    JAX_PLATFORMS=cpu python3 -m pytest tests/test_queue_deployment.py -q
"""

from __future__ import annotations

import os
import random
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

from core import reference, spec, sut  # noqa: E402

from jepsen_tpu.analyze.constraints import analyze_constraints  # noqa: E402
from jepsen_tpu.checker import seq as seqmod  # noqa: E402
from jepsen_tpu.checker.basic import QueueLinearizable  # noqa: E402
from jepsen_tpu.checker.linearizable import Linearizable  # noqa: E402
from jepsen_tpu.history import encode_ops, info_op, invoke_op, ok_op  # noqa: E402,E501
from jepsen_tpu.models import unordered_queue  # noqa: E402

#: the cell's generator and plain reference (`benchmark/models/`)
QUEUE = spec.model({"checker": {"model": "unordered_queue"}})

#: (encoded ops, capacity, crashes, drain): small deployments whose
#: enqueue counts `QueueLinearizable` sizes at the capacity
SIZES = [(40, 32, 4, [0, 0]), (100, 64, 8, [1, 4]), (200, 128, 12, [5, 8])]


def deployment(cap, crashes, drain):
    return {"procs": 5, "overlap": 5, "crashes": crashes,
            "enqueue_share": 0.5, "drain": drain, "init": cap}


def checked(chk, events, tmp_path):
    test = {"name": "queue", "store_base": str(tmp_path),
            "start_time": "t"}
    return chk.check(test, sut.history(events))


def plain_wgl(events, cap):
    model = unordered_queue(cap)
    seq = encode_ops(sut.history(events), model.f_codes)
    return seqmod.check_opseq(seq, model, lint=False, hb=False, dpor=False)


@pytest.mark.parametrize("n_ops,cap,crashes,drain", SIZES,
                         ids=[f"{s[0]}ops" for s in SIZES])
def test_device_agrees_with_the_plain_wgl(n_ops, cap, crashes, drain,
                                          tmp_path):
    rng = random.Random(3000 + n_ops)
    chk = Linearizable(unordered_queue(cap), algorithm="device")
    for _ in range(3):
        events = QUEUE.history(rng, deployment(cap, crashes, drain), n_ops,
                               True)
        rows = reference.rows_of(events)
        got = checked(chk, events, tmp_path)
        want = plain_wgl(events, cap)
        assert got["valid"] is want["valid"] is True
        assert reference.check(QUEUE, rows, None)[0] is True
        for r in (got, want):
            assert reference.replay(QUEUE, rows, r["linearization"], None)
        assert "device-bfs" in got["engine"]


def _duplicate_delivery(events):
    """The last :ok dequeue returns the value an earlier one returned."""
    deq = [i for i, e in enumerate(events)
           if e[1] == "ok" and e[2] == "dequeue"]
    first, last = deq[0], deq[-1]
    p, typ, f, _v = events[last]
    return events[:last] + [(p, typ, f, events[first][3])] \
        + events[last + 1:]


@pytest.mark.parametrize("n_ops,cap,crashes,drain", SIZES[1:],
                         ids=[f"{s[0]}ops" for s in SIZES[1:]])
def test_a_planted_duplicate_delivery_is_refuted(n_ops, cap, crashes,
                                                 drain, tmp_path):
    rng = random.Random(4000 + n_ops)
    events = _duplicate_delivery(
        QUEUE.history(rng, deployment(cap, crashes, drain), n_ops, True))
    assert reference.check(QUEUE, reference.rows_of(events), None)[0] \
        is False
    got = checked(Linearizable(unordered_queue(cap), algorithm="device"),
                  events, tmp_path)
    assert got["valid"] is False
    assert plain_wgl(events, cap)["valid"] is False


def _raw_history(rng, n_ops, cap, crashes, drain):
    """A history as the suite records it: the drains not expanded."""
    while True:
        h = QUEUE.queue_history(rng, n_ops=n_ops, n_procs=5, overlap=5,
                                crashes=crashes, enqueue_share=0.5,
                                drain=drain, exact=True)
        if h is not None and QUEUE.sized(h, cap):
            return h


@pytest.mark.parametrize("n_ops,cap,crashes,drain", SIZES,
                         ids=[f"{s[0]}ops" for s in SIZES])
def test_the_suites_entry_checks_what_the_cell_checks(n_ops, cap, crashes,
                                                      drain, tmp_path):
    """`QueueLinearizable.check` on the raw history (the suite's
    `--queue-linear` entry) sizes the model at the cell's capacity and
    gives the verdict of the cell's direct call on the expanded one."""
    rng = random.Random(5000 + n_ops)
    direct = Linearizable(unordered_queue(cap), algorithm="device")
    for _ in range(2):
        raw = _raw_history(rng, n_ops, cap, crashes, drain)
        suite = checked(QueueLinearizable(), raw, tmp_path)
        cell = checked(direct, QUEUE.expand_drains(raw), tmp_path)
        assert suite["model"] == unordered_queue(cap).name
        assert suite["valid"] is cell["valid"] is True
        ops, _lossy = QueueLinearizable._expand_drains(sut.history(raw))
        assert ops == sut.history(QUEUE.expand_drains(raw))


def test_the_prepass_chains_the_drain_and_places_crashed_enqueues():
    model = unordered_queue(16)
    h = [invoke_op(0, "enqueue", 1), invoke_op(1, "enqueue", 2),
         info_op(0, "enqueue", 1), ok_op(1, "enqueue", 2),
         invoke_op(1, "enqueue", 3), ok_op(1, "enqueue", 3),
         invoke_op(2, "dequeue", None), ok_op(2, "dequeue", 1),
         invoke_op(3, "dequeue", None), invoke_op(4, "dequeue", None),
         ok_op(3, "dequeue", 2), ok_op(4, "dequeue", 3)]
    seq = encode_ops(h, model.f_codes)
    a = analyze_constraints(seq, model)
    assert a.decided is None
    assert (a.stats["early_enqueues"], a.stats["drain_chain"]) == (1, 2)
    # the crashed enqueue (row 0) goes before the :ok enqueue invoked
    # while it was open; the drain's two dequeues (rows 4, 5) in order
    assert 0 in a.must_pred[1] and 4 in a.must_pred[5]
    # a capacity that could bind: no canonical edge
    a = analyze_constraints(seq, unordered_queue(2))
    assert a.stats["edges"]["canon"] == 0


def _random_queue_history(rng):
    """A small queue history of random intervals and values, valid or
    not: duplicates, crashed enqueues and dequeues (with and without a
    value), and sometimes a final drain of concurrent dequeues."""
    n = rng.randrange(3, 9)
    events, open_ = [], {}
    for p in range(n):
        f = rng.choice(["enqueue", "dequeue"])
        v = rng.randrange(4) if f == "enqueue" or rng.random() < 0.8 \
            else None
        events.append(("inv", p, f, v))
        open_[p] = (f, v)
        while open_ and rng.random() < 0.5:
            q = rng.choice(sorted(open_))
            events.append(("done", q) + open_.pop(q))
    for q in sorted(open_):
        events.append(("done", q) + open_.pop(q))
    if rng.random() < 0.5:
        k = rng.randrange(2, 4)
        events += [("inv", n + i, "dequeue", None) for i in range(k)]
        events += [("done", n + i, "dequeue", rng.randrange(4))
                   for i in range(k)]
    crashed = {p for p in range(n) if rng.random() < 0.3}
    out = []
    for kind, p, f, v in events:
        if kind == "inv":
            out.append(invoke_op(p, f, None if f == "dequeue" else v))
        elif p in crashed:
            out.append(info_op(p, f, v if f == "enqueue"
                               or rng.random() < 0.5 else None))
        else:
            out.append(ok_op(p, f, v))
    return out


@pytest.mark.parametrize("cap,least_edged", [(4, 10), (16, 40)])
def test_canonical_edges_keep_every_verdict(cap, least_edged, tmp_path):
    """The prepass's edges on random small histories: the host search
    under them, and for the first of them the device search, answer as
    the plain one (at capacity 4 some histories can fill the queue, and
    the prepass then emits none)."""
    rng = random.Random(cap)
    model = unordered_queue(cap)
    device = Linearizable(model, algorithm="device")
    seen = {True: 0, False: 0}
    edged = 0
    for i in range(400):
        h = _random_queue_history(rng)
        seq = encode_ops(h, model.f_codes)
        plain = seqmod.check_opseq(seq, model, lint=False, hb=False,
                                   dpor=False)
        pruned = seqmod.check_opseq(seq, model, lint=False, hb=True,
                                    dpor=True)
        assert pruned["valid"] is plain["valid"]
        if i < 40:
            got = device.check({"name": "q", "store_base": str(tmp_path),
                                "start_time": "t"}, h)
            assert got["valid"] is plain["valid"]
        seen[plain["valid"]] += 1
        a = analyze_constraints(seq, model)
        edged += a.decided is None and a.stats["edges"]["canon"] > 0
    assert min(seen.values()) >= 40
    assert edged >= least_edged
