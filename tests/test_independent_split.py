"""The one-pass key split (`independent.subhistories`) against the per-key
rescan it replaces (`independent.subhistory` over `history_keys`)."""

from __future__ import annotations

import random
from dataclasses import dataclass

import pytest

from jepsen_tpu import independent, obs
from jepsen_tpu.history import Op, info_op, invoke_op, ok_op
from jepsen_tpu.independent import KV

#: keys of mixed types; 1 and 1.0 (and True) are one dict key and equal
#: under ``==``, so both splits merge them
KEYS = [0, 1, 1.0, 2, True, "a", "b", ("x", 1), ("y",), (1, "z")]


def _per_key(h):
    return {k: independent.subhistory(k, h)
            for k in independent.history_keys(h)}


def _unkeyed(rng, i):
    """An op whose value is not a KV: nemesis, logging, or a client op
    with a plain value (a CAS pair is a tuple, not a key)."""
    return rng.choice([
        lambda: info_op("nemesis", rng.choice(["start", "stop"]), None),
        lambda: info_op("nemesis", "partition", {"n1": ["n2"]}),
        lambda: invoke_op(rng.randrange(4), "cas",
                          (rng.randrange(3), rng.randrange(3)), index=i),
        lambda: ok_op(rng.randrange(4), "read", rng.randrange(3), time=i),
        lambda: Op("logger", "info", "log", "note", error="x"),
    ])()


def _keyed(rng, i):
    v = rng.choice([None, rng.randrange(5),
                    (rng.randrange(5), rng.randrange(5))])
    return Op(rng.randrange(8), rng.choice(["invoke", "ok", "fail", "info"]),
              rng.choice(["read", "write", "cas"]),
              KV(rng.choice(KEYS), v), time=i * 10, index=i,
              error=rng.choice([None, "timeout"]))


def _history(seed):
    """Un-keyed ops before the first key, between keys and after the
    last one, around keyed ops of every key in `KEYS`."""
    rng = random.Random(seed)
    h = [_unkeyed(rng, i) for i in range(rng.randrange(1, 4))]
    for i in range(len(h), 200):
        h.append(_unkeyed(rng, i) if rng.random() < 0.2 else _keyed(rng, i))
    return h + [_unkeyed(rng, 200 + i) for i in range(rng.randrange(1, 4))]


def _assert_same_split(h):
    new = independent.subhistories(h)
    old = _per_key(h)
    assert new == old
    assert list(new) == list(old) == independent.history_keys(h)
    # un-keyed ops are the very objects of the history, in every
    # subhistory, as the per-key rescan keeps them
    unkeyed = [op for op in h if not independent.is_tuple(op.value)]
    for k, sub in new.items():
        shared = [op for op in sub
                  if any(op is u for u in unkeyed)]
        assert len(shared) == len(unkeyed)
        assert all(a is b for a, b in zip(shared, unkeyed))
        assert all(not independent.is_tuple(op.value) for op in sub)
    return new


@pytest.mark.parametrize("seed", range(8))
def test_split_matches_per_key_rescan(seed):
    new = _assert_same_split(_history(seed))
    assert len(new) >= 5
    # 1, 1.0 and True are one key, under the first spelling seen
    assert sum(1 for k in new if k == 1) == 1


@pytest.mark.parametrize("h", [
    [],
    [info_op("nemesis", "start", None), invoke_op(0, "cas", (1, 2)),
     ok_op(0, "cas", (1, 2)), info_op("nemesis", "stop", None)],
], ids=["empty", "no-keys"])
def test_split_without_keys(h):
    assert _assert_same_split(h) == {}


def test_split_cas_pair_stays_unkeyed():
    h = [invoke_op(0, "cas", (0, 1)), ok_op(0, "cas", (0, 1)),
         invoke_op(1, "write", KV("k", 3)), ok_op(1, "write", KV("k", 3)),
         invoke_op(0, "cas", KV("k", (3, 4))),
         ok_op(0, "cas", KV("k", (3, 4)))]
    new = _assert_same_split(h)
    assert list(new) == ["k"]
    assert [op.value for op in new["k"]] == [(0, 1), (0, 1), 3, 3,
                                             (3, 4), (3, 4)]
    assert new["k"][0] is h[0]


def test_split_keeps_op_subclass():
    @dataclass
    class TaggedOp(Op):
        tag: str = "t"

    h = [TaggedOp(0, "invoke", "write", KV("k", 1), tag="w"),
         Op(0, "ok", "write", KV("k", 1), time=5, index=1, error="e")]
    new = _assert_same_split(h)
    first, second = new["k"]
    assert type(first) is TaggedOp and first.tag == "w" and first.value == 1
    assert type(second) is Op and second == Op(0, "ok", "write", 1, 5, 1, "e")


# ---------------------------------------------------------------------------
# the checker: the same verdicts as a split by per-key rescan
# ---------------------------------------------------------------------------


def _etcdemo_test(seed, n_keys=20, stale=(3, 8, 15)):
    """A small etcdemo-like test: a valid CAS-register history per key
    (30 or 100 ops, so keys go both to the host and to the device batch),
    processes offset per key, a stale read near the end of the ``stale``
    keys, groups interleaved at random with nemesis events spread in."""
    from dataclasses import replace

    from jepsen_tpu.synth import register_history

    rng = random.Random(seed)
    streams = []
    for k in range(n_keys):
        h = register_history(rng, n_ops=30 if k % 4 == 1 else 100,
                             n_procs=5,
                             overlap=3, crash_p=0.02, max_crashes=1,
                             n_values=5)
        if k in stale:
            reads = [i for i, op in enumerate(h)
                     if op.type == "ok" and op.f == "read"]
            writes = [op.value for op in h
                      if op.type == "ok" and op.f == "write"]
            i = reads[-1]
            old = next((v for v in writes if v != h[i].value), 99)
            h[i] = replace(h[i], value=old)
        streams.append([replace(op, process=op.process + 10 * k,
                                value=KV(k, op.value)) for op in h])
    out, heads = [], [0] * n_keys
    live = list(range(n_keys))
    while live:
        g = rng.choice(live)
        out.append(streams[g][heads[g]])
        heads[g] += 1
        if heads[g] == len(streams[g]):
            live.remove(g)
        if rng.random() < 0.01:
            out.append(info_op("nemesis", rng.choice(["start", "stop"]),
                               None))
    return out


def test_checker_verdicts_match_per_key_split(monkeypatch, tmp_path):
    from jepsen_tpu.checker.linearizable import Linearizable
    from jepsen_tpu.models import cas_register

    h = _etcdemo_test(27)
    assert any(not independent.is_tuple(op.value) for op in h)
    test = {"name": "split", "store_base": str(tmp_path), "start_time": "t"}
    chk = independent.checker(Linearizable(cas_register()))
    new = chk.check(test, h)

    def per_key_split(history):
        return _per_key(history), sum(
            not independent.is_tuple(op.value) for op in history)

    monkeypatch.setattr(independent, "_split", per_key_split)
    old = chk.check(test, h)
    assert new["valid"] == old["valid"] is False
    assert new["failures"] == old["failures"] != []
    assert list(new["results"]) == list(old["results"])
    assert sorted(new["results"]) == list(range(20))
    for k in range(20):
        n, o = new["results"][k], old["results"][k]
        assert n["valid"] == o["valid"], k


def test_split_span_args(tmp_path):
    from jepsen_tpu.checker.linearizable import Linearizable
    from jepsen_tpu.models import cas_register

    h = [info_op("nemesis", "start", None),
         invoke_op(0, "write", KV("a", 1)), ok_op(0, "write", KV("a", 1)),
         info_op("nemesis", "stop", None),
         invoke_op(1, "read", KV("b", None)), ok_op(1, "read", KV("b", None)),
         invoke_op(2, "cas", (0, 1)), info_op(2, "cas", (0, 1))]
    obs.enable(True)
    try:
        obs.set_run(None)
        obs.recorder(None).clear()
        r = independent.checker(Linearizable(cas_register())).check(
            {"name": "span", "store_base": str(tmp_path),
             "start_time": "t"}, h)
        spans = obs.recorder(None).spans()
    finally:
        obs.enable(None)
        obs.set_run(None)
    assert r["valid"] is True and r["failures"] == []
    (split,) = [s for s in spans if s["name"] == "independent.split"]
    assert split["args"] == {"events": 8, "keys": 2, "unkeyed": 4}
