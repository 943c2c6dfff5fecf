"""A valid device verdict gets a certificate: `Linearizable._check_direct`
re-checks a history of at most ``witness_threshold`` rows on the host and
returns the host's linearization, or "unknown" where the two searches
disagree.  Lock histories (`synth.sim_mutex_history`) whose completion
order is no linearization put the device BFS, not the greedy witness, on
the path.

    JAX_PLATFORMS=cpu python3 -m pytest tests/test_certify.py -q
"""

from __future__ import annotations

import random

import pytest

from jepsen_tpu import obs
from jepsen_tpu.analyze.audit import audit
from jepsen_tpu.checker import linearizable as lin
from jepsen_tpu.checker.linearizable import Linearizable, greedy_witness
from jepsen_tpu.history import encode_ops, invoke_op, ok_op
from jepsen_tpu.models import mutex
from jepsen_tpu.synth import sim_mutex_history

#: seeds of `_lock_history` whose completion order does not replay (and
#: that the static prepass leaves to the search)
SEEDS = (1, 2, 9, 11)


def _lock_history(seed):
    rng = random.Random(seed)
    return sim_mutex_history(rng, n_ops=rng.randrange(60, 200), n_procs=4,
                             crash_p=0.05, max_crashes=6)


def _replays(seq, model, order):
    """``order`` holds every ok row once, each row invoked before every
    row not yet placed returned, and the model allows each step."""
    oks = {i for i in range(len(seq)) if bool(seq.ok[i])}
    if len(set(order)) != len(order) or not oks <= set(order):
        return False
    state = model.init
    placed = set()
    for i in order:
        if any(int(seq.ret[j]) < int(seq.inv[i])
               for j in oks - placed - {i}):
            return False
        placed.add(i)
        state = model.pystep(state, int(seq.f[i]), int(seq.v1[i]),
                             int(seq.v2[i]))
        if state is None:
            return False
    return True


def _test(tmp_path):
    return {"name": "certify", "store_base": str(tmp_path),
            "start_time": "t"}


@pytest.mark.parametrize("seed", SEEDS)
def test_a_valid_device_verdict_carries_a_linearization(seed, tmp_path):
    model = mutex()
    h = _lock_history(seed)
    seq = encode_ops(h, model.f_codes)
    assert not greedy_witness(seq, model)
    r = Linearizable(model, algorithm="device").check(_test(tmp_path), h)
    assert r["valid"] is True
    assert r["engine"].startswith("device-bfs")
    assert r["engine"].endswith("+host-witness")
    assert "witness_dropped" not in r
    assert r["certify_configs"] > 0
    assert _replays(seq, model, r["linearization"])
    assert audit(seq, model, r)["ok"]


def test_above_the_witness_threshold_the_verdict_stays_uncertified(
        tmp_path):
    model = mutex()
    h = _lock_history(SEEDS[0])
    chk = Linearizable(model, algorithm="device", witness_threshold=10)
    r = chk.check(_test(tmp_path), h)
    assert r["valid"] is True
    assert r.get("linearization") is None
    assert r["witness_dropped"] == lin.WITNESS_DROPPED_DEVICE
    assert "+host-witness" not in r["engine"]


def test_a_host_that_contradicts_the_device_makes_it_unknown(
        monkeypatch, tmp_path):
    model = mutex()
    # a device search that answers "valid" where the truth is "invalid":
    # two ok acquires, one after the other, and no release
    device = {"valid": True, "configs": 7, "max_depth": 2,
              "engine": "device-bfs",
              "witness_dropped": lin.WITNESS_DROPPED_DEVICE}
    monkeypatch.setattr(lin, "search_opseq",
                        lambda *a, **kw: dict(device))
    bad = [invoke_op(0, "acquire", None), ok_op(0, "acquire", None),
           invoke_op(1, "acquire", None), ok_op(1, "acquire", None)]
    chk = Linearizable(model, algorithm="device", hb=False)
    r = chk.check(_test(tmp_path), bad)
    assert r["valid"] == "unknown"
    assert r["device"]["valid"] is True
    assert r["host"]["valid"] is False
    assert "disagree" in r["error"]


def test_a_device_win_of_the_competition_is_certified(monkeypatch,
                                                      tmp_path):
    """``algorithm="auto"`` races the host searches against the device;
    here both host legs wait until the race is over, so the device wins
    it and the certification re-checks the history alone."""
    from jepsen_tpu.checker import linear, seq as seqmod

    def loser(real):
        def leg(*a, **kw):
            if "cancel" not in kw:
                return real(*a, **kw)
            kw["cancel"].wait(60)
            return {"valid": "unknown", "configs": 0}
        return leg

    monkeypatch.setattr(seqmod, "check_opseq", loser(seqmod.check_opseq))
    monkeypatch.setattr(linear, "check_opseq_linear",
                        loser(linear.check_opseq_linear))
    model = mutex()
    h = _lock_history(SEEDS[2])
    seq = encode_ops(h, model.f_codes)
    # a short history, so the race is made to start at a low threshold
    chk = Linearizable(model, algorithm="auto", host_threshold=8)
    assert len(seq) > chk.host_threshold
    r = chk.check(_test(tmp_path), h)
    assert r["valid"] is True
    assert r["engine"] == "competition(device)+host-witness"
    assert "witness_dropped" not in r
    assert _replays(seq, model, r["linearization"])


def test_a_host_that_gives_up_leaves_the_verdict_uncertified(
        monkeypatch, tmp_path):
    """The certification is bounded by the competition's memory cap and
    by the device search's own time; a host search that runs out of
    either answers "unknown", and the device verdict stands as it was."""
    import time

    from jepsen_tpu.checker import seq as seqmod

    calls = []

    def gives_up(seq, model, **kw):
        calls.append((len(seq), kw, time.perf_counter()))
        return {"valid": "unknown", "configs": kw["max_configs"] + 1,
                "info": "exceeded max_configs"}

    monkeypatch.setattr(seqmod, "check_opseq", gives_up)
    model = mutex()
    h = _lock_history(SEEDS[3])
    r = Linearizable(model, algorithm="device").check(_test(tmp_path), h)
    assert r["valid"] is True
    assert r.get("linearization") is None
    assert r["witness_dropped"] == lin.WITNESS_DROPPED_DEVICE
    assert r["engine"].startswith("device-bfs")
    assert "+host-witness" not in r["engine"]
    ((rows, kw, at),) = calls
    assert kw["max_configs"] == lin.host_config_cap(rows)
    # 3,000 rows cap near 3.5M configurations, not the DFS's own 5M
    assert lin.host_config_cap(3000) < 5_000_000
    assert at < kw["deadline"] < float("inf")


def test_the_certification_is_spanned_with_its_configs(tmp_path):
    model = mutex()
    h = _lock_history(SEEDS[1])
    obs.enable(True)
    try:
        obs.set_run(None)
        obs.recorder(None).clear()
        r = Linearizable(model, algorithm="device").check(_test(tmp_path),
                                                          h)
        spans = obs.recorder(None).spans()
    finally:
        obs.enable(None)
        obs.set_run(None)
    (cert,) = [s for s in spans if s["name"] == "linearizable.certify"]
    assert cert["args"]["rows"] == len(encode_ops(h, model.f_codes))
    assert cert["args"]["configs"] == r["certify_configs"] > 0
    assert cert["args"]["valid"] is True
    # the certification runs after the device search, outside its slices
    slices = [s for s in spans if s["name"] == "device.slice"]
    assert slices and all(s["ts"] + s["dur"] <= cert["ts"] + 0.2
                          for s in slices)
