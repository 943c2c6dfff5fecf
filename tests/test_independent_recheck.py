"""The re-check of a key the device batch found invalid: confirmed on the
host from the batch's own depth and window, with no solo device search
(`IndependentChecker._device_batch`, `Linearizable.confirm_batch_verdict`),
against the sub-checker's own check of the key alone."""

from __future__ import annotations

import pytest
from test_independent_split import _etcdemo_test

from jepsen_tpu import independent, obs
from jepsen_tpu.checker import linearizable as lin
from jepsen_tpu.checker.linearizable import Linearizable
from jepsen_tpu.history import ok_op
from jepsen_tpu.independent import KV
from jepsen_tpu.models import cas_register

#: the keys `_etcdemo_test` plants a stale read in: each holds 100 ops,
#: above `host_threshold`, so the batch finds it invalid
STALE = (3, 8)


def _check(chk, h, tmp_path):
    """``chk.check`` of ``h`` with spans recorded: (result, spans)."""
    obs.enable(True)
    try:
        obs.set_run(None)
        obs.recorder(None).clear()
        r = chk.check({"name": "recheck", "store_base": str(tmp_path),
                       "start_time": "t"}, h)
        spans = obs.recorder(None).spans()
    finally:
        obs.enable(None)
        obs.set_run(None)
    return r, spans


def _key_spans(spans):
    return {s["args"]["key"]: s for s in spans
            if s["name"] == "independent.key"
            and s["args"]["why"] in ("confirm", "recheck")}


def _inside(spans, outer, name):
    """The ``name`` spans that open inside ``outer``."""
    return [s for s in spans if s["name"] == name
            and outer["ts"] <= s["ts"] < outer["ts"] + outer["dur"]]


def _final_paths(r):
    return [p["linearized"] for p in r.get("final_paths") or []]


def _solo(chk, h, k, tmp_path):
    return chk.check({"name": "solo", "store_base": str(tmp_path),
                      "start_time": "t"}, independent.subhistories(h)[k])


@pytest.fixture(scope="module")
def history():
    return _etcdemo_test(27, n_keys=12, stale=STALE)


@pytest.mark.parametrize("algorithm", ["device", "auto"])
def test_confirm_matches_solo_check(history, algorithm, tmp_path):
    sub = Linearizable(cas_register(), algorithm=algorithm)
    r, spans = _check(independent.checker(sub), history, tmp_path)
    assert sorted(r["failures"]) == list(STALE)
    ks = _key_spans(spans)
    assert sorted(ks) == list(STALE)
    # `auto`'s own check races host searches against the device search;
    # where the host's linear sweep wins, its verdict has no final paths
    # and counts depth otherwise, so the answer to match is the one of
    # the race's other winners: the device search confirmed on the host
    device = Linearizable(cas_register(), algorithm="device")
    for k in STALE:
        got, want = r["results"][k], _solo(device, history, k, tmp_path)
        assert _solo(sub, history, k, tmp_path)["valid"] is False
        assert got["valid"] is want["valid"] is False
        assert got["max_depth"] == want["max_depth"] > 0
        assert _final_paths(got) == _final_paths(want) != []
        assert got["engine"].startswith("device-batch")
        assert got["witness_prefix_ops"] == want["witness_prefix_ops"]
        # the fast path, with no device search of its own
        assert ks[k]["args"]["why"] == "confirm"
        assert ks[k]["args"]["confirmed"] is True
        assert _inside(spans, ks[k], "linearizable.confirm")
        assert not _inside(spans, ks[k], "device.slice")


def _edit_batch(monkeypatch, edit):
    """`search_batch` with ``edit`` applied to each invalid verdict."""
    search_batch = lin.search_batch

    def edited(seqs, model, **kw):
        out = search_batch(seqs, model, **kw)
        for r in out:
            if r["valid"] is False:
                edit(r)
        return out

    monkeypatch.setattr(lin, "search_batch", edited)


def test_too_short_cut_falls_back(history, monkeypatch, tmp_path):
    """A batch depth that cuts the history before its failure gives a
    valid prefix: the key falls back to the solo check, which answers
    as it does alone."""
    _edit_batch(monkeypatch, lambda r: r.update(max_depth=0, window=1))
    sub = Linearizable(cas_register(), algorithm="device")
    r, spans = _check(independent.checker(sub), history, tmp_path)
    assert sorted(r["failures"]) == list(STALE)
    for k in STALE:
        (confirm,) = [s for s in spans if s["name"] == "independent.key"
                      and s["args"]["key"] == k
                      and s["args"]["why"] == "confirm"]
        (fallback,) = [s for s in spans if s["name"] == "independent.key"
                       and s["args"]["key"] == k
                       and s["args"]["why"] == "recheck"]
        assert confirm["args"]["confirmed"] is False
        assert fallback["args"]["fallback"] == "prefix True"
        assert _inside(spans, fallback, "device.slice")
        got, want = r["results"][k], _solo(sub, history, k, tmp_path)
        assert got["valid"] is want["valid"] is False
        assert got["max_depth"] == want["max_depth"]
        assert _final_paths(got) == _final_paths(want) != []
        assert got["engine"] == want["engine"]


def test_confirm_error_falls_back(history, monkeypatch, tmp_path):
    """A confirmation that raises costs the key only its fast path: it
    gets the sub-checker's own check."""
    def broken(*_a, **_kw):
        raise RuntimeError("planted")

    monkeypatch.setattr(Linearizable, "_confirm_invalid", broken)
    sub = Linearizable(cas_register(), algorithm="device")
    r, spans = _check(independent.checker(sub), history, tmp_path)
    ks = _key_spans(spans)
    for k in STALE:
        assert ks[k]["args"]["fallback"] == "RuntimeError"
        # the own check confirms through the same method, so it fails
        # the same way, and check_safe makes the key "unknown"
        assert r["results"][k]["valid"] == "unknown"
        assert "planted" in r["results"][k]["error"]


def test_lint_error_gives_unknown(history, tmp_path):
    """A key whose events the lint refuses (an orphan completion, which
    the encoding drops, so the batch still finds the key invalid) is
    "unknown", as the sub-checker's own check makes it."""
    k = STALE[0]
    orphan = ok_op(99, "read", KV(k, 1))
    h = history[:10] + [orphan] + history[10:]
    sub = Linearizable(cas_register(), algorithm="device")
    r, spans = _check(independent.checker(sub), h, tmp_path)
    assert r["results"][k]["valid"] == "unknown"
    assert "H002" in r["results"][k]["error"]
    assert r["results"][STALE[1]]["valid"] is False
    fallback = _key_spans(spans)[k]
    assert fallback["args"]["why"] == "recheck"
    assert fallback["args"]["fallback"] == "lint"


@pytest.mark.parametrize("kw", [{"algorithm": "host"},
                                {"algorithm": "device", "decompose": True}],
                         ids=["host", "decompose"])
def test_no_fast_path_without_a_device_search(history, kw, tmp_path):
    """Where the sub-checker's own check runs no device search to skip,
    a batch-invalid key gets that check, as before."""
    sub = Linearizable(cas_register(), **kw)
    assert not sub.confirms_batch_verdict({"valid": False, "window": 4})
    r, spans = _check(independent.checker(sub), history, tmp_path)
    assert sorted(r["failures"]) == list(STALE)
    assert not [s for s in spans if s["name"] == "independent.key"
                and s["args"]["why"] == "confirm"]
    ks = _key_spans(spans)
    for k in STALE:
        assert ks[k]["args"] == {"key": k, "rows": ks[k]["args"]["rows"],
                                 "why": "recheck"}
        got, want = r["results"][k], _solo(sub, history, k, tmp_path)
        assert got["valid"] is want["valid"] is False
        assert got.get("max_depth") == want.get("max_depth")
        assert _final_paths(got) == _final_paths(want)


def test_no_fast_path_for_a_host_verdict(history, monkeypatch, tmp_path):
    """A batch verdict that no device search gave (the prepass's, a host
    engine's: it carries no window) gets the solo check, which decides
    it without a device search to skip."""
    _edit_batch(monkeypatch, lambda r: r.pop("window"))
    sub = Linearizable(cas_register(), algorithm="device")
    assert sub.confirms_batch_verdict({"valid": False, "window": 4})
    r, spans = _check(independent.checker(sub), history, tmp_path)
    assert sorted(r["failures"]) == list(STALE)
    ks = _key_spans(spans)
    for k in STALE:
        assert ks[k]["args"]["why"] == "recheck"
        assert "fallback" not in ks[k]["args"]
