"""Sequential linearizability oracle tests.

Histories mirror the reference's checker_test.clj style: hand-written
valid, invalid, and pathological cases, plus knossos's crashed-op
semantics (:info ops may linearize at any later point, or never).
"""

import random
from dataclasses import replace

import pytest

from jepsen_tpu.analyze.audit import audit
from jepsen_tpu.history import (
    INF_RET, encode_ops, fail_op, info_op, invoke_op, ok_op,
)
from jepsen_tpu.checker.seq import check_opseq
from jepsen_tpu.models import cas_register, mutex, register


def check(model, *ops):
    seq = encode_ops(list(ops), model.f_codes)
    return check_opseq(seq, model)


def test_empty_history_valid():
    r = check(register(0))
    assert r["valid"] is True


def test_sequential_read_write_valid():
    r = check(
        register(0),
        invoke_op(0, "write", 1), ok_op(0, "write", 1),
        invoke_op(0, "read"), ok_op(0, "read", 1),
    )
    assert r["valid"] is True
    assert r["linearization"] == [0, 1]


def test_stale_read_invalid():
    r = check(
        register(0),
        invoke_op(0, "write", 1), ok_op(0, "write", 1),
        invoke_op(0, "read"), ok_op(0, "read", 0),  # saw the old value
    )
    assert r["valid"] is False


def test_concurrent_reads_may_reorder():
    # write(1) overlaps two reads: one sees 0, one sees 1 — both orders
    # exist, so valid.
    r = check(
        register(0),
        invoke_op(0, "write", 1),
        invoke_op(1, "read"), ok_op(1, "read", 0),
        invoke_op(2, "read"), ok_op(2, "read", 1),
        ok_op(0, "write", 1),
    )
    assert r["valid"] is True


def test_read_before_overlap_must_see_old():
    # read completes before write invokes -> must see 0
    r = check(
        register(0),
        invoke_op(1, "read"), ok_op(1, "read", 1),
        invoke_op(0, "write", 1), ok_op(0, "write", 1),
    )
    assert r["valid"] is False


def test_cas_register_valid_chain():
    r = check(
        cas_register(0),
        invoke_op(0, "cas", (0, 2)), ok_op(0, "cas", (0, 2)),
        invoke_op(1, "cas", (2, 3)), ok_op(1, "cas", (2, 3)),
        invoke_op(0, "read"), ok_op(0, "read", 3),
    )
    assert r["valid"] is True


def test_cas_from_wrong_value_invalid():
    r = check(
        cas_register(0),
        invoke_op(0, "cas", (5, 2)), ok_op(0, "cas", (5, 2)),
    )
    assert r["valid"] is False


def test_failed_op_did_not_happen():
    r = check(
        register(0),
        invoke_op(0, "write", 1), fail_op(0, "write", 1),
        invoke_op(0, "read"), ok_op(0, "read", 0),
    )
    assert r["valid"] is True


def test_info_op_may_have_happened():
    # crashed write(1); later read sees 1 -> valid only if the crashed
    # write is allowed to have taken effect
    r = check(
        register(0),
        invoke_op(0, "write", 1), info_op(0, "write", 1),
        invoke_op(1, "read"), ok_op(1, "read", 1),
    )
    assert r["valid"] is True


def test_info_op_may_not_have_happened():
    r = check(
        register(0),
        invoke_op(0, "write", 1), info_op(0, "write", 1),
        invoke_op(1, "read"), ok_op(1, "read", 0),
    )
    assert r["valid"] is True


def test_info_op_takes_effect_late():
    # crashed write(1) invoked FIRST; reads see 0, 0, then 1: the crashed
    # op may linearize arbitrarily late (knossos crashed-op semantics).
    r = check(
        register(0),
        invoke_op(0, "write", 1), info_op(0, "write", 1),
        invoke_op(1, "read"), ok_op(1, "read", 0),
        invoke_op(1, "read"), ok_op(1, "read", 1),
        invoke_op(1, "read"), ok_op(1, "read", 1),
    )
    assert r["valid"] is True


def test_info_cannot_unhappen():
    # 0 -> 1 -> 0 with only one crashed write(1): the final read of 0 is
    # impossible once 1 was observed (no op writes 0 again).
    r = check(
        register(0),
        invoke_op(0, "write", 1), info_op(0, "write", 1),
        invoke_op(1, "read"), ok_op(1, "read", 1),
        invoke_op(1, "read"), ok_op(1, "read", 0),
    )
    assert r["valid"] is False


def test_mutex_valid():
    r = check(
        mutex(),
        invoke_op(0, "acquire"), ok_op(0, "acquire"),
        invoke_op(0, "release"), ok_op(0, "release"),
        invoke_op(1, "acquire"), ok_op(1, "acquire"),
    )
    assert r["valid"] is True


def test_mutex_double_acquire_invalid():
    r = check(
        mutex(),
        invoke_op(0, "acquire"), ok_op(0, "acquire"),
        invoke_op(1, "acquire"), ok_op(1, "acquire"),
    )
    assert r["valid"] is False


def test_mutex_concurrent_handoff_valid():
    # release overlaps the second acquire -> legal interleaving exists
    r = check(
        mutex(),
        invoke_op(0, "acquire"), ok_op(0, "acquire"),
        invoke_op(0, "release"),
        invoke_op(1, "acquire"), ok_op(1, "acquire"),
        ok_op(0, "release"),
    )
    assert r["valid"] is True


def test_unknown_on_config_explosion():
    # tiny cap forces the unknown path
    ops = []
    for i in range(8):
        ops.append(invoke_op(i, "write", i))
    for i in range(8):
        ops.append(info_op(i, "write", i))
    # an ok read forces the search to actually order the crashed writes
    ops += [invoke_op(8, "read"), ok_op(8, "read", 3)]
    seq = encode_ops(ops, register(0).f_codes)
    r = check_opseq(seq, register(0), max_configs=2)
    assert r["valid"] == "unknown"


def test_invalid_reports_final_ops():
    r = check(
        register(0),
        invoke_op(0, "write", 1), ok_op(0, "write", 1),
        invoke_op(0, "read"), ok_op(0, "read", 5),
    )
    assert r["valid"] is False
    assert r["final_ops"], "should report the stuck frontier ops"


def test_multi_register_read_through_encode():
    # regression: compound read values (key, nil) must be filled in from
    # the ok completion, or a read of a never-written value passes.
    from jepsen_tpu.models import multi_register
    m = multi_register(3)
    r = check(
        m,
        invoke_op(0, "write", (0, 5)), ok_op(0, "write", (0, 5)),
        invoke_op(1, "read", (0, None)), ok_op(1, "read", (0, 7)),
    )
    assert r["valid"] is False
    r2 = check(
        m,
        invoke_op(0, "write", (0, 5)), ok_op(0, "write", (0, 5)),
        invoke_op(1, "read", (0, None)), ok_op(1, "read", (0, 5)),
    )
    assert r2["valid"] is True


def test_invalid_at_depth_zero_reports_final_ops():
    r = check(register(0), invoke_op(0, "read"), ok_op(0, "read", 5))
    assert r["valid"] is False
    assert r["final_ops"] == [0]


# ---------------------------------------------------------------------------
# Candidate enumeration: the DFS finds a configuration's enabled ops among
# the unlinearized rows only.  These tests pin what it must return against
# naive recomputations over the whole history.
# ---------------------------------------------------------------------------


def _register_history(rng, n_ops, *, procs=5, overlap=5, crashes=2,
                      n_values=5, init=0):
    """A cas-register history, valid by construction: each op takes
    effect at its completion.  Exactly ``crashes`` ops crash (:info,
    taking effect with probability 1/2); a crashed worker goes on under
    a fresh process id, as a Jepsen worker thread does."""
    state = init
    h = []
    pending = {}
    pid = list(range(procs))
    crash_at = set(rng.sample(range(n_ops), crashes))
    done = 0
    while done < n_ops or pending:
        free = [w for w in range(procs) if w not in pending]
        if done < n_ops and free and len(pending) < overlap \
                and (not pending or rng.random() < 0.5):
            w = rng.choice(free)
            f = rng.choice(["read", "write", "cas"])
            v = (None if f == "read" else rng.randrange(n_values)
                 if f == "write" else
                 (rng.randrange(n_values), rng.randrange(n_values)))
            h.append(invoke_op(pid[w], f, v))
            pending[w] = (f, v, done in crash_at)
            done += 1
            continue
        w = rng.choice(list(pending))
        f, v, crashed = pending.pop(w)
        if crashed:
            if rng.random() < 0.5:
                if f == "write":
                    state = v
                elif f == "cas" and state == v[0]:
                    state = v[1]
            h.append(info_op(pid[w], f, v if f != "read" else None))
            pid[w] += procs
        elif f == "read":
            h.append(ok_op(pid[w], f, state))
        elif f == "write":
            state = v
            h.append(ok_op(pid[w], f, v))
        elif state == v[0]:
            state = v[1]
            h.append(ok_op(pid[w], f, v))
        else:
            h.append(fail_op(pid[w], f, v))
    return h


def _stale_read(h, at):
    """Rewrite the ok read nearest fraction ``at`` of the way through to
    a value an ok op left in the register before the read was invoked,
    which a barrier op (returned before the read was invoked) then
    overwrote with no op able to set it again in between: no
    linearization exists.  Returns (history, the read's event index),
    or None where no read qualifies."""
    ops, open_ = [], {}
    for i, op in enumerate(h):
        if op.type == "invoke":
            open_[op.process] = d = {"f": op.f, "value": op.value, "inv": i,
                                     "ret": float("inf"), "type": "info"}
            ops.append(d)
        else:
            d = open_.pop(op.process)
            d["type"] = op.type
            if op.type == "ok":
                d["ret"] = i
                d["value"] = op.value

    def sets(d):
        if d["type"] == "fail" or d["f"] == "read":
            return None
        return d["value"] if d["f"] == "write" else d["value"][1]

    target = int(at * (len(h) - 1))
    reads = sorted((d for d in ops if d["f"] == "read" and d["type"] == "ok"
                    and d["value"] is not None),
                   key=lambda d: (abs(d["inv"] - target), d["inv"]))
    for r in reads:
        before = [d for d in ops if d["type"] == "ok"
                  and d["ret"] < r["inv"] and sets(d) is not None]
        for v in sorted({sets(d) for d in before} - {r["value"]}):
            last_set = max(d["ret"] for d in ops
                           if sets(d) == v and d["inv"] < r["ret"])
            barrier = max((b["inv"] for b in before if sets(b) != v),
                          default=-1)
            if barrier > last_set:
                out = list(h)
                out[r["ret"]] = replace(out[r["ret"]], value=v)
                return out, r["inv"]
    return None


def _enabled(seq, lin: set, j: int) -> bool:
    """The module docstring's rule, over every row: j is unlinearized
    and no other unlinearized op returned before j was invoked."""
    return j not in lin and all(
        int(seq.inv[j]) < int(seq.ret[k])
        for k in range(len(seq)) if k != j and k not in lin)


def _naive_window(seq, lin: set) -> list[int]:
    """The unlinearized rows in index order, up to the first whose
    invocation is not below the least return among those before it."""
    cand, minret = [], INF_RET + 1
    for j in range(len(seq)):
        if j in lin:
            continue
        if int(seq.inv[j]) >= minret:
            break
        cand.append(j)
        minret = min(minret, int(seq.ret[j]))
    return cand


def _replays(seq, model, path) -> bool:
    """A partial linearization replays: every step is enabled by the
    docstring's rule and legal in the model."""
    lin: set = set()
    state = model.init
    for j in path:
        if not _enabled(seq, lin, j):
            return False
        state = model.pystep(state, int(seq.f[j]), int(seq.v1[j]),
                             int(seq.v2[j]))
        if state is None:
            return False
        lin.add(j)
    return True


def _assert_frontier(seq, model, r):
    """An invalid verdict's frontier is the candidate window of its
    first deepest configuration, and its final paths replay."""
    assert r["final_paths"], "an invalid DFS verdict reports final paths"
    for p in r["final_paths"]:
        assert len(p["linearized"]) == r["max_depth"]
        assert _replays(seq, model, p["linearized"])
    lin = set(r["final_paths"][0]["linearized"])
    window = r["final_ops"]
    assert window == _naive_window(seq, lin)
    # the (min, second-min) exclusion leaves exactly the enabled ops
    for j in window:
        excl = min((int(seq.ret[k]) for k in window if k != j),
                   default=INF_RET + 1)
        assert (int(seq.inv[j]) < excl) == _enabled(seq, lin, j)
    assert {j for j in range(len(seq)) if _enabled(seq, lin, j)} \
        <= set(window)


@pytest.mark.parametrize("pruned", [True, False], ids=["hb-dpor", "plain"])
@pytest.mark.parametrize("seed,n_ops", [(1, 30), (2, 60), (3, 120),
                                        (4, 220), (5, 260), (6, 300)])
def test_candidate_window_property(seed, n_ops, pruned):
    rng = random.Random(seed)
    model = cas_register(0)
    h = _register_history(rng, n_ops, crashes=1 + seed % 3)
    seq = encode_ops(h, model.f_codes)
    r = check_opseq(seq, model, hb=pruned, dpor=pruned)
    assert r["valid"] is True
    a = audit(seq, model, r)
    assert a["ok"] and a["checked"] == "linearization", a

    stale = _stale_read(h, 0.9)
    assert stale is not None
    sseq = encode_ops(stale[0], model.f_codes)
    r = check_opseq(sseq, model, hb=pruned, dpor=pruned)
    assert r["valid"] is False
    assert r["configs"] > 0
    _assert_frontier(sseq, model, r)


def test_deep_history_stale_read_regression():
    """A 5-process history of ~1,000 ops that may have taken effect
    (1,400 invocations, the failed cas ops dropped), 2 crashes and a
    stale read near its end: the search goes ~1,000 deep before it
    refutes."""
    rng = random.Random(7100000003)
    model = cas_register(0)
    while True:  # redrawn until the stale read lands in the last tenth
        h = _register_history(rng, 1400)
        stale = _stale_read(h, 0.98)
        if stale is not None and stale[1] > 0.9 * len(h):
            break
    h, read_inv = stale
    seq = encode_ops(h, model.f_codes)
    inv = [int(x) for x in seq.inv]
    ret = [int(x) for x in seq.ret]
    read = inv.index(read_inv)
    r = check_opseq(seq, model)
    assert r["valid"] is False
    # deepest prefix: at least every op that returned before the read
    # was invoked, at most every other op invoked before it returned
    must = sum(1 for k in range(len(seq)) if ret[k] < inv[read])
    may = sum(1 for k in range(len(seq)) if inv[k] < ret[read]) - 1
    assert must <= r["max_depth"] <= may
    assert len(seq) > 900 and r["max_depth"] > 0.9 * len(seq)
    _assert_frontier(seq, model, r)
    plain = check_opseq(seq, model, hb=False, dpor=False)
    assert plain["valid"] is False
    assert plain["max_depth"] == r["max_depth"]
