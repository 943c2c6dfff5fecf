"""Differential tests: device frontier search vs the exact host oracle.

The reference establishes confidence in its checker by racing two knossos
algorithms (`competition`, jepsen/src/jepsen/checker.clj:122-126); here we
run the vectorized device engine and the host DFS on the same random
histories and require identical verdicts.  Histories come from a
simulator that is valid-by-construction (ops take effect at their
completion — a legal linearization point), plus corrupted and
crash-heavy variants that are frequently invalid.
"""

import random

import jax

import pytest

from jepsen_tpu.history import (
    encode_ops, fail_op, info_op, invoke_op, ok_op,
)
from jepsen_tpu.checker import seq as oracle
from jepsen_tpu.checker import linearizable as lin
from jepsen_tpu.models import cas_register, mutex, register

# Shared generous dims so all differential cases reuse one compiled kernel.
DIMS = lin.SearchDims(n_det_pad=128, n_crash_pad=32, window=96, k=16,
                      state_width=1, frontier=256)


def random_register_history(rng: random.Random, n_procs=4, n_ops=40, *,
                            crash_p=0.0, cas=True):
    """Simulate processes against a real register (canonical simulator:
    jepsen_tpu/synth.py; shared with tools/fuzz.py)."""
    from jepsen_tpu.synth import sim_register_history

    return sim_register_history(rng, n_procs, n_ops, crash_p=crash_p,
                                cas=cas, max_crashes=8)


def corrupt(rng: random.Random, h):
    """Flip one ok read's value (canonical: synth.flip_read)."""
    from jepsen_tpu.synth import flip_read

    return flip_read(rng, h)


def both_verdicts(h, model):
    s = encode_ops(h, model.f_codes)
    a = oracle.check_opseq(s, model)
    es = lin.encode_search(s)
    assert es.window <= DIMS.window, "test dims too small"
    assert es.concurrency <= DIMS.k, "test dims too small"
    b = lin.search_opseq(s, model, dims=DIMS)
    return a, b


@pytest.mark.parametrize("seed", range(12))
def test_differential_valid_histories(seed):
    rng = random.Random(seed)
    h = random_register_history(rng, n_procs=4, n_ops=40)
    a, b = both_verdicts(h, cas_register())
    assert a["valid"] is True, f"simulator produced invalid history? {a}"
    assert b["valid"] is True, f"device disagrees: {b}"


@pytest.mark.parametrize("seed", range(12))
def test_differential_corrupted_histories(seed):
    rng = random.Random(1000 + seed)
    h = corrupt(rng, random_register_history(rng, n_procs=4, n_ops=40))
    a, b = both_verdicts(h, cas_register())
    assert a["valid"] in (True, False)
    assert b["valid"] == a["valid"], f"oracle={a} device={b}"


@pytest.mark.parametrize("seed", range(12))
def test_differential_crashy_histories(seed):
    rng = random.Random(2000 + seed)
    h = random_register_history(rng, n_procs=4, n_ops=30, crash_p=0.25)
    a, b = both_verdicts(h, cas_register())
    assert a["valid"] is True, f"simulator produced invalid history? {a}"
    assert b["valid"] is True, f"device disagrees: {b}"


@pytest.mark.parametrize("seed", range(8))
def test_differential_crashy_corrupted(seed):
    rng = random.Random(3000 + seed)
    h = corrupt(rng, random_register_history(rng, n_procs=4, n_ops=30,
                                             crash_p=0.25))
    a, b = both_verdicts(h, cas_register())
    assert b["valid"] == a["valid"], f"oracle={a} device={b}"


def test_mutex_history():
    # hazelcast-style lock workload (hazelcast.clj:379-386): acquire and
    # release must alternate globally.
    m = mutex()
    h = [invoke_op(0, "acquire", None), ok_op(0, "acquire", None),
         invoke_op(1, "acquire", None),  # blocks...
         invoke_op(0, "release", None), ok_op(0, "release", None),
         ok_op(1, "acquire", None),
         invoke_op(1, "release", None), ok_op(1, "release", None)]
    a = oracle.check_opseq(encode_ops(h, m.f_codes), m)
    assert a["valid"] is True
    s = encode_ops(h, m.f_codes)
    b = lin.search_opseq(s, m, dims=lin.SearchDims(
        n_det_pad=64, n_crash_pad=32, window=32, k=4, state_width=1,
        frontier=64))
    assert b["valid"] is True

    # double acquire with no release: invalid
    h2 = [invoke_op(0, "acquire", None), ok_op(0, "acquire", None),
          invoke_op(1, "acquire", None), ok_op(1, "acquire", None)]
    s2 = encode_ops(h2, m.f_codes)
    assert oracle.check_opseq(s2, m)["valid"] is False
    b2 = lin.search_opseq(s2, m, dims=lin.SearchDims(
        n_det_pad=64, n_crash_pad=32, window=32, k=4, state_width=1,
        frontier=64))
    assert b2["valid"] is False


def test_checker_wrapper_small_and_large():
    rng = random.Random(7)
    model = cas_register()
    chk = lin.linearizable(model, host_threshold=10)
    h = random_register_history(rng, n_procs=4, n_ops=6)
    out = chk.check({}, h)
    assert out["valid"] is True and out["engine"] == "host-oracle"

    h2 = random_register_history(rng, n_procs=4, n_ops=60)
    out2 = chk.check({}, h2)
    assert out2["valid"] is True

    h3 = corrupt(rng, h2)
    out3 = chk.check({}, h3)
    ref = oracle.check_opseq(encode_ops(h3, model.f_codes), model)
    assert out3["valid"] == ref["valid"]
    if out3["valid"] is False:
        # invalid verdicts come back host-confirmed with a witness frontier
        assert "final_ops" in out3


def test_larger_history_smoke():
    rng = random.Random(99)
    h = random_register_history(rng, n_procs=8, n_ops=300)
    model = cas_register()
    s = encode_ops(h, model.f_codes)
    out = lin.search_opseq(s, model)
    assert out["valid"] is True


def test_truncate_to_failure_soundness():
    """The witness prefix must agree with the full-history verdict on
    corrupted histories (the cut is closed, so prefix-invalid implies
    full-invalid)."""
    model = cas_register()
    for seed in range(6):
        rng = random.Random(400 + seed)
        from jepsen_tpu.synth import corrupt_read, register_history

        h = register_history(rng, n_ops=200, n_procs=6, overlap=3,
                             crash_p=0.02)
        h = corrupt_read(rng, h, at=0.3)  # fail early: big truncation win
        s = encode_ops(h, model.f_codes)
        full = oracle.check_opseq(s, model)
        if full["valid"] is not False:
            continue
        out = lin.search_opseq(s, model)
        assert out["valid"] is False
        trunc = lin.truncate_to_failure(s, out["max_depth"], out["window"])
        if trunc is not None:
            assert len(trunc) < len(s)
            assert oracle.check_opseq(trunc, model)["valid"] is False


def test_wrapper_witness_prefix():
    from jepsen_tpu.synth import corrupt_read, register_history

    model = cas_register()
    rng = random.Random(77)
    h = register_history(rng, n_ops=300, n_procs=6, overlap=3)
    h = corrupt_read(rng, h, at=0.2)
    chk = lin.linearizable(model, host_threshold=10)
    out = chk.check({}, h)
    ref = oracle.check_opseq(encode_ops(h, model.f_codes), model)
    assert out["valid"] == ref["valid"]
    if out["valid"] is False and "witness_prefix_ops" in out:
        assert out["witness_prefix_ops"] < 300


def test_slicing_equivalence(monkeypatch):
    """Tiny slices (1 level per device call) must give the same verdict
    as big ones — the slice boundary is invisible to the search."""
    monkeypatch.setattr(lin, "_SLICE_LEVELS0", 1)
    monkeypatch.setattr(lin, "_adapt_lvl_cap", lambda cap, dt, **kw: cap)
    rng = random.Random(77)
    h = corrupt(rng, random_register_history(rng, n_procs=4, n_ops=40))
    model = cas_register()
    s = encode_ops(h, model.f_codes)
    a = oracle.check_opseq(s, model)
    slices = []
    b = lin.search_opseq(s, model, dims=DIMS,
                         on_slice=lambda c, d: slices.append(True))
    assert b["valid"] == a["valid"], f"oracle={a} device={b}"
    assert len(slices) > 1, "expected multiple 1-level slices"


def test_checkpoint_resume(tmp_path, monkeypatch):
    """Stop a search mid-flight, persist the carry, resume in a 'new'
    driver, and get the same verdict as an uninterrupted run."""
    monkeypatch.setattr(lin, "_SLICE_LEVELS0", 2)
    monkeypatch.setattr(lin, "_adapt_lvl_cap", lambda cap, dt, **kw: cap)
    rng = random.Random(78)
    h = corrupt(rng, random_register_history(rng, n_procs=4, n_ops=40))
    model = cas_register()
    s = encode_ops(h, model.f_codes)
    # hb=False: the static prepass would decide this corrupt history
    # outright with zero device slices — this test targets the
    # checkpoint machinery, which needs real slices to snapshot
    want = lin.search_opseq(s, model, dims=DIMS, hb=False)["valid"]

    ckpt = str(tmp_path / "search.npz")

    class Stop(Exception):
        pass

    n = [0]

    def save_then_stop(carry, dims):
        n[0] += 1
        lin.save_checkpoint(ckpt, carry, dims, model, budget=20_000_000,
                            seq=s)
        if n[0] >= 2:
            raise Stop

    try:
        lin.search_opseq(s, model, dims=DIMS, on_slice=save_then_stop,
                         hb=False)
    except Stop:
        pass
    carry, dims2, name, budget, digest, _pallas = \
        lin.load_checkpoint(ckpt)
    # the adaptive driver may have moved frontier width along the grid;
    # everything else must round-trip exactly
    assert {**dims2.__dict__, "frontier": 0} == \
        {**DIMS.__dict__, "frontier": 0}
    assert dims2.frontier == lin._grid_width(dims2.frontier)
    assert name == model.name
    assert digest == lin.history_digest(s, model)
    out = lin.resume_opseq(s, model, ckpt)
    assert out["valid"] == want
    assert out["engine"].startswith("device")

    # resuming against a different history must be refused
    h2 = corrupt(random.Random(99),
                 random_register_history(random.Random(99), n_procs=4,
                                         n_ops=40))
    s2 = encode_ops(h2, model.f_codes)
    with pytest.raises(ValueError, match="digest"):
        lin.resume_opseq(s2, model, ckpt)


@pytest.mark.parametrize("seed", range(6))
def test_escalation_resumes_not_restarts(seed, monkeypatch):
    """Force frontier overflow with a tiny initial frontier: the ladder
    must widen and RESUME from the pre-overflow carry, producing the
    oracle's verdict."""
    monkeypatch.setattr(lin, "_SLICE_LEVELS0", 4)
    monkeypatch.setattr(lin, "_adapt_lvl_cap", lambda cap, dt, **kw: cap)
    rng = random.Random(4000 + seed)
    h = corrupt(rng, random_register_history(rng, n_procs=4, n_ops=40))
    model = cas_register()
    s = encode_ops(h, model.f_codes)
    a = oracle.check_opseq(s, model)
    tiny = lin.SearchDims(n_det_pad=128, n_crash_pad=32, window=96,
                          k=16, state_width=1, frontier=8)
    b = lin.search_opseq(s, model, dims=tiny)
    assert b["valid"] == a["valid"], f"oracle={a} device={b}"
    # ladder must actually have escalated for a nontrivial search
    if a["configs"] > 64:
        assert b["frontier"] > 8, f"no escalation happened: {b}"


def test_fuzzer_smoke(monkeypatch):
    """tools/fuzz.py end to end: a handful of clean rounds, plus shrink
    on a hand-planted divergence stand-in (the shrinker must reduce a
    corrupted history to a small core that still diverges under a fake
    'engine')."""
    import os

    monkeypatch.syspath_prepend(
        os.path.join(os.path.dirname(__file__), "..", "tools"))
    import fuzz

    model = cas_register()
    for i in range(4):
        h = fuzz.gen_history(random.Random(i), "cas-register", 20, 3,
                             0.0)
        assert fuzz.diverges(h, model) is False

    # shrink with a stand-in divergence predicate ("oracle says
    # invalid") — exercises the pair-dropping logic without needing a
    # real engine bug.  Search a few seeds for an invalid corruption
    # rather than pinning one (randrange/choice sequences are not
    # guaranteed stable across CPython versions).
    from jepsen_tpu.history import encode_ops as enc

    def invalid(hh, m):
        try:
            s = enc(hh, m.f_codes)
        except Exception:
            return False
        return oracle.check_opseq(
            s, m, max_configs=fuzz.ORACLE_CAP)["valid"] is False

    h = None
    for seed in range(30):
        rng = random.Random(seed)
        cand = fuzz.corrupt(rng, fuzz.gen_history(rng, "cas-register",
                                                  30, 3, 0.0))
        if invalid(cand, model):
            h = cand
            break
    assert h is not None, "no invalid corruption in 30 seeds?!"
    monkeypatch.setattr(fuzz, "diverges", lambda hh, m: invalid(hh, m))
    small = fuzz.shrink(h, model)
    assert invalid(small, model)
    assert len(small) < len(h), "shrinker must actually reduce"
    assert len(small) <= 12, f"expected a small core, got {len(small)}"


# ---------------------------------------------------------------------------
# competition mode (checker.clj:122-126's :competition selector)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("bad", [False, True])
def test_competition_agrees_with_oracle(bad):
    rng = random.Random(31 if bad else 13)
    h = random_register_history(rng, n_procs=4, n_ops=60)
    if bad:
        h = corrupt(rng, h)
    model = cas_register()
    s = encode_ops(h, model.f_codes)
    want = oracle.check_opseq(s, model)["valid"]
    out = lin.check_competition(s, model)
    assert out["valid"] == want
    assert out["engine"].startswith("competition(")


def test_competition_host_wins_when_device_stalls(monkeypatch):
    """With a zero device budget the host oracle must carry the race."""
    rng = random.Random(5)
    h = corrupt(rng, random_register_history(rng, n_procs=4, n_ops=50))
    model = cas_register()
    s = encode_ops(h, model.f_codes)
    out = lin.check_competition(s, model, budget=1)
    assert out["valid"] is False
    assert out["engine"] in ("competition(host-wgl)",
                             "competition(host-linear)")


def test_linearizable_algorithm_selection():
    rng = random.Random(77)
    h = corrupt(rng, random_register_history(rng, n_procs=4, n_ops=60))
    model = cas_register()
    test = {"name": "alg", "start_time": 0}
    for alg in ("auto", "host", "wgl", "device", "linear", "competition"):
        chk = lin.linearizable(model, algorithm=alg)
        assert chk.check(test, h, {})["valid"] is False, alg
    with pytest.raises(ValueError):
        lin.linearizable(model, algorithm="quantum")


# ---------------------------------------------------------------------------
# unordered-queue model on device (sorted-array multiset encoding)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", range(8))
def test_differential_queue_histories(seed):
    from jepsen_tpu.models import unordered_queue
    from jepsen_tpu.synth import corrupt_dequeue, sim_queue_history

    rng = random.Random(500 + seed)
    h = sim_queue_history(rng, 30, 4,
                          crash_p=(0.1 if seed % 2 else 0.0))
    n_enq = sum(1 for o in h if o.f == "enqueue" and o.type == "invoke")
    # fixed capacity so every seed shares ONE compiled kernel (the cache
    # keys on model.name, which embeds capacity)
    model = unordered_queue(31)
    assert n_enq < 31
    s = encode_ops(h, model.f_codes)
    a = oracle.check_opseq(s, model)
    b = lin.search_opseq(s, model)
    assert a["valid"] is True, f"simulator produced invalid queue? {a}"
    assert b["valid"] is True, f"device disagrees: {b}"

    hb = corrupt_dequeue(random.Random(seed), h)
    if hb is not h:
        sb = encode_ops(hb, model.f_codes)
        ab = oracle.check_opseq(sb, model)
        bb = lin.search_opseq(sb, model)
        assert bb["valid"] == ab["valid"], f"oracle={ab} device={bb}"


def test_queue_duplicate_values_dedup():
    """Two enqueues of the same value: the multiset must hold both, and
    dequeuing it twice is legal while a third dequeue is not."""
    from jepsen_tpu.models import unordered_queue

    model = unordered_queue(4)
    h = [invoke_op(0, "enqueue", 7), ok_op(0, "enqueue", 7),
         invoke_op(0, "enqueue", 7), ok_op(0, "enqueue", 7),
         invoke_op(0, "dequeue", None), ok_op(0, "dequeue", 7),
         invoke_op(0, "dequeue", None), ok_op(0, "dequeue", 7)]
    s = encode_ops(h, model.f_codes)
    assert oracle.check_opseq(s, model)["valid"] is True
    assert lin.search_opseq(s, model)["valid"] is True

    h_bad = h + [invoke_op(0, "dequeue", None), ok_op(0, "dequeue", 7)]
    s_bad = encode_ops(h_bad, model.f_codes)
    assert oracle.check_opseq(s_bad, model)["valid"] is False
    assert lin.search_opseq(s_bad, model)["valid"] is False


def test_search_batch_mixed_difficulty_compaction():
    """Keys of very different sizes in one batch: the compacting driver
    must retire easy keys early and still return correct verdicts for
    every key in input order."""
    from jepsen_tpu.synth import corrupt_read, register_history

    model = cas_register()
    seqs, want = [], []
    for k in range(13):  # odd count: exercises grid padding
        rng = random.Random(9000 + k)
        n = 12 if k % 3 else 120  # most keys tiny, a few long-tail
        h = register_history(rng, n_ops=n, n_procs=4, overlap=3)
        if k % 2 == 0:
            h = corrupt_read(rng, h, at=0.7)
        s = encode_ops(h, model.f_codes)
        seqs.append(s)
        want.append(oracle.check_opseq(s, model)["valid"])
    # defeat the greedy-witness host path for valid keys? no — mixed
    # batches exercise exactly the production flow (greedy disposes of
    # well-behaved keys, the device batch gets the rest)
    got = lin.search_batch(seqs, model, budget=500_000)
    assert [r["valid"] for r in got] == want
    assert all(r["engine"] in
               ("device-batch", "device-batch(pallas)",
                "greedy-witness", "hb-decide", "device-bfs",
                "device-bfs(pallas)", "trivial")
               for r in got)
    # at least the corrupted keys must have ridden the device
    assert sum(r["engine"].startswith("device-batch")
               for r in got) >= 6


@pytest.mark.parametrize("seed", range(8))
def test_differential_fifo_queue_histories(seed):
    from jepsen_tpu.models import fifo_queue
    from jepsen_tpu.synth import sim_queue_history, swap_dequeues

    rng = random.Random(600 + seed)
    h = sim_queue_history(rng, 28, 4, fifo=True,
                          crash_p=(0.1 if seed % 2 else 0.0))
    model = fifo_queue(29)
    s = encode_ops(h, model.f_codes)
    a = oracle.check_opseq(s, model)
    b = lin.search_opseq(s, model)
    assert a["valid"] is True, f"simulator produced invalid fifo? {a}"
    assert b["valid"] is True, f"device disagrees: {b}"

    hb = swap_dequeues(random.Random(seed), h)
    if hb is not h:
        sb = encode_ops(hb, model.f_codes)
        ab = oracle.check_opseq(sb, model)
        bb = lin.search_opseq(sb, model)
        assert bb["valid"] == ab["valid"], f"oracle={ab} device={bb}"


def test_fifo_rejects_out_of_order_service():
    from jepsen_tpu.models import fifo_queue, unordered_queue

    h = [invoke_op(0, "enqueue", 1), ok_op(0, "enqueue", 1),
         invoke_op(0, "enqueue", 2), ok_op(0, "enqueue", 2),
         invoke_op(0, "dequeue", None), ok_op(0, "dequeue", 2),
         invoke_op(0, "dequeue", None), ok_op(0, "dequeue", 1)]
    fifo, uq = fifo_queue(4), unordered_queue(4)
    s_f = encode_ops(h, fifo.f_codes)
    s_u = encode_ops(h, uq.f_codes)
    # LIFO service order: fine for a multiset, fatal for FIFO
    assert oracle.check_opseq(s_u, uq)["valid"] is True
    assert lin.search_opseq(s_u, uq)["valid"] is True
    assert oracle.check_opseq(s_f, fifo)["valid"] is False
    assert lin.search_opseq(s_f, fifo)["valid"] is False


def test_width_floor_backend_policy(monkeypatch):
    """The narrowest rung is backend-dependent: 16 on CPU (narrow
    valleys are cheap there), 64 on TPU (the chip pads narrow shapes to
    its vector tiles while every rung costs a compile), env-overridable
    either way."""
    monkeypatch.setattr(lin, "_WIDTH_FLOOR", None)
    monkeypatch.delenv("JEPSEN_TPU_WIDTH_FLOOR", raising=False)
    assert lin._width_floor() == (
        64 if jax.default_backend() == "tpu" else 16)
    monkeypatch.setattr(lin, "_WIDTH_FLOOR", None)
    monkeypatch.setenv("JEPSEN_TPU_WIDTH_FLOOR", "128")
    assert lin._grid_width(1) == 128
    assert lin._grid_width(129) == 256
    # reset so later tests see the real policy
    monkeypatch.setattr(lin, "_WIDTH_FLOOR", None)
