"""Static analysis (jepsen_tpu/analyze/): linter + plan explainer.

Three contracts:

  * every error code drives a fatal diagnostic through EVERY wired
    engine entry point (check_opseq, check_opseq_linear, Linearizable,
    search_batch, the decompose engine) — and ``lint=False`` /
    JEPSEN_TPU_LINT=0 restores the old permissive behavior;
  * differential fuzz: the linter NEVER alters a verdict on well-formed
    histories (>= 200 synthetic histories, :info ops included);
  * the plan explainer predicts the same SearchDims / bucket /
    decomposition choices the live engines make on the BENCH configs
    (explain output compared to recorded run stats).
"""

import os
import random
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from jepsen_tpu import synth  # noqa: E402
from jepsen_tpu.analyze import (  # noqa: E402
    HistoryLintError,
    analyze,
    explain,
    explain_batch,
    lint_history,
    lint_opseq,
)
from jepsen_tpu.analyze.lint import scan_events  # noqa: E402
from jepsen_tpu.checker.linear import check_opseq_linear  # noqa: E402
from jepsen_tpu.checker.linearizable import (  # noqa: E402
    Linearizable,
    search_batch,
    search_opseq,
)
from jepsen_tpu.checker.seq import check_opseq  # noqa: E402
from jepsen_tpu.decompose.engine import check_opseq_decomposed  # noqa: E402
from jepsen_tpu.history import (  # noqa: E402
    Op,
    complete,
    encode_ops,
    fail_op,
    invoke_op,
    ok_op,
    pair_index,
)
from jepsen_tpu.models import cas_register, multi_register, register  # noqa: E402


def codes(diags):
    return {d.code for d in diags}


def err_codes(diags):
    return {d.code for d in diags if d.severity == "error"}


# ---------------------------------------------------------------------------
# event-level linter: every code
# ---------------------------------------------------------------------------


def test_clean_history_no_diagnostics():
    rng = random.Random(11)
    h = synth.sim_register_history(rng, n_ops=60, crash_p=0.1)
    assert lint_history(h, cas_register()) == []


def test_nemesis_events_are_exempt():
    # the nemesis journals :info for both invocation and completion
    # (core.py NemesisWorker); that must not read as orphan completions
    h = [Op(process="nemesis", type="info", f="start"),
         Op(process="nemesis", type="info", f="start"),
         invoke_op(0, "write", 1), ok_op(0, "write", 1),
         Op(process="nemesis", type="info", f="stop"),
         Op(process="nemesis", type="info", f="stop")]
    assert lint_history(h, cas_register()) == []


def test_h001_double_invoke():
    h = [invoke_op(0, "write", 1), invoke_op(0, "write", 2),
         ok_op(0, "write", 2)]
    diags = lint_history(h)
    assert err_codes(diags) == {"H001"}
    assert diags[0].index == 1 and diags[0].process == 0


def test_h002_orphan_completion():
    h = [invoke_op(0, "write", 1), ok_op(0, "write", 1),
         ok_op(0, "write", 1)]
    assert err_codes(lint_history(h)) == {"H002"}


def test_h003_bad_completion_type():
    h = [invoke_op(0, "write", 1),
         Op(process=0, type="oops", f="write", value=1)]
    diags = lint_history(h)
    assert "H003" in err_codes(diags)


def test_h004_nonmonotone_indices_warn_only():
    h = [Op(process=0, type="invoke", f="write", value=1, index=5),
         Op(process=0, type="ok", f="write", value=1, index=3)]
    diags = lint_history(h)
    assert codes(diags) == {"H004"}
    assert all(d.severity == "warning" for d in diags)


def test_h005_unencodable_value():
    h = [invoke_op(0, "write", [1, 2, 3]), ok_op(0, "write", [1, 2, 3])]
    assert "H005" in err_codes(lint_history(h))


def test_h005_skips_dropped_fail_rows():
    # encode_ops drops :fail rows before encoding their value; the lint
    # must mirror that (a defect on a dropped row is a non-event)
    h = [invoke_op(0, "write", [1, 2, 3]),
         fail_op(0, "write", [1, 2, 3])]
    assert lint_history(h, cas_register()) == []


def test_h006_value_drift_warning():
    h = [invoke_op(0, "write", 1), ok_op(0, "write", 2)]
    diags = lint_history(h)
    assert codes(diags) == {"H006"}
    assert all(d.severity == "warning" for d in diags)


def test_h006_nil_lane_refinement_is_clean():
    # multi-register reads invoke with (key, nil); the completion fills
    # the nil lane — complete()'s documented contract, not drift
    h = [invoke_op(0, "read", (3, None)), ok_op(0, "read", (3, 7))]
    assert lint_history(h, multi_register(8)) == []


def test_m001_unknown_f():
    h = [invoke_op(0, "frobnicate", 1), ok_op(0, "frobnicate", 1)]
    diags = lint_history(h, cas_register())
    assert "M001" in err_codes(diags)
    # without a model the check cannot run
    assert "M001" not in codes(lint_history(h))


def test_m001_skips_failed_rows():
    h = [invoke_op(0, "frobnicate", 1), fail_op(0, "frobnicate", 1)]
    assert lint_history(h, cas_register()) == []


def test_scan_facts():
    rng = random.Random(5)
    h = synth.register_history(rng, n_ops=50, n_procs=6, overlap=4,
                               crash_p=0.1, max_crashes=3)
    sc = scan_events(h, cas_register())
    assert sc.diagnostics == []
    assert sc.n_invoke == sum(1 for op in h if op.type == "invoke")
    assert sc.n_info == sum(1 for op in h if op.type == "info")
    assert sc.concurrency >= 1
    assert sc.pairs == pair_index(h)


# ---------------------------------------------------------------------------
# OpSeq-level linter
# ---------------------------------------------------------------------------


def _valid_seq(seed=3, n=40, crash_p=0.1):
    rng = random.Random(seed)
    h = synth.sim_register_history(rng, n_ops=n, crash_p=crash_p)
    return encode_ops(h, cas_register().f_codes)


def test_opseq_clean():
    assert lint_opseq(_valid_seq(), cas_register()) == []


def test_opseq_nonmonotone_inv():
    seq = _valid_seq()
    seq.inv = seq.inv[::-1].copy()
    assert "H004" in err_codes(lint_opseq(seq))


def test_opseq_ret_before_inv():
    seq = _valid_seq()
    seq.ret = np.asarray(seq.ret).copy()
    seq.ret[0] = int(seq.inv[0])  # returns at its own invocation rank
    assert "H004" in err_codes(lint_opseq(seq))


def test_opseq_ok_never_returns():
    from jepsen_tpu.history import INF_RET

    seq = _valid_seq()
    rows = np.nonzero(np.asarray(seq.ok))[0]
    seq.ret = np.asarray(seq.ret).copy()
    seq.ret[rows[0]] = INF_RET
    assert "H002" in err_codes(lint_opseq(seq))


def test_opseq_unknown_f_code():
    seq = _valid_seq()
    seq.f = np.asarray(seq.f).copy()
    seq.f[0] = 99
    assert "M001" in err_codes(lint_opseq(seq, cas_register()))


def test_opseq_column_shape_mismatch():
    seq = _valid_seq()
    seq.v1 = np.asarray(seq.v1)[:-1].copy()
    assert "H007" in err_codes(lint_opseq(seq))


# ---------------------------------------------------------------------------
# engine wiring: fatal on errors, off-switches honored
# ---------------------------------------------------------------------------

ENGINES = [
    pytest.param(lambda s, m: check_opseq(s, m), id="check_opseq"),
    pytest.param(lambda s, m: check_opseq_linear(s, m),
                 id="check_opseq_linear"),
    pytest.param(lambda s, m: search_opseq(s, m, budget=10_000),
                 id="search_opseq"),
    pytest.param(lambda s, m: search_batch([s], m, budget=10_000),
                 id="search_batch"),
    pytest.param(lambda s, m: check_opseq_decomposed(
        s, m, sub_max_configs=100_000), id="decompose"),
]

#: the off-switch variants run HOST engines only — the point is the
#: permissive contract, and a garbage encoding fed to the device BFS can
#: cost arbitrary search time (exactly why the linter exists)
ENGINES_OFF = [
    pytest.param(lambda s, m: check_opseq(s, m, max_configs=100_000,
                                          lint=False),
                 id="check_opseq"),
    pytest.param(lambda s, m: check_opseq_linear(
        s, m, max_configs=100_000, lint=False),
                 id="check_opseq_linear"),
    pytest.param(lambda s, m: check_opseq_decomposed(
        s, m, sub_max_configs=100_000, lint=False), id="decompose"),
]


def _malformed_seq():
    seq = _valid_seq(seed=9, n=12, crash_p=0.0)
    seq.inv = seq.inv[::-1].copy()
    return seq


@pytest.mark.parametrize("engine", ENGINES)
def test_engines_raise_on_malformed(engine):
    with pytest.raises(HistoryLintError) as ei:
        engine(_malformed_seq(), cas_register())
    assert any(d.code == "H004" for d in ei.value.diagnostics)


@pytest.mark.parametrize("engine", ENGINES_OFF)
def test_engines_permissive_with_lint_off(engine):
    # lint=False restores the seed's silent tolerance: the engine runs
    # (whatever it concludes) instead of raising
    out = engine(_malformed_seq(), cas_register())
    if isinstance(out, list):
        out = out[0]
    assert out["valid"] in (True, False, "unknown")


def test_device_engines_permissive_with_lint_off():
    # a mildly-corrupted seq (ok row that never returns — H002 at the
    # opseq level) stays cheap to search, so the device entries can
    # demonstrate the same off-switch without unbounded work
    from jepsen_tpu.history import INF_RET

    seq = _valid_seq(seed=13, n=12, crash_p=0.0)
    rows = np.nonzero(np.asarray(seq.ok))[0]
    seq.ret = np.asarray(seq.ret).copy()
    seq.ret[rows[-1]] = INF_RET
    with pytest.raises(HistoryLintError):
        search_opseq(seq, cas_register(), budget=10_000)
    r1 = search_opseq(seq, cas_register(), budget=100_000, lint=False)
    r2 = search_batch([seq], cas_register(), budget=100_000,
                      lint=False)[0]
    assert r1["valid"] in (True, False, "unknown")
    assert r2["valid"] in (True, False, "unknown")


def test_env_knob_disables_lint(monkeypatch):
    monkeypatch.setenv("JEPSEN_TPU_LINT", "0")
    out = check_opseq(_malformed_seq(), cas_register())
    assert out["valid"] in (True, False, "unknown")


def test_search_batch_names_offending_key():
    good = _valid_seq(seed=1, n=20, crash_p=0.0)
    with pytest.raises(HistoryLintError) as ei:
        search_batch([good, _malformed_seq()], cas_register(),
                     budget=10_000)
    errs = [d for d in ei.value.diagnostics if d.severity == "error"]
    assert all("batch key 1" in d.message for d in errs)


def test_linearizable_raises_on_event_level_defects():
    chk = Linearizable(cas_register(), algorithm="linear")
    bad = [invoke_op(0, "write", 1), invoke_op(0, "write", 2),
           ok_op(0, "write", 2)]
    with pytest.raises(HistoryLintError):
        chk.check({"name": ""}, bad)
    # the per-checker off switch keeps the seed behavior
    out = Linearizable(cas_register(), algorithm="linear",
                       lint=False).check({"name": ""}, bad)
    assert out["valid"] in (True, False, "unknown")


def test_linearizable_surfaces_warnings():
    chk = Linearizable(cas_register(), algorithm="linear")
    h = [invoke_op(0, "write", 1), ok_op(0, "write", 2)]  # H006 drift
    out = chk.check({"name": ""}, h)
    assert out["valid"] in (True, False)
    warns = out.get("lint_warnings", [])
    assert any(w["code"] == "H006" for w in warns)


def test_check_safe_degrades_lint_error_to_unknown():
    # a malformed history inside a real run must degrade the composed
    # verdict to unknown (with the diagnostic), never crash the run
    from jepsen_tpu.checker.core import check_safe

    chk = Linearizable(cas_register(), algorithm="linear")
    bad = [invoke_op(0, "write", 1), ok_op(0, "write", 1),
           ok_op(0, "write", 1)]
    out = check_safe(chk, {"name": ""}, bad)
    assert out["valid"] == "unknown"
    assert "H002" in str(out.get("error", ""))


# ---------------------------------------------------------------------------
# strict pair_index / complete (satellite 1)
# ---------------------------------------------------------------------------


def test_pair_index_strict_and_permissive():
    dbl = [invoke_op(0, "write", 1), invoke_op(0, "write", 2),
           ok_op(0, "write", 2)]
    orphan = [ok_op(0, "write", 1)]
    # permissive (default): double-invoke overwrites, orphan dropped
    assert pair_index(dbl) == {1: 2, 2: 1}
    assert pair_index(orphan) == {}
    with pytest.raises(HistoryLintError) as ei:
        pair_index(dbl, strict=True)
    assert ei.value.diagnostics[0].code == "H001"
    with pytest.raises(HistoryLintError) as ei:
        pair_index(orphan, strict=True)
    assert ei.value.diagnostics[0].code == "H002"


def test_complete_strict_and_permissive():
    orphan = [invoke_op(0, "read", None), ok_op(0, "read", 5),
              ok_op(1, "read", 6)]
    done = complete(orphan)
    assert done[0].value == 5  # permissive fill-in still works
    with pytest.raises(HistoryLintError):
        complete(orphan, strict=True)
    # well-formed histories pass strict mode untouched
    rng = random.Random(2)
    h = synth.sim_register_history(rng, n_ops=30, crash_p=0.1)
    assert complete(h, strict=True) == complete(h)


# ---------------------------------------------------------------------------
# differential fuzz: lint never alters verdicts on well-formed histories
# ---------------------------------------------------------------------------


def _fuzz_histories(n=200):
    """Well-formed histories: valid and invalid, :info ops included,
    register + mutex + queue shapes."""
    out = []
    for i in range(n):
        rng = random.Random(1000 + i)
        kind = i % 4
        if kind == 0:
            h = synth.sim_register_history(rng, n_ops=30,
                                           crash_p=0.15)
            m = cas_register()
        elif kind == 1:
            h = synth.sim_register_history(rng, n_ops=30, crash_p=0.1)
            h = synth.flip_read(rng, h)  # (almost always) invalid
            m = cas_register()
        elif kind == 2:
            from jepsen_tpu.models import mutex

            h = synth.sim_mutex_history(rng, n_ops=24, crash_p=0.1)
            m = mutex()
        else:
            h = synth.register_history(rng, n_ops=30, n_procs=4,
                                       overlap=3, crash_p=0.1,
                                       max_crashes=4,
                                       unique_writes=True, cas=False)
            if i % 8 == 3:
                h = synth.swap_read_values(rng, h)
            m = register(0)
        out.append((h, m))
    return out


def test_differential_fuzz_linter_verdict_neutral():
    checked = 0
    for h, m in _fuzz_histories(200):
        seq = encode_ops(h, m.f_codes)
        assert lint_opseq(seq, m) == [], "fuzz history must be clean"
        on = check_opseq_linear(seq, m, lint=True)
        off = check_opseq_linear(seq, m, lint=False)
        assert on["valid"] == off["valid"]
        assert on["configs"] == off["configs"]
        checked += 1
    assert checked >= 200


def test_differential_fuzz_wgl_and_batch():
    # a slice of the corpus through the other engines (the linear sweep
    # above covers volume; these cover the wiring)
    corpus = _fuzz_histories(24)
    seqs, models = [], []
    for h, m in corpus:
        seq = encode_ops(h, m.f_codes)
        on = check_opseq(seq, m, max_configs=200_000, lint=True)
        off = check_opseq(seq, m, max_configs=200_000, lint=False)
        assert on["valid"] == off["valid"]
        if m.name == "cas-register":
            seqs.append(seq)
    on_b = search_batch(seqs, cas_register(), budget=300_000, lint=True)
    off_b = search_batch(seqs, cas_register(), budget=300_000,
                         lint=False)
    assert [r["valid"] for r in on_b] == [r["valid"] for r in off_b]


# ---------------------------------------------------------------------------
# plan explainer vs the live engines (BASELINE configs)
# ---------------------------------------------------------------------------


def test_explain_matches_engine_facts_bench_batch_key():
    """BASELINE config 3's key shape: the plan's window/concurrency/dims
    must equal what the device engine reports after actually running."""
    seq, model = synth.baseline_batch_key(0)
    plan = explain(seq, model)
    r = search_opseq(seq, model, budget=500_000)
    assert r["valid"] in (True, False)
    if "window" in r:  # device path reports its encoding facts
        assert plan["window"] == r["window"]
        assert plan["concurrency"] == r["concurrency"]
        assert plan["engine"] == "device-bfs"
    else:
        assert plan["engine"] == r["engine"]


def test_explain_engine_route_greedy_and_fallback():
    # greedy: a valid low-contention history is disposed host-side
    rng = random.Random(3)
    h = synth.register_history(rng, n_ops=60, n_procs=4, overlap=2,
                               crash_p=0.0)
    m = cas_register()
    seq = encode_ops(h, m.f_codes)
    plan = explain(seq, m)
    r = search_opseq(seq, m)
    assert plan["engine"] == r["engine"] == "greedy-witness"

    # fallback: crash count past MAX_CRASH forces the host sweep
    rng = random.Random(4)
    h2 = synth.register_history(rng, n_ops=400, n_procs=80, overlap=70,
                                crash_p=0.9, max_crashes=70)
    h2 = synth.corrupt_read(rng, h2, at=0.5)
    seq2 = encode_ops(h2, m.f_codes)
    from jepsen_tpu.checker.linearizable import MAX_CRASH, MAX_WINDOW

    es_facts = explain(seq2, m)
    if es_facts["n_crash"] > MAX_CRASH or es_facts["window"] > MAX_WINDOW:
        assert es_facts["engine"] == "host-linear(fallback)"
        assert not es_facts["device_eligible"]
        import time

        # the label (not the verdict) is what's under test, and it is
        # set on every exit path — a tight deadline keeps this cheap
        r2 = search_opseq(seq2, m, budget=50_000,
                          deadline=time.perf_counter() + 5.0)
        assert r2["engine"] in ("host-linear(fallback)",
                                "greedy-witness")


def test_explain_batch_matches_bucketed_run_stats():
    """The bucket plan (count + per-bucket dims + greedy/hard split)
    must equal the bucket_batch stats the live scheduler records."""
    seqs, model = synth.baseline_batch(12)
    # one wide outlier so bucketing has real work to do (kept modest:
    # this test is about PLAN equality, not search throughput)
    rng = random.Random(77)
    wide = synth.register_history(rng, n_ops=256, n_procs=16,
                                  overlap=12, crash_p=0.0)
    wide = synth.corrupt_read(rng, wide, at=0.9)
    seqs.append(encode_ops(wide, model.f_codes))

    plan = explain_batch(seqs, model)
    # small budget: the PLAN equality under test is decided host-side;
    # invalid keys may exhaust it ("unknown"), which costs nothing here
    results = search_batch(seqs, model, budget=50_000, bucket=True)
    stats = results[0].get("bucket_batch")
    assert stats is not None, "bucketed run must record stats"
    assert plan["n_keys"] == stats["n_keys"]
    assert plan["n_buckets"] == stats["n_buckets"]
    assert plan["greedy"] == stats["greedy"]
    assert plan["hard"] == stats["hard"]
    # per-bucket: same sizes and the same tight dims, in the same
    # largest-cost-first order
    assert [b["n_keys"] for b in plan["buckets"]] == \
        [b["n_keys"] for b in stats["buckets"]]
    assert [b["dims"] for b in plan["buckets"]] == \
        [b["dims"] for b in stats["buckets"]]
    assert [b["padding_efficiency"] for b in plan["buckets"]] == \
        [b["padding_efficiency"] for b in stats["buckets"]]


def test_explain_decompositions_match_engine_methods():
    m = register(0)
    # unique-writes, no quiescence pressure -> value blocks apply
    rng = random.Random(21)
    h = synth.register_history(rng, n_ops=80, n_procs=6, overlap=5,
                               crash_p=0.0, unique_writes=True,
                               cas=False)
    seq = encode_ops(h, m.f_codes)
    plan = explain(seq, m)
    assert plan["decompositions"]["value_blocks"]["applies"]
    r = check_opseq_decomposed(seq, m, sub_max_configs=500_000)
    assert "value-blocks" in r["decompose"]["methods"]

    # reused values + permanent overlap -> nothing applies, engine goes
    # direct (the 10k64 "applies: false" case, scaled down)
    rng = random.Random(22)
    h2 = synth.register_history(rng, n_ops=80, n_procs=8, overlap=8,
                                crash_p=0.0, n_values=3, cas=False)
    seq2 = encode_ops(h2, m.f_codes)
    plan2 = explain(seq2, m)
    assert not plan2["decompositions"]["value_blocks"]["applies"]
    r2 = check_opseq_decomposed(seq2, m, sub_max_configs=500_000)
    expect = {"direct", "sub-search"}
    if plan2["decompositions"]["quiescence"]["applies"]:
        expect.add("quiescence")
    assert set(r2["decompose"]["methods"]) <= expect | {"cache"}

    # quiescent history -> cuts predicted and used
    rng = random.Random(23)
    h3 = synth.register_history(rng, n_ops=40, n_procs=3, overlap=1,
                                crash_p=0.0, n_values=3, cas=False)
    seq3 = encode_ops(h3, m.f_codes)
    plan3 = explain(seq3, m)
    r3 = check_opseq_decomposed(seq3, m, sub_max_configs=500_000)
    if plan3["decompositions"]["quiescence"]["applies"]:
        assert r3["decompose"]["segments"] == \
            plan3["decompositions"]["quiescence"]["segments"]


def test_explain_multi_register_key_partition():
    m = multi_register(4)
    rng = random.Random(31)
    h = []
    for p in range(3):
        for i in range(6):
            k = rng.randrange(4)
            h.append(invoke_op(p, "write", (k, p * 100 + i)))
            h.append(ok_op(p, "write", (k, p * 100 + i)))
    seq = encode_ops(h, m.f_codes)
    plan = explain(seq, m)
    kp = plan["decompositions"]["key_partition"]
    assert kp["applies"]
    r = check_opseq_decomposed(seq, m, sub_max_configs=500_000)
    assert r["decompose"]["cells"] == kp["cells"]


# ---------------------------------------------------------------------------
# plan gates for the live families added since PR 7 (replicated,
# replicated-queue, pgwire) — regression pins so explain() routes them
# instead of falling through to defaults
# ---------------------------------------------------------------------------


def _v2_style_history(keyed=False, with_cas=False):
    """A replicated/pgwire-shaped history: cas_register(MISSING=-1)
    semantics — reads of a missing row return -1, unique writes."""
    h = [invoke_op(0, "read", (7, None) if keyed else -1),
         ok_op(0, "read", (7, -1) if keyed else -1),
         invoke_op(1, "write", (7, 5) if keyed else 5),
         ok_op(1, "write", (7, 5) if keyed else 5),
         invoke_op(0, "read", (7, 5) if keyed else 5),
         ok_op(0, "read", (7, 5) if keyed else 5),
         invoke_op(2, "write", (9, 8) if keyed else 8),
         ok_op(2, "write", (9, 8) if keyed else 8)]
    if with_cas:
        h += [invoke_op(1, "cas", (8, 11)), ok_op(1, "cas", (8, 11))]
    return h


def test_plan_routes_replicated_family():
    """cas_register(-1) — the replicated/pgwire model with MISSING
    reads.  Unique-writes all-:ok histories must hit the value-block
    AND hb decide-fast gates (not fall through to a raw search), and
    the prediction must match a real engine run."""
    m = cas_register(-1)
    seq = encode_ops(_v2_style_history(), m.f_codes)
    plan = explain(seq, m)
    assert not plan["independent"]["detected"]
    assert plan["decompositions"]["value_blocks"]["applies"]
    assert plan["hb"]["applies"]
    assert plan["hb"]["decided"] is True
    assert plan["hb"]["reason"] == "gk-interval"
    st = plan["streaming"]
    assert st["device_eligible"] is True  # register family state-pins
    r = check_opseq(seq, m)
    assert r["valid"] is True

    # cas rows take the history out of the unique-writes algebra: the
    # hb gate must say so (decide-fast off, canonical read-order only)
    seq2 = encode_ops(_v2_style_history(with_cas=True), m.f_codes)
    plan2 = explain(seq2, m)
    assert plan2["hb"]["decided"] is None
    assert "cas" in plan2["hb"]["reason"]
    assert plan2["hb"]["edges"]["rf"] == 0


def test_plan_routes_pgwire_independent_composite():
    """The pgwire/kv campaign records jepsen.independent [k v]
    histories; under the register model the whole-history plan used to
    mis-read key lanes as values.  explain() must flag the composite
    and name the per-key demux route."""
    m = cas_register(-1)
    seq = encode_ops(_v2_style_history(keyed=True), m.f_codes)
    plan = explain(seq, m)
    ind = plan["independent"]
    assert ind["detected"] is True
    assert ind["keys"] == 2  # keys 7 and 9
    assert "demux" in ind["route"]
    from jepsen_tpu.analyze.plan import render_plan

    assert "KEYED COMPOSITE" in render_plan(plan)
    # an un-keyed history must not trip the gate
    plain = explain(encode_ops(_v2_style_history(), m.f_codes), m)
    assert plain["independent"] == {"detected": False}


def test_plan_routes_replicated_queue_family():
    """unordered-queue (the replicated-queue/disque multiset model):
    every register-only gate must decline WITH a reason, the hb pass
    must report itself out of scope, and segment folds must never
    predict the device state-pinning route."""
    from jepsen_tpu.analyze.plan import segment_fold_route
    from jepsen_tpu.models import unordered_queue

    m = unordered_queue(8)
    h = []
    for i in range(4):
        h += [invoke_op(i % 2, "enqueue", i + 1),
              ok_op(i % 2, "enqueue", i + 1)]
    for i in range(4):
        h += [invoke_op(i % 2, "dequeue", i + 1),
              ok_op(i % 2, "dequeue", i + 1)]
    seq = encode_ops(h, m.f_codes)
    plan = explain(seq, m)
    dec = plan["decompositions"]
    assert dec["key_partition"]["applies"] is False
    assert "multi-register" in dec["key_partition"]["reason"]
    assert dec["value_blocks"]["applies"] is False
    assert "single register" in dec["value_blocks"]["reason"]
    assert plan["hb"]["applies"] is False
    assert "out of scope" in plan["hb"]["reason"]
    st = plan["streaming"]
    assert st["device_eligible"] is False
    assert st["routes"]["device"] == 0
    # the fold router must pin queue folds to host at ANY size: the
    # pseudo-op state pinning trick needs a single-value register
    assert segment_fold_route(10_000, 40, m) == "host"
    assert segment_fold_route(10_000, 40, m, host_fold_max=0) == "host"
    r = check_opseq(seq, m)
    assert r["valid"] is True


def test_analyze_end_to_end_and_render():
    from jepsen_tpu.analyze.plan import render_plan

    rng = random.Random(41)
    h = synth.sim_register_history(rng, n_ops=40, crash_p=0.1)
    rep = analyze(h, cas_register())
    assert rep["errors"] == 0
    assert rep["plan"] is not None
    text = render_plan(rep["plan"])
    assert "SearchDims" in text and "decompositions" in text
    # malformed history: no plan, errors reported
    bad = [invoke_op(0, "write", 1), invoke_op(0, "write", 2),
           ok_op(0, "write", 2)]
    rep2 = analyze(bad, cas_register())
    assert rep2["errors"] >= 1 and rep2["plan"] is None


def test_analyze_cli_module(tmp_path):
    from jepsen_tpu import store
    from jepsen_tpu.analyze.__main__ import main

    rng = random.Random(51)
    h = synth.sim_register_history(rng, n_ops=30, crash_p=0.1)
    p = tmp_path / "history.jsonl"
    import json

    with open(p, "w") as f:
        for op in h:
            f.write(json.dumps(op.to_dict()) + "\n")
    assert store.read_history(str(p))  # format sanity
    assert main([str(p), "--model", "cas-register", "--explain"]) == 0
    assert main([str(p), "--json"]) == 0
    # lint errors exit 1
    bad = tmp_path / "bad.jsonl"
    with open(bad, "w") as f:
        f.write(json.dumps({"process": 0, "type": "ok", "f": "write",
                            "value": 1}) + "\n")
    assert main([str(bad)]) == 1


# ---------------------------------------------------------------------------
# Q-codes: the queue-history lint family (analyze/lint.py + the
# multiset checkers' on-by-default wiring in checker/basic.py)
# ---------------------------------------------------------------------------


def _qops(*specs):
    from jepsen_tpu.history import info_op, invoke_op, ok_op

    mk = {"invoke": invoke_op, "ok": ok_op, "info": info_op}
    return [mk[t](p, f, v) for t, p, f, v in specs]


def test_q001_ack_without_claim_is_an_error():
    h = _qops(("invoke", 0, "enqueue", 1), ("ok", 0, "enqueue", 1),
              ("invoke", 1, "ack", 1), ("ok", 1, "ack", 1))
    codes = [d.code for d in scan_events(h).diagnostics]
    assert "Q001" in codes
    # wired on by default: the multiset checker raises
    from jepsen_tpu.analyze.lint import HistoryLintError
    from jepsen_tpu.checker import basic

    with pytest.raises(HistoryLintError):
        basic.total_queue().check({}, h)


def test_q002_double_ack_is_an_error():
    h = _qops(("invoke", 0, "enqueue", 1), ("ok", 0, "enqueue", 1),
              ("invoke", 1, "dequeue", None), ("ok", 1, "dequeue", 1),
              ("invoke", 1, "ack", 1), ("ok", 1, "ack", 1),
              ("invoke", 1, "ack", 1), ("ok", 1, "ack", 1))
    diags = scan_events(h).diagnostics
    assert [d.code for d in diags].count("Q002") == 1
    assert all(d.code != "Q001" for d in diags)  # claimed first: legal


def test_q003_unexpected_dequeue_warns_but_checker_judges():
    from jepsen_tpu.checker import basic

    h = _qops(("invoke", 1, "dequeue", None), ("ok", 1, "dequeue", 7))
    diags = scan_events(h).diagnostics
    q3 = [d for d in diags if d.code == "Q003"]
    assert q3 and q3[0].severity == "warning"
    # the checker still returns its own verdict, warnings attached
    out = basic.total_queue().check({}, h)
    assert out["valid"] is False
    assert any(d["code"] == "Q003" for d in out["lint_warnings"])
    out2 = basic.queue().check({}, h)
    assert out2["valid"] is False
    assert any(d["code"] == "Q003" for d in out2["lint_warnings"])


def test_q003_drained_element_never_enqueued_warns():
    h = _qops(("invoke", 0, "drain", None), ("ok", 0, "drain", [5]))
    codes = [d.code for d in scan_events(h).diagnostics]
    assert "Q003" in codes


def test_q_codes_respect_the_lint_knob(monkeypatch):
    from jepsen_tpu.checker import basic

    h = _qops(("invoke", 1, "ack", 9), ("ok", 1, "ack", 9))
    monkeypatch.setenv("JEPSEN_TPU_LINT", "0")
    out = basic.total_queue().check({}, h)  # must not raise
    assert "lint_warnings" not in out


def test_clean_queue_history_has_no_q_codes():
    h = _qops(("invoke", 0, "enqueue", 1), ("ok", 0, "enqueue", 1),
              ("invoke", 1, "dequeue", None), ("ok", 1, "dequeue", 1),
              ("invoke", 1, "ack", 1), ("ok", 1, "ack", 1),
              ("invoke", 2, "drain", None), ("ok", 2, "drain", []))
    assert not [d for d in scan_events(h).diagnostics
                if d.code.startswith("Q")]
    from jepsen_tpu.checker import basic

    assert "lint_warnings" not in basic.total_queue().check({}, h)


def test_q_codes_documented():
    from jepsen_tpu.analyze.lint import ERROR_CODES, QUEUE_CODES

    for code in QUEUE_CODES:
        assert code in ERROR_CODES
