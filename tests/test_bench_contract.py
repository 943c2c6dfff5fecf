"""Bench honesty contracts (VERDICT r3 weak #3 / item 6).

The benchmark's labels must not overstate the verified work: a tier
named "1k" must carry EXACTLY 1000 encoded ops, and the per-core batch
accounting must bill only workers that actually ran.

The in-process label/accounting contracts ride tier-1; the batch
tier's decomposed cold+warm pass runs under ``-m slow``.  bench.py
measures the chip only: a run that finds no TPU exits non-zero.
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import bench  # noqa: E402


@pytest.mark.parametrize("name,nominal", [("1k", 1_000), ("10k", 10_000)])
def test_register_tiers_encode_to_nominal(name, nominal):
    seq, _model = bench.make_seq(name)
    assert len(seq) == nominal


def test_mutex_tier_close_to_nominal():
    # the mutex generator's acquire-chain suffix makes exact hits rare;
    # the scan must land within 0.2% (the emitted metric string always
    # carries the actual count either way)
    seq, _model = bench.make_seq("mutex2k")
    assert abs(len(seq) - 2_000) <= 4


def test_tier_history_deterministic_across_processes():
    # children rebuild the identical history from the resolved nominal
    # (shared via BENCH_NOMINAL_* env)
    import numpy as np

    s1, _ = bench.make_seq("1k")
    bench._SEQ_CACHE.clear()
    s2, _ = bench.make_seq("1k")
    assert np.array_equal(s1.f, s2.f) and np.array_equal(s1.inv, s2.inv)


def test_batch_stats_per_core_math():
    res = {"n_keys": 256, "t_first": 9.9}
    host = {"batch256": {"host_pool": {
        "keys_done": 128, "n_keys": 256, "seconds": 4.0,
        "configs": 1, "n_procs": 2}}}
    s = bench.batch_stats(res, host, t_dev=2.0)
    # pool: 128 keys / 4s = 32 keys/s on 2 procs -> 16 keys/s/core
    assert s["host_pool_keys_per_sec"] == 32.0
    assert s["host_pool_keys_per_sec_per_core"] == 16.0
    # full pool time extrapolates to 8s for all 256 keys
    assert s["speedup_vs_host_pool"] == 4.0
    # device: 128 keys/s vs 16/core
    assert s["speedup_vs_host_pool_per_core"] == 8.0
    # 16-core extrapolation: 256/(16*16) = 1s vs 2s device
    assert s["vs_baseline"] == 0.5
    assert "EXTRAPOLATED" in s["vs_baseline_basis"]


def test_batch_stats_no_pool():
    s = bench.batch_stats({"n_keys": 4, "t_first": 1.0}, {}, t_dev=1.0)
    assert s["vs_baseline"] is None


def test_wide_tier_is_wide_and_near_nominal():
    # BASELINE config #5's 64-proc worst-case-frontier variant: the
    # encoding must actually be wide (the tier exists to stress big
    # levels) and close to its nominal size
    import jepsen_tpu.checker.linearizable as lin

    seq, model = bench.make_seq("10k64")
    assert abs(len(seq) - 10_000) <= 16
    es = lin.encode_search(seq)
    assert es.concurrency >= 24, es.concurrency
    assert es.window >= 128, es.window


def test_wide_tier_is_last_and_not_headline():
    # lowest priority: usually undecided; must never displace the 10k
    # headline or spend earlier tiers' budget
    names = [t[0] for t in bench.TIERS]
    assert names[-1] == "10k64"
    assert bench.TIERS[-1][4] is False


def test_uniq_tier_exercises_value_blocks():
    """ISSUE 2 satellite: the unique-writes wide tier must be exactly
    10k encoded ops, quiescence-free, and ELIGIBLE for the per-value
    block decomposition — so config 5's `applies: false` stops being
    the only decomposition data point at device scale."""
    from jepsen_tpu.decompose.partition import (quiescence_segments,
                                                value_block_verdict)

    seq, model = bench.make_seq("10kuniq")
    assert len(seq) == 10_000
    assert len(quiescence_segments(seq)) == 1  # no quiescent point
    vb = value_block_verdict(seq, model)
    assert vb in (True, False)  # the decomposition APPLIES
    d = bench._single_decomposed(seq, model, 1_000_000, vb, 1.0)
    assert d["applies"] is True
    assert d["valid"] == vb
    assert "value-blocks" in (d.get("methods") or [])
    # not the headline, and ordered before the 10k64 straggler
    names = [t[0] for t in bench.TIERS]
    assert names.index("10k") < names.index("10kuniq") \
        < names.index("10k64")
    spec = {t[0]: t for t in bench.TIERS}["10kuniq"]
    assert spec[4] is False


def test_batch_tier_runs_before_the_10k():
    # cheapest first: a budget cut during the 10k (the longest search)
    # must not cost the batch tier
    names = [t[0] for t in bench.TIERS]
    assert names.index("batch256") < names.index("10k")


def test_compact_emit_fits_driver_tail():
    """The emitted stdout line must stay under the driver's recorded
    tail (the full detail blob once blew through ~2000 chars and the
    parsed result came back null), keeping the headline and the
    device it ran on."""
    import json

    # a worst-case-ish full result: long basis strings, several tiers
    dev = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    full = {
        "metric": "ops-verified/sec, 10000-op 32-proc CAS-register "
                  "history, decided verdict (invalid), tpu backend",
        "value": 29.4, "unit": "ops/s", "vs_baseline": 0.07,
        "detail": {
            "device": dev, "backend": "tpu", "engine": "device-bfs",
            "device_verdict": False, "device_seconds": 339.8,
            "n_ops": 10000, "vs_baseline_basis": "EXTRAPOLATED: " + "x" * 300,
            "host_linear": {"valid": False, "seconds": 23.5,
                            "configs": 12_900_000, "failing_depth": 7388},
            **{f"tier_{n}": {"backend": "tpu", "device_verdict": False,
                             "device_seconds": 1.0, "junk": "z" * 500}
               for n in ("1k", "mutex2k", "10k64")},
            "batch256": {"backend": "tpu", "valid": "192 valid",
                         "device_seconds": 1.5, "junk": "z" * 500},
        },
    }
    c = bench._compact_result(full)
    s = json.dumps(c)
    assert len(s) <= bench._COMPACT_LIMIT, len(s)
    # headline fields and the device survive verbatim
    assert c["value"] == 29.4 and c["vs_baseline"] == 0.07
    assert c["detail"]["device"] == dev


def test_bench_without_a_chip_exits_nonzero():
    """No TPU, no measurement: bench.py refuses instead of timing the
    CPU under a device metric."""
    import subprocess

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, os.path.join(repo, "bench.py"), "--quick"],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert "found no TPU" in out.stderr
    assert not out.stdout.strip()


def test_wide_tier_host_comparator_always_present(monkeypatch):
    """VERDICT r4 weak #4: the 10k64 row must never ship comparator-
    free — host_linear runs under its own cap and reports seconds +
    configs even when undecided."""
    monkeypatch.setenv("BENCH_HOST_10K64_S", "5")
    monkeypatch.setattr(bench, "HOST_S", 0.1)  # starve the other tiers
    wide_spec = [t for t in bench.TIERS if t[0] == "10k64"]
    out = bench.host_comparators(wide_spec)
    row = out["10k64"]["host_linear"]
    assert row["seconds"] > 0
    assert row["configs"] > 0


@pytest.mark.slow
def test_batch_tier_reports_decomposed_cold_and_warm(tmp_path,
                                                     monkeypatch):
    """ISSUE 1 config 3 contract: the batch tier must report the
    decomposed-vs-direct comparison — cold pass filling the canonical-
    hash cache, warm pass serving every key from it, verdicts
    bit-identical to the direct engine.  Runs the tier in process
    (on the test's CPU platform; the label says so)."""
    make_batch = bench.make_batch
    monkeypatch.setattr(bench, "make_batch", lambda: make_batch(8))
    monkeypatch.setenv("BENCH_DECOMPOSE_CACHE",
                       str(tmp_path / "verdicts.jsonl"))
    j = bench.run_tier("batch256", 2_000_000, 120.0)
    assert j["backend"] == j["device"]["platform"] == "cpu"
    dec = j["decomposed"]
    assert dec["verdicts_agree"] is True
    assert dec["prior_cache_entries"] == 0
    assert dec["warm_hits"] == 8 and dec["warm_hit_rate"] == 1.0
    assert dec["t_warm"] > 0 and dec["t_cold"] > 0
    # the criterion's evidence fields exist and are numbers
    assert isinstance(dec["speedup_warm_vs_direct"], (int, float))
    # the cache file persisted (store.py-style jsonl)
    assert (tmp_path / "verdicts.jsonl").exists()


def test_single_decomposed_probe_is_honest_when_nothing_splits():
    """ISSUE 1 config 5 contract: when neither cutter fires (permanent
    in-flight overlap, non-unique writes), the report must say
    applies=False instead of re-running the direct engine under a
    'decomposed' label."""
    import random

    from jepsen_tpu.history import encode_ops
    from jepsen_tpu.models import cas_register
    from jepsen_tpu.synth import register_history

    rng = random.Random(1)
    m = cas_register()
    h = register_history(rng, n_ops=60, n_procs=8, overlap=8,
                         crash_p=0.0, n_values=4)
    seq = encode_ops(h, m.f_codes)
    d = bench._single_decomposed(seq, m, 1_000_000, False, 1.0)
    if d.get("applies") is False:
        assert d["segments"] == 1 and d["cells"] == 1
        assert "direct engine" in d["note"]
    else:
        # the generator happened to quiesce: then a real decomposed
        # verdict must have been produced and must agree
        assert d["valid"] in (True, False)
