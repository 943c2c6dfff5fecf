"""Shape-bucketed device batching (checker/bucket.py, ISSUE 2).

The scheduler's contract: bucketed `search_batch` is VERDICT-IDENTICAL
to the single fused batch on any mix of key shapes (sizes, :info crash
ops, duplicates, corruptions), while reporting strictly less padded
work on heterogeneous batches.  The satellites ride along: the full
per-cell result dicts from `device_batch_cells`, the pool's final
queue drain, the portfolio's decomposed leg, and the persistent
compilation-cache wiring (env knob + CLI flag).
"""

import os
import queue
import random
import threading

import pytest

from jepsen_tpu.checker import linearizable as lin
from jepsen_tpu.checker.bucket import (bucket_key, bucketing_enabled,
                                       plan_buckets,
                                       search_batch_bucketed)
from jepsen_tpu.history import encode_ops
from jepsen_tpu.models import cas_register
from jepsen_tpu.synth import (flip_read, register_history,
                              sim_register_history)


def _mixed_batch():
    """Mixed-size batch: narrow keys with :info crash ops, DUPLICATE
    keys (two copies per shape), medium keys, and one corrupted WIDE
    key that must ride the device (a valid wide key would be disposed
    of host-side by the greedy witness and never pad anything)."""
    m = cas_register()
    seqs = []
    for k in range(6):
        rng = random.Random(k % 3)
        h = sim_register_history(rng, n_procs=3, n_ops=18, crash_p=0.1)
        if k % 3 == 0:
            h = flip_read(random.Random(k), h)
        seqs.append(encode_ops(h, m.f_codes))
    for k in range(3):
        rng = random.Random(100 + k)
        h = register_history(rng, n_ops=64, n_procs=6, overlap=4,
                             crash_p=0.02, max_crashes=2, n_values=4)
        if k == 1:
            h = flip_read(rng, h)
        seqs.append(encode_ops(h, m.f_codes))
    rng = random.Random(999)
    h = register_history(rng, n_ops=200, n_procs=8, overlap=12,
                         crash_p=0.02, max_crashes=2, n_values=5)
    seqs.append(encode_ops(flip_read(rng, h), m.f_codes))
    return seqs, m


# ---------------------------------------------------------------------------
# differential parity: bucketed vs unbucketed
# ---------------------------------------------------------------------------


def test_differential_bucketed_vs_unbucketed_mixed_sizes():
    seqs, m = _mixed_batch()
    fused = lin.search_batch(seqs, m, budget=300_000, bucket=False)
    buck = lin.search_batch(seqs, m, budget=300_000, bucket=True,
                            audit=True)
    assert [r["valid"] for r in buck] == [r["valid"] for r in fused]
    # per-key accounting stays honest: every result names a real
    # engine, and device-ridden keys bill configs
    for r in buck:
        assert r.get("engine")
    # invalid keys exist in this batch (corruptions) and agree
    assert False in [r["valid"] for r in buck]
    # ISSUE 4: every per-key verdict is a certified one — greedy keys
    # carry real witnesses (surviving bucket padding/reordering: the
    # rows index each key's OWN OpSeq), device keys explicit drop
    # reasons — and the independent audit replays all of them clean
    from jepsen_tpu.analyze.audit import audit

    greedy_wit = 0
    for s, r in zip(seqs, buck):
        if r["valid"] is True:
            assert "linearization" in r or "witness_dropped" in r, r
        elif r["valid"] is False:
            assert "final_ops" in r or "frontier_dropped" in r, r
        assert audit(s, m, r)["ok"], r
        if r.get("engine") == "greedy-witness":
            assert r.get("linearization"), r
            greedy_wit += 1
    assert greedy_wit > 0


def test_differential_bucketed_vs_unbucketed_reordered():
    """Same keys, shuffled: verdicts follow the keys, not the order
    (the bucketed path scatters/gathers through bucket plans)."""
    seqs, m = _mixed_batch()
    rng = random.Random(7)
    perm = list(range(len(seqs)))
    rng.shuffle(perm)
    shuffled = [seqs[i] for i in perm]
    base = lin.search_batch(seqs, m, budget=300_000, bucket=False)
    buck = lin.search_batch(shuffled, m, budget=300_000, bucket=True)
    assert [buck[perm.index(i)]["valid"] for i in range(len(seqs))] == \
        [r["valid"] for r in base]


@pytest.mark.parametrize("route", ["bucketed", "fused", "sharded"])
def test_invalid_batch_verdict_carries_its_window(route):
    """Each invalid verdict of the device carries its key's own window,
    not the bucket's padded one, so `truncate_to_failure` cuts it where
    it cuts a solo search's."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    seqs, m = _mixed_batch()
    kw = {"bucket": route != "fused"}
    if route == "sharded":
        kw["sharding"] = NamedSharding(Mesh(np.array(jax.devices()),
                                            ("shard",)),
                                       PartitionSpec("shard"))
    out = lin.search_batch(seqs, m, budget=300_000, **kw)
    device = [(s, r) for s, r in zip(seqs, out)
              if r["valid"] is False and r["engine"].startswith("device")]
    assert len(device) >= 2
    for s, r in device:
        assert r["window"] == lin.encode_search(s).window
    # the padded widths differ from the keys' own
    assert {r["window"] for _s, r in device} - {32, 64}


def test_differential_fuzz_random_batches():
    """Randomized rounds: batch composition (sizes, corruption, crash
    ops, duplicate keys) varies per round; verdicts must match the
    fused path exactly every time.  Shapes draw from a small dims pool
    so compiled kernels cache across rounds."""
    m = cas_register()
    for round_ in range(3):
        rng = random.Random(7000 + round_)
        seqs = []
        for _ in range(rng.randrange(4, 9)):
            size = rng.choice([14, 18, 40, 64])
            seed = rng.randrange(4)
            h = sim_register_history(random.Random(seed), n_procs=3,
                                     n_ops=size, crash_p=0.08)
            if rng.random() < 0.4:
                h = flip_read(random.Random(seed + 50), h)
            seqs.append(encode_ops(h, m.f_codes))
        seqs += seqs[:2]  # duplicate keys
        fused = lin.search_batch(seqs, m, budget=200_000, bucket=False)
        buck = lin.search_batch(seqs, m, budget=200_000, bucket=True)
        assert [r["valid"] for r in buck] == \
            [r["valid"] for r in fused], f"round {round_}"


def test_bucketed_handles_all_greedy_and_empty():
    m = cas_register()
    rng = random.Random(3)
    h = register_history(rng, n_ops=24, n_procs=3, overlap=2,
                         n_values=3)
    seqs = [encode_ops(h, m.f_codes)] * 3  # valid: greedy disposes all
    out = search_batch_bucketed(seqs, m, budget=100_000)
    assert [r["valid"] for r in out] == [True] * 3
    assert all(r["engine"] == "greedy-witness" for r in out)
    assert search_batch_bucketed([], m) == []


# ---------------------------------------------------------------------------
# bucket planning
# ---------------------------------------------------------------------------


def test_wide_plus_narrow_lands_in_two_buckets():
    """ISSUE 2 satellite: a 1-wide-key + N-narrow-key batch must land
    in >= 2 buckets."""
    seqs, m = _mixed_batch()
    keys = [bucket_key(lin.encode_search(s)) for s in seqs]
    plans = plan_buckets(keys, 8)
    assert len(plans) >= 2
    out = search_batch_bucketed(seqs, m, budget=300_000)
    st = out[0]["bucket_batch"]
    assert st["n_buckets"] >= 2
    # the wide key's bucket pads to ITS dims, not the narrow keys'
    dims = [b["dims"] for b in st["buckets"] if b["dims"]]
    assert len({tuple(d) for d in dims}) >= 2


def test_plan_buckets_cap_merges_and_covers():
    keys = [(64, 32, 32), (128, 32, 32), (256, 64, 32), (512, 96, 64),
            (64, 64, 32), (1024, 32, 32), (64, 32, 32)]
    plans = plan_buckets(keys, 2)
    assert len(plans) == 2
    covered = sorted(i for grp in plans for i in grp)
    assert covered == list(range(len(keys)))
    # no cap: one bucket per distinct dims tuple
    assert len(plan_buckets(keys, 99)) == len(set(keys))


def test_bucket_key_matches_single_key_dims():
    seqs, m = _mixed_batch()
    for s in seqs:
        es = lin.encode_search(s)
        d = lin.choose_dims(es, m)
        assert bucket_key(es) == (d.n_det_pad, d.window, d.n_crash_pad)


def test_mixed_batch_padding_efficiency_beats_fused():
    """The acceptance criterion's shape: on a mixed-size batch the
    bucketed path reports strictly higher useful/padded than the
    single-fused-batch counterfactual."""
    seqs, m = _mixed_batch()
    out = search_batch_bucketed(seqs, m, budget=300_000)
    st = out[0]["bucket_batch"]
    assert st["padded_ops"] < st["fused_padded_ops"]
    assert st["padding_efficiency"] > st["fused_padding_efficiency"]
    assert "kernel_cache" in st and st["kernel_cache"]["misses"] >= 0


def test_env_knob_disables_bucketing(monkeypatch):
    monkeypatch.setenv("JEPSEN_TPU_BATCH_BUCKETS", "0")
    assert bucketing_enabled() is False
    seqs, m = _mixed_batch()
    out = lin.search_batch(seqs[:4], m, budget=100_000)
    assert all("bucket_batch" not in r for r in out)
    monkeypatch.setenv("JEPSEN_TPU_BATCH_BUCKETS", "4")
    assert bucketing_enabled() is True
    # "1" is a single fused bucket — counts as disabled
    monkeypatch.setenv("JEPSEN_TPU_BATCH_BUCKETS", "1")
    assert bucketing_enabled() is False


# ---------------------------------------------------------------------------
# scheduler satellites
# ---------------------------------------------------------------------------


def test_device_batch_cells_returns_full_dicts():
    from jepsen_tpu.decompose.schedule import device_batch_cells

    m = cas_register()
    cells = []
    for k in range(4):
        rng = random.Random(40 + k)
        h = sim_register_history(rng, n_procs=3, n_ops=16, crash_p=0.0)
        if k % 2 == 0:
            h = flip_read(rng, h)
        cells.append(encode_ops(h, m.f_codes))
    out = device_batch_cells(cells, m, budget=100_000)
    assert len(out) == 4
    for r in out:
        assert isinstance(r, dict)
        assert r["valid"] in (True, False, "unknown")
        assert "configs" in r and "engine" in r
    # verdicts agree with the direct oracle per cell
    from jepsen_tpu.checker.seq import check_opseq

    for cell, r in zip(cells, out):
        assert r["valid"] == check_opseq(cell, m)["valid"]


def test_pool_drain_collects_raced_verdicts():
    from jepsen_tpu.decompose.schedule import _drain_queue

    q: "queue.Queue" = queue.Queue()
    q.put((0, True, 10))
    q.put((2, False, 5))
    out: dict = {1: (True, 3)}
    _drain_queue(q, out)
    assert out == {0: (True, 10), 1: (True, 3), 2: (False, 5)}
    _drain_queue(q, out)  # empty queue: no-op
    assert out == {0: (True, 10), 1: (True, 3), 2: (False, 5)}


def _invalid_builder():
    # overlap=1: quiescence-rich, so the decomposed leg has a real cut
    # to work with (on an undecomposable history it now concedes
    # "unknown" instead of duplicating the linear leg)
    m = cas_register()
    rng = random.Random(5)
    h = register_history(rng, n_ops=60, n_procs=4, overlap=1, n_values=3)
    from jepsen_tpu.synth import corrupt_read

    h = corrupt_read(rng, h, at=0.7)
    return encode_ops(h, m.f_codes), m


def test_portfolio_worker_decompose_leg_runs_inprocess():
    """The new leg's worker path, driven directly (no spawn): the
    decomposed engine decides and labels the leg 'decompose'."""
    from jepsen_tpu.checker.parallel import _portfolio_worker

    ready, go = threading.Event(), threading.Event()
    go.set()
    q: "queue.Queue" = queue.Queue()
    _portfolio_worker(_invalid_builder, (), "decompose", 0, 1_000_000,
                      False, ready, go, q)
    algo, seed, r = q.get_nowait()
    assert algo == "decompose"
    assert r["valid"] is False
    assert r["engine"].startswith("decompose")


def test_portfolio_worker_decompose_leg_concedes_undecomposable():
    """No cutter applies (duplicate writes, no quiescent point, single
    register): the leg must concede "unknown" instead of duplicating
    the sibling linear leg's whole-history sweep."""
    from jepsen_tpu.checker.parallel import _portfolio_worker
    from jepsen_tpu.history import invoke_op, ok_op

    m = cas_register()
    h = [invoke_op(0, "write", 1), invoke_op(1, "write", 1),
         ok_op(0, "write", 1), invoke_op(2, "read", None),
         ok_op(1, "write", 1), invoke_op(0, "read", None),
         ok_op(2, "read", 1), ok_op(0, "read", 1)]
    ready, go = threading.Event(), threading.Event()
    go.set()
    q: "queue.Queue" = queue.Queue()
    _portfolio_worker(lambda: (encode_ops(h, m.f_codes), m), (),
                      "decompose", 0, 1_000_000, False, ready, go, q)
    _algo, _seed, r = q.get_nowait()
    assert r["valid"] == "unknown"
    assert r.get("info") == "nothing decomposes"


def test_linearizable_decompose_cache_object_memoized(tmp_path):
    """A path/True verdict_cache is constructed ONCE per checker —
    re-parsing the whole jsonl on every check() was O(n^2) across a
    suite run."""
    from jepsen_tpu.checker.linearizable import Linearizable

    m = cas_register()
    rng = random.Random(9)
    h = sim_register_history(rng, n_procs=3, n_ops=20)
    chk = Linearizable(m, algorithm="linear", decompose=True,
                       verdict_cache=str(tmp_path / "v.jsonl"))
    r1 = chk.check({"name": ""}, h)
    c1 = chk._cache_obj
    r2 = chk.check({"name": ""}, h)
    assert chk._cache_obj is c1
    assert r2["valid"] == r1["valid"]
    assert r2["decompose"]["cache_hits"] >= 1


def test_portfolio_races_decomposed_leg():
    """n_procs >= 3 adds the dedicated decomposed leg; the race still
    returns the right verdict whichever leg wins."""
    from jepsen_tpu.checker.parallel import portfolio_check

    out = portfolio_check(_invalid_builder, n_procs=3, deadline_s=120)
    assert out["valid"] is False
    assert out["engine"].startswith("host3(")


# ---------------------------------------------------------------------------
# compilation-cache wiring
# ---------------------------------------------------------------------------


def test_enable_compilation_cache(tmp_path, monkeypatch):
    """One placement rule: JAX_COMPILATION_CACHE_DIR where it is set
    (and no other path), else the fixed <repo>/.jax_cache."""
    import jax

    from jepsen_tpu.util import enable_compilation_cache, repo_root

    old = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compilation_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = os.path.join(repo_root(), ".jax_cache")
        assert enable_compilation_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", old)


def test_compilation_cache_entries_land_in_the_env_dir(tmp_path,
                                                       monkeypatch):
    """With JAX_COMPILATION_CACHE_DIR set, a compile writes its entry
    there, and a fresh compile of the same program reads it back."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache

    from jepsen_tpu.util import enable_compilation_cache

    cfg = jax.config
    old = (cfg.jax_compilation_cache_dir,
           cfg.jax_enable_compilation_cache,
           cfg.jax_persistent_cache_min_compile_time_secs)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    try:
        enable_compilation_cache()
        cfg.update("jax_enable_compilation_cache", True)
        cfg.update("jax_persistent_cache_min_compile_time_secs", 0)
        compilation_cache.reset_cache()

        def f(x):
            return (x * 3 + 1).sum()

        x = jnp.arange(7, dtype=jnp.int32)
        jax.jit(f).lower(x).compile()
        entries = set(os.listdir(tmp_path))
        assert entries, "no cache entry written"
        jax.clear_caches()
        jax.jit(f).lower(x).compile()
        assert set(os.listdir(tmp_path)) == entries  # read, not rewritten
    finally:
        cfg.update("jax_compilation_cache_dir", old[0])
        cfg.update("jax_enable_compilation_cache", old[1])
        cfg.update("jax_persistent_cache_min_compile_time_secs", old[2])
        compilation_cache.reset_cache()


def test_cli_compile_cache_flag(tmp_path, monkeypatch):
    import argparse

    import jax

    from jepsen_tpu import cli
    from jepsen_tpu.util import repo_root

    # the cli sets the env var OUTSIDE monkeypatch; register it so
    # teardown removes it (same trick as test_cli_flag_sets_env_knob)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "placeholder")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    old = jax.config.jax_compilation_cache_dir
    try:
        p = argparse.ArgumentParser()
        cli.add_test_opts(p)
        opts = cli.test_opt_fn(p.parse_args(["--dummy"]))
        assert opts["compile_cache_dir"] == os.path.join(
            repo_root(), ".jax_cache")
        opts = cli.test_opt_fn(p.parse_args(
            ["--compile-cache-dir", str(tmp_path), "--dummy"]))
        assert opts["compile_cache_dir"] == str(tmp_path)
        assert os.environ["JAX_COMPILATION_CACHE_DIR"] == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    finally:
        jax.config.update("jax_compilation_cache_dir", old)
