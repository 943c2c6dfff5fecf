"""Device-search telemetry (jepsen_tpu/obs/telemetry.py) — the aux
counter block that opens the device black box.

Contract under test:

  * **verdict byte-identity** — telemetry ON vs OFF returns byte-for-
    byte identical verdicts (everything except the attached
    ``search_telemetry`` block itself) across every engine route:
    host DFS, host linear, device BFS, batched, bucketed, mesh-
    sharded, decomposed, streamed — audits on (the acceptance
    criterion's differential fuzz);
  * **the aux block is honest** — schema/unpack unit-tested; the
    observed counters line up with what the search reports (configs
    expanded, goal found), and mask-kill / dedup-fold columns move
    exactly when the must-order mask / dead-value dedup are active;
  * **compile/transfer accounting** — a kernel-cache miss records a
    ``device.compile`` span (hits never do) tagged with whether a
    persistent XLA cache (util.enable_compilation_cache) is
    configured, and argument staging records byte-counted
    ``device.transfer`` spans;
  * **knobs** — JEPSEN_TPU_TELEMETRY / --no-telemetry / enable()
    gate everything; the off path builds the exact pre-telemetry
    kernels (separate cache key) and attaches nothing.
"""

import json
import random

import numpy as np
import pytest

import jax
from jax.sharding import Mesh

from jepsen_tpu import obs
from jepsen_tpu.checker import linearizable as lin
from jepsen_tpu.checker import seq as oracle
from jepsen_tpu.checker.linear import check_opseq_linear
from jepsen_tpu.history import encode_ops, invoke_op, ok_op
from jepsen_tpu.models import cas_register, register
from jepsen_tpu.obs import telemetry as tele
from jepsen_tpu.obs.metrics import REGISTRY
from jepsen_tpu.synth import corrupt_read, register_history

# test_linearizable.py's shared generous dims: one compiled kernel
# serves every differential case here too
DIMS = lin.SearchDims(n_det_pad=128, n_crash_pad=32, window=96, k=16,
                      state_width=1, frontier=256)


@pytest.fixture(autouse=True)
def _telemetry_default():
    """Each test starts from the env-default knob state."""
    tele.enable(None)
    yield
    tele.enable(None)


@pytest.fixture(scope="module")
def mesh():
    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("needs a multi-device mesh")
    return Mesh(np.array(devs), ("shard",))


#: stat fields that differ RUN-to-run regardless of the telemetry
#: knob — wall-clock timings and process-global cache warmth
#: (bucket_batch's kernel_cache deltas, verdict-cache hit/miss
#: counters: the ON pass warms the caches the OFF pass then hits) —
#: not verdict content
_VOLATILE = ("seconds", "probe_seconds", "t_dev", "phase_s",
             "kernel_cache", "cache_hits", "cache_misses",
             "cache_inserts", "hits", "misses", "inserts")


def _canon(v):
    if isinstance(v, dict):
        return {k: _canon(x) for k, x in v.items()
                if k not in _VOLATILE and k != "search_telemetry"}
    if isinstance(v, list):
        return [_canon(x) for x in v]
    return v


def _strip(r: dict) -> str:
    """Canonical verdict bytes: everything except the telemetry
    block itself and wall-clock timing stats."""
    return json.dumps(_canon(r), sort_keys=True, default=str)


# ---------------------------------------------------------------------------
# Unit: knob
# ---------------------------------------------------------------------------


def test_knob_default_on_and_env_off(monkeypatch):
    assert tele.enabled() is True  # default ON
    monkeypatch.setenv("JEPSEN_TPU_TELEMETRY", "0")
    tele.enable(None)  # drop the cached env read
    assert tele.enabled() is False
    monkeypatch.setenv("JEPSEN_TPU_TELEMETRY", "off")
    tele.enable(None)
    assert tele.enabled() is False
    monkeypatch.setenv("JEPSEN_TPU_TELEMETRY", "1")
    tele.enable(None)
    assert tele.enabled() is True


def test_enable_overrides_env(monkeypatch):
    monkeypatch.setenv("JEPSEN_TPU_TELEMETRY", "0")
    tele.enable(True)
    assert tele.enabled() is True
    tele.enable(False)
    assert tele.enabled() is False
    tele.enable(None)
    assert tele.enabled() is False  # back to the env knob


def test_cli_no_telemetry_sets_env_and_disables(monkeypatch):
    import argparse
    import os

    from jepsen_tpu import cli

    monkeypatch.delenv("JEPSEN_TPU_TELEMETRY", raising=False)
    p = argparse.ArgumentParser()
    cli.add_test_opts(p)
    ns = p.parse_args(["--no-telemetry"])
    assert ns.no_telemetry is True
    try:
        opts = cli.test_opt_fn(ns)
        assert opts.get("no_telemetry") is True
        assert os.environ.get("JEPSEN_TPU_TELEMETRY") == "0"
        assert tele.enabled() is False
    finally:
        # plain pop, NOT monkeypatch.delenv: test_opt_fn set the var
        # outside monkeypatch's ledger, so a second delenv would
        # record "0" as the value to RESTORE at teardown and leak
        # telemetry-off into every later test
        os.environ.pop("JEPSEN_TPU_TELEMETRY", None)
        tele.enable(None)


# ---------------------------------------------------------------------------
# Unit: aux-block schema and unpack
# ---------------------------------------------------------------------------


def test_unpack_levels_schema_and_zero_rows():
    blk = np.zeros((tele.TELE_ROWS, tele.TELE_COLS), np.int32)
    blk[0] = (4, 10, 2, 1, 3, 6, 0, 0)
    blk[1] = (6, 12, 0, 0, 1, 2, 1, 1)
    # row 5 never written (occupancy 0) -> dropped
    blk[5, tele.C_EXP] = 99
    rows = tele.unpack_levels(blk)
    assert len(rows) == 2
    assert rows[0] == {"occupancy": 4, "expanded": 10,
                      "mask_killed": 2, "dedup_folds": 1,
                      "crash_rounds": 3, "next_count": 6,
                      "overflow": 0, "goal": 0}
    assert rows[1]["goal"] == 1 and rows[1]["overflow"] == 1
    with pytest.raises(ValueError):
        tele.unpack_levels(np.zeros((4, 3), np.int32))
    with pytest.raises(ValueError):
        tele.unpack_levels(np.zeros(tele.TELE_COLS, np.int32))


def test_observed_prune_ratio_math():
    assert tele.observed_prune_ratio(0, 0, 0) is None
    assert tele.observed_prune_ratio(10, 0, 0) == 1.0
    assert tele.observed_prune_ratio(1, 3, 0) == 0.25
    assert tele.observed_prune_ratio(1, 1, 2) == 0.25


def test_accumulator_totals_truncation_and_block():
    acc = tele.SearchTelemetry()
    blk = np.zeros((tele.TELE_ROWS, tele.TELE_COLS), np.int32)
    for i in range(tele.TELE_ROWS):
        blk[i] = (2, 4, 1, 0, 0, 2, 0, 0)
    acc.add_slice(blk)
    # every row written incl. the additive last one -> truncated
    assert acc.truncated is True
    assert acc.n_levels == tele.TELE_ROWS
    assert acc.totals["expanded"] == 4 * tele.TELE_ROWS
    out = acc.block(predicted=0.5)
    assert out["observed_prune_ratio"] == pytest.approx(4 / 5)
    assert out["predicted_prune_ratio"] == 0.5
    assert out["prune_ratio_delta"] == pytest.approx(0.3)
    assert out["truncated"] is True
    assert out["per_level_columns"] == list(tele.COLUMNS)


def test_accumulator_per_level_cap():
    acc = tele.SearchTelemetry()
    blk = np.zeros((tele.TELE_ROWS, tele.TELE_COLS), np.int32)
    blk[:, tele.C_OCC] = 1
    for _ in range(8):  # 8 x 128 levels > BLOCK_LEVEL_CAP
        acc.add_slice(blk)
    out = acc.block()
    assert len(out["per_level"]) == tele.BLOCK_LEVEL_CAP
    assert out["per_level_capped"] is True
    assert out["levels"] == 8 * tele.TELE_ROWS


def test_add_totals_folds_batched_blocks():
    acc = tele.SearchTelemetry()
    blk = np.zeros((3, tele.TELE_ROWS, tele.TELE_COLS), np.int32)
    blk[:, 0] = (5, 7, 1, 0, 0, 5, 0, 1)
    acc.add_totals(blk)  # 3-D: lane-sum first
    assert acc.totals["occupancy"] == 15
    assert acc.totals["expanded"] == 21
    assert acc.levels == []  # totals-only: no per-level rows kept


# ---------------------------------------------------------------------------
# The block rides device results and the counters move
# ---------------------------------------------------------------------------


def _crashy_seq(seed: int, model, n_ops: int = 50):
    """A crash-heavy simulated history: the class where the greedy
    witness / hb prepass usually fail to decide and the device BFS
    actually runs."""
    from jepsen_tpu.synth import sim_register_history

    rng = random.Random(seed)
    h = sim_register_history(rng, 4, n_ops, crash_p=0.15,
                             max_crashes=8)
    return encode_ops(h, model.f_codes)


def _device_searched(r: dict) -> bool:
    return str(r.get("engine", "")).startswith("device")


def _first_device_search(model, seeds=range(40)):
    for seed in seeds:
        s = _crashy_seq(seed, model)
        r = lin.search_opseq(s, model, dims=DIMS)
        if _device_searched(r) and "search_telemetry" in r:
            return s, r
    pytest.fail("no seed reached the device kernel")


def test_search_telemetry_block_on_device_result():
    model = cas_register()
    levels_before = REGISTRY.get(
        "jtpu_search_levels_total").total()
    s, r = _first_device_search(model)
    st = r["search_telemetry"]
    for k in ("levels", "slices", "max_occupancy", "expanded",
              "mask_killed", "dedup_folds", "crash_rounds",
              "overflows", "goals", "observed_prune_ratio",
              "truncated"):
        assert k in st, k
    assert st["levels"] > 0 and st["slices"] >= 1
    assert st["expanded"] > 0
    ratio = st["observed_prune_ratio"]
    assert ratio is not None and 0 < ratio <= 1.0
    # predicted (hb/dpor prepass) rides next to observed when computed
    if "predicted_prune_ratio" in st:
        assert st["prune_ratio_delta"] == pytest.approx(
            ratio - st["predicted_prune_ratio"], abs=1e-5)
    # per-level rows align with the totals
    per = st["per_level"]
    cols = st["per_level_columns"]
    exp_i = cols.index("expanded")
    if not st.get("per_level_capped"):
        assert sum(r2[exp_i] for r2 in per) == st["expanded"]
    # registry counters moved
    assert REGISTRY.get("jtpu_search_levels_total").total() \
        > levels_before
    assert REGISTRY.get(
        "jtpu_search_observed_prune_ratio").value() == ratio


def test_telemetry_off_attaches_nothing():
    model = cas_register()
    s, _ = _first_device_search(model)
    tele.enable(False)
    r = lin.search_opseq(s, model, dims=DIMS)
    assert "search_telemetry" not in r
    assert _device_searched(r)


def test_device_level_spans_under_tracing():
    model = cas_register()
    s, _ = _first_device_search(model)
    obs.enable(True)
    run = "t-tele-spans"
    obs.set_run(run)
    try:
        r = lin.search_opseq(s, model, dims=DIMS)
        spans = obs.recorder(run).spans()
    finally:
        obs.set_run(None)
        obs.drop_recorder(run)
        obs.enable(None)
    lvl = [s2 for s2 in spans if s2["name"] == "device.level"]
    slc = [s2 for s2 in spans if s2["name"] == "device.slice"]
    ts = [s2 for s2 in spans if s2["name"] == "search.telemetry"]
    assert slc and lvl and ts
    st = r["search_telemetry"]
    assert len(lvl) == min(st["levels"],
                           tele.TELE_ROWS * st["slices"])
    # child spans sit inside their slice's window and carry the
    # schema's args
    a = lvl[0]["args"]
    for k in ("level", "occupancy", "expanded", "mask_killed",
              "dedup_folds", "frontier"):
        assert k in a, k
    # level spans are apportioned inside the driver's t0..t1 window,
    # which opens a hair before the slice span object itself records
    assert lvl[0]["ts"] >= min(x["ts"] for x in slc) - 5000.0
    # the search.telemetry span carries the result block (minus the
    # per-level rows) — traces are self-contained for obs_guard
    assert ts[-1]["args"]["observed_prune_ratio"] == \
        st["observed_prune_ratio"]


def test_decided_search_emits_prune_span_without_block():
    """A statically decided search (hb prepass) has no device work:
    result keeps its certificate shape (no search_telemetry key) but
    a traced run still records observed=0 vs predicted=0."""
    model = register(0)
    h = []
    for p in range(3):  # unique writes, quiescent: hb decides
        h += [invoke_op(p, "write", 10 + p), ok_op(p, "write", 10 + p)]
    h += [invoke_op(0, "read", None), ok_op(0, "read", 12)]
    s = encode_ops(h, model.f_codes)
    obs.enable(True)
    run = "t-tele-decided"
    obs.set_run(run)
    try:
        r = lin.search_opseq(s, model, dims=DIMS)
        spans = obs.recorder(run).spans()
    finally:
        obs.set_run(None)
        obs.drop_recorder(run)
        obs.enable(None)
    assert (r.get("hb") or {}).get("decided") is not None \
        or r.get("engine") in ("hb-decide", "greedy-witness")
    ts = [s2 for s2 in spans if s2["name"] == "search.telemetry"]
    if (r.get("hb") or {}).get("decided") is not None:
        assert "search_telemetry" not in r
        assert ts and ts[-1]["args"].get("decided") is True
        assert ts[-1]["args"]["observed_prune_ratio"] == 0.0
        assert "prune_ratio_delta" in ts[-1]["args"]


def test_mask_and_dedup_columns_fire_when_reductions_do():
    """Crash-heavy cas histories build masked (+dedup) kernels: the
    aux block's mask-kill / dedup-fold columns must actually move —
    the observed twin of the dpor layer's predicted reductions."""
    model = cas_register()
    killed = folded = False
    for seed in range(60):
        s = _crashy_seq(seed, model)
        # dpor pinned on: the reductions must not depend on what env
        # state earlier test files left behind
        r = lin.search_opseq(s, model, dims=DIMS, dpor=True)
        st = r.get("search_telemetry")
        if not st:
            continue
        killed = killed or st["mask_killed"] > 0
        folded = folded or st["dedup_folds"] > 0
        if killed and folded:
            break
    assert killed, "no seed produced device mask kills"
    assert folded, "no seed produced device dedup folds"


# ---------------------------------------------------------------------------
# Compile / transfer accounting
# ---------------------------------------------------------------------------


def test_compile_span_on_miss_never_on_hit():
    model = cas_register()
    # dims unique to this test so the first get_kernel is a real miss
    dims = lin.SearchDims(n_det_pad=96, n_crash_pad=32, window=64,
                          k=16, state_width=1, frontier=128)
    for k in [k for k in list(lin._KERNEL_CACHE) if dims in k]:
        lin._KERNEL_CACHE.pop(k, None)
    obs.enable(True)
    run = "t-compile-span"
    obs.set_run(run)
    try:
        lin.get_kernel(model, dims, telemetry=tele.enabled())
        first = [s for s in obs.recorder(run).spans()
                 if s["name"] == "device.compile"]
        lin.get_kernel(model, dims, telemetry=tele.enabled())
        second = [s for s in obs.recorder(run).spans()
                  if s["name"] == "device.compile"]
    finally:
        obs.set_run(None)
        obs.drop_recorder(run)
        obs.enable(None)
    assert len(first) == 1, "cache miss must record device.compile"
    a = first[0]["args"]
    assert a["cache"] == "miss"
    assert a["engine"] in ("xla", "pallas")
    assert "persistent_cache" in a
    assert len(second) == 1, "cache hit must NOT record a compile"


def test_compile_span_detects_persistent_cache(tmp_path,
                                               monkeypatch):
    from jepsen_tpu import util

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    prior = jax.config.jax_compilation_cache_dir
    applied = util.enable_compilation_cache()
    assert applied == str(tmp_path)
    obs.enable(True)
    run = "t-compile-pcache"
    obs.set_run(run)
    try:
        with tele.compile_span(engine="xla"):
            pass
        span = [s for s in obs.recorder(run).spans()
                if s["name"] == "device.compile"][0]
        assert span["args"]["persistent_cache"] is True
        jax.config.update("jax_compilation_cache_dir", None)
        with tele.compile_span(engine="xla"):
            pass
        span2 = [s for s in obs.recorder(run).spans()
                 if s["name"] == "device.compile"][-1]
        assert span2["args"]["persistent_cache"] is False
    finally:
        jax.config.update("jax_compilation_cache_dir", prior)
        obs.set_run(None)
        obs.drop_recorder(run)
        obs.enable(None)


def test_transfer_accounting_counts_bytes():
    m = REGISTRY.get("jtpu_device_transfer_bytes_total")
    before = m.value(direction="h2d")
    arrs = (np.zeros(10, np.int32), np.zeros((4, 4), np.int32))
    nb = tele.transfer_bytes(arrs)
    assert nb == 40 + 64
    tele.record_transfer(nb)
    assert m.value(direction="h2d") == before + nb
    tele.record_transfer(0)  # no-op, no crash
    assert m.value(direction="h2d") == before + nb


def test_device_seconds_and_idle_fraction_derived():
    from jepsen_tpu.obs.metrics import derived_stats

    tele.record_device_seconds(0.25)
    d = derived_stats(REGISTRY)
    assert "device_idle_fraction" in d
    assert 0.0 <= d["device_idle_fraction"] <= 1.0
    assert "observed_prune_ratio" in d


# ---------------------------------------------------------------------------
# Differential fuzz: byte-identical verdicts on/off, all routes
# ---------------------------------------------------------------------------


def _routes(s, model, mesh=None):
    from jepsen_tpu.decompose.engine import check_opseq_decomposed
    from jepsen_tpu.stream import StreamChecker

    out = {
        "dfs": oracle.check_opseq(s, model),
        "linear": check_opseq_linear(s, model, witness_cap=200_000),
        "direct": lin.search_opseq(s, model, budget=300_000,
                                   dims=DIMS),
        "decomposed": check_opseq_decomposed(s, model, witness=True),
        "batched": lin.search_batch([s, s], model,
                                    budget=300_000)[0],
        "bucketed": lin.search_batch([s], model, bucket=True,
                                     budget=300_000)[0],
    }
    if mesh is not None:
        out["sharded"] = lin.search_opseq_sharded(
            s, model, mesh, budget=300_000)
    return out


@pytest.mark.parametrize("group", range(3))
def test_differential_fuzz_identical_verdicts(group, mesh):
    """Telemetry ON vs OFF: every route's verdict bytes (minus the
    block itself) must be identical, audits clean, across valid,
    corrupted, and crash-heavy histories + a streamed leg."""
    from jepsen_tpu.analyze.audit import audit as audit_fn
    from jepsen_tpu.stream import StreamChecker

    n_checked = 0
    for i in range(8):
        seed = group * 100 + i
        rng = random.Random(seed)
        model = cas_register()
        h = register_history(rng, n_ops=30, n_procs=4, overlap=4,
                             crash_p=(0.0, 0.1, 0.25)[group])
        if i % 2:
            h = corrupt_read(rng, h, at=0.8)
        s = encode_ops(h, model.f_codes)

        tele.enable(True)
        on = _routes(s, model, mesh)
        sc = StreamChecker(model)
        for op in h:
            sc.ingest(op)
        on["streamed"] = sc.finalize()

        tele.enable(False)
        off = _routes(s, model, mesh)
        sc = StreamChecker(model)
        for op in h:
            sc.ingest(op)
        off["streamed"] = sc.finalize()
        tele.enable(None)

        for route in on:
            assert _strip(on[route]) == _strip(off[route]), \
                f"seed {seed} route {route} verdict bytes differ"
            if on[route]["valid"] != "unknown" \
                    and route != "streamed":
                a = audit_fn(s, model, on[route])
                assert a["ok"], f"seed {seed} route {route} audit"
        n_checked += 1
    assert n_checked == 8


def test_explain_plan_carries_telemetry_block():
    """The static plan states where its predicted prune ratios become
    observations — and that they won't, when the knob is off."""
    from jepsen_tpu.analyze.plan import explain, render_plan

    model = cas_register()
    s = _crashy_seq(0, model)
    plan = explain(s, model)
    assert plan["telemetry"]["enabled"] is True
    assert "observed" in plan["telemetry"]["observed_at"]
    assert "telemetry: on" in render_plan(plan)
    tele.enable(False)
    try:
        plan = explain(s, model)
        assert plan["telemetry"]["enabled"] is False
        assert "telemetry: off" in render_plan(plan)
    finally:
        tele.enable(None)


def test_sharded_route_telemetry_block(mesh):
    """The mesh-sharded driver aggregates per-shard blocks; its
    telemetry must ride the result like the single-device path."""
    model = cas_register()
    for seed in range(30):
        s = _crashy_seq(seed, model)
        r = lin.search_opseq_sharded(s, model, mesh, budget=300_000)
        st = r.get("search_telemetry")
        if st and st["levels"] > 0:
            assert st["expanded"] > 0
            assert st["observed_prune_ratio"] is not None
            return
    pytest.fail("no sharded search produced device telemetry")
