"""Differential tests for the pallas level-loop kernel.

The pallas engine (checker/pallas_level.py) promises bit-for-bit the
SAME search as the XLA step kernel under the all-pairs prune: identical
carries slice by slice (frontier rows, counts, configs, overflow) and
identical verdicts through the full driver.  Off-TPU it runs in
interpret mode, so these tests exercise the exact kernel semantics the
chip will execute.  tests/test_chip_compile.py compiles it for a
described v5e (Mosaic lowering); chip_smoke.py runs it on the chip.
"""

import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import jepsen_tpu.checker.linearizable as lin
from jepsen_tpu.checker import pallas_level as plev
from jepsen_tpu.checker.seq import check_opseq
from jepsen_tpu.history import encode_ops
from jepsen_tpu.models import cas_register, mutex
from jepsen_tpu.synth import (corrupt_read, register_history,
                              sim_mutex_history)


def _encode(model, h):
    seq = encode_ops(h, model.f_codes)
    es = lin.encode_search(seq)
    return seq, es


def _steps(model, dims):
    xla = jax.jit(lin.build_search_step_fn(model, dims))
    pal = jax.jit(plev.build_pallas_step_fn(model, dims, interpret=True))
    return xla, pal


def _args(es, esp):
    # the ONE signature home: identical for the XLA and pallas steps
    # (reduction planes inert here — unreduced differential runs)
    return lin.search_args(esp, es)


def _lockstep(model, h, *, frontier, bail, slices=12, lvl_cap=8,
              budget=10**8):
    """Drive both kernels slice by slice; assert identical carries."""
    seq, es = _encode(model, h)
    dims = lin.choose_dims(es, model, frontier=frontier)
    if not plev.eligible(model, dims):
        pytest.skip(f"dims not pallas-eligible: {dims}")
    esp = lin.pad_search(es, dims.n_det_pad, dims.n_crash_pad)
    old = lin._DOMINANCE_MODE
    lin._DOMINANCE_MODE = "allpairs"
    try:
        xla, pal = _steps(model, dims)
        a = _args(es, esp)
        cx = cp = tuple(jnp.asarray(c)
                        for c in lin._init_carry(dims, model))
        for s in range(slices):
            cx = xla(*a, jnp.int32(budget), jnp.int32(lvl_cap),
                     jnp.bool_(bail), *cx)
            cp = pal(*a, jnp.int32(budget), jnp.int32(lvl_cap),
                     jnp.bool_(bail), *cp)
            fx, cnx, stx, cfx, mdx, ovx = [np.asarray(v) for v in cx]
            fp, cnp_, stp, cfp, mdp, ovp = [np.asarray(v) for v in cp]
            assert (int(cnx), int(stx), int(cfx), int(mdx),
                    bool(ovx)) == (int(cnp_), int(stp), int(cfp),
                                   int(mdp), bool(ovp)), f"slice {s}"
            assert np.array_equal(fx[:int(cnx)], fp[:int(cnp_)]), \
                f"slice {s} frontier"
            if int(stx) != -1 or int(cnx) == 0 or (bail and bool(ovx)):
                return int(stx), int(cfx), bool(ovx)
        return int(stx), int(cfx), bool(ovx)
    finally:
        lin._DOMINANCE_MODE = old


@pytest.mark.parametrize("seed", [1, 2, 3, 4, 5])
def test_lockstep_register_with_crashes(seed):
    rng = random.Random(seed)
    model = cas_register()
    h = register_history(rng, n_ops=56, n_procs=4, overlap=3,
                         crash_p=0.08, max_crashes=4, n_values=3)
    if seed % 2:
        h = corrupt_read(rng, h, at=0.85)
    _lockstep(model, h, frontier=16, bail=False)


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_lockstep_mutex(seed):
    rng = random.Random(seed)
    model = mutex()
    h = sim_mutex_history(rng, n_ops=60, n_procs=3, crash_p=0.06,
                          max_crashes=4)
    _lockstep(model, h, frontier=16, bail=False)


@pytest.mark.parametrize("seed", [21, 22, 23])
def test_lockstep_overflow_and_bail(seed):
    """A deliberately wide history at frontier 16 must overflow; the
    uncommitted-level revert under bail must match exactly."""
    rng = random.Random(seed)
    model = cas_register()
    h = register_history(rng, n_ops=64, n_procs=8, overlap=7,
                         crash_p=0.05, max_crashes=3, n_values=2)
    st, cfg, ovf = _lockstep(model, h, frontier=16, bail=True)
    # at least one run should overflow to exercise the revert path;
    # the equality assertions inside _lockstep are the real test
    _lockstep(model, h, frontier=16, bail=False)


def test_full_search_pallas_engine_matches_oracle():
    """search_opseq with the pallas engine forced end-to-end (driver,
    escalation ladder, checkpoint shape) vs the WGL oracle."""
    old = lin._ENGINE_MODE
    lin._ENGINE_MODE = "pallas"
    try:
        for seed in (31, 32, 33, 34):
            rng = random.Random(seed)
            model = cas_register()
            h = register_history(rng, n_ops=44, n_procs=3, overlap=2,
                                 crash_p=0.06, max_crashes=3,
                                 n_values=3)
            if seed % 2:
                h = corrupt_read(rng, h, at=0.8)
            seq = encode_ops(h, model.f_codes)
            out = lin.search_opseq(seq, model, budget=5_000_000)
            oracle = check_opseq(seq, model)
            assert out["valid"] == oracle["valid"], seed
    finally:
        lin._ENGINE_MODE = old


def test_full_search_configs_match_xla_allpairs():
    """Forced-pallas and forced-xla-allpairs searches must explore the
    IDENTICAL config count (same survivor order, same prune)."""
    rng = random.Random(41)
    model = cas_register()
    h = register_history(rng, n_ops=48, n_procs=4, overlap=3,
                         crash_p=0.08, max_crashes=4, n_values=3)
    seq = encode_ops(h, model.f_codes)
    old_e, old_d = lin._ENGINE_MODE, lin._DOMINANCE_MODE
    try:
        lin._DOMINANCE_MODE = "allpairs"
        lin._ENGINE_MODE = "pallas"
        a = lin.search_opseq(seq, model, budget=5_000_000)
        lin._ENGINE_MODE = "xla"
        b = lin.search_opseq(seq, model, budget=5_000_000)
    finally:
        lin._ENGINE_MODE, lin._DOMINANCE_MODE = old_e, old_d
    assert a["valid"] == b["valid"]
    assert a["configs"] == b["configs"]
    assert a["max_depth"] == b["max_depth"]


def test_search_batch_pallas_matches_oracle():
    """The batched escalation ladder with the pallas kernel forced
    (vmap of the fused level-loop) vs per-key oracle verdicts."""
    model = cas_register()
    seqs = []
    for k in range(8):
        rng = random.Random(f"pb{k}")
        h = register_history(rng, n_ops=40, n_procs=4, overlap=3,
                             crash_p=0.04, max_crashes=2, n_values=3)
        if k % 3 == 0:
            h = corrupt_read(rng, h, at=0.8)
        seqs.append(encode_ops(h, model.f_codes))
    old = lin._ENGINE_MODE
    lin._ENGINE_MODE = "pallas"
    try:
        got = lin.search_batch(seqs, model, budget=2_000_000)
    finally:
        lin._ENGINE_MODE = old
    for k, (s, r) in enumerate(zip(seqs, got)):
        oracle = check_opseq(s, model)
        assert r["valid"] == oracle["valid"], k


def test_checkpoint_resume_under_pallas(tmp_path):
    """The checkpoint path on the pallas engine: a deadline-killed
    pallas search checkpoints; resume_opseq (also on pallas) finishes
    it and labels the engine honestly."""
    import time

    rng = random.Random(71)
    model = cas_register()
    h = register_history(rng, n_ops=80, n_procs=4, overlap=3,
                         crash_p=0.05, max_crashes=3, n_values=3)
    h = corrupt_read(rng, h, at=0.9)
    seq = encode_ops(h, model.f_codes)
    path = str(tmp_path / "ck.npz")
    old = lin._ENGINE_MODE
    lin._ENGINE_MODE = "pallas"
    try:
        saved = []

        def on_slice(carry, dims):
            lin.save_checkpoint(path, carry, dims, model, 10**7,
                                seq=seq)
            saved.append(1)

        out = lin.search_opseq(
            seq, model, budget=10**7, on_slice=on_slice,
            deadline=time.perf_counter())  # expire immediately
        if out["valid"] != "unknown" or not saved:
            pytest.skip("search decided before the deadline could cut "
                        "it (host too fast)")
        res = lin.resume_opseq(seq, model, path)
        assert res["valid"] is False
        assert res["engine"] == "device-bfs(pallas,resumed)"
        oracle = check_opseq(seq, model)
        assert res["valid"] == oracle["valid"]
    finally:
        lin._ENGINE_MODE = old


def test_cross_backend_resume_keeps_pallas_evidence(tmp_path):
    """A TPU window runs pallas slices and checkpoints; the next window
    resumes on a host where pallas is off.  The accumulated verdict's
    engine label must still carry the pallas evidence (the checkpoint
    persists the driver's actual-execution flag — through an atomic
    tmp-path + rename save too)."""
    import os
    import time

    rng = random.Random(72)
    model = cas_register()
    h = register_history(rng, n_ops=80, n_procs=4, overlap=3,
                         crash_p=0.05, max_crashes=3, n_values=3)
    h = corrupt_read(rng, h, at=0.9)
    seq = encode_ops(h, model.f_codes)
    path = str(tmp_path / "ck.npz")
    old = lin._ENGINE_MODE
    lin._ENGINE_MODE = "pallas"
    try:
        saved = []

        def on_slice(carry, dims):
            # atomic save: tmp path then rename (np.savez appends
            # .npz when the suffix is missing, so the tmp name must
            # keep it)
            lin.save_checkpoint(path + ".tmp.npz", carry, dims, model,
                                10**7, seq=seq)
            os.replace(path + ".tmp.npz", path)
            saved.append(1)

        out = lin.search_opseq(
            seq, model, budget=10**7, on_slice=on_slice,
            deadline=time.perf_counter())
        if out["valid"] != "unknown" or not saved:
            pytest.skip("search decided before the deadline could cut "
                        "it (host too fast)")
        lin._ENGINE_MODE = "xla"
        res = lin.resume_opseq(seq, model, path)
        assert res["valid"] is False
        assert res["engine"] == "device-bfs(pallas,resumed)"
    finally:
        lin._ENGINE_MODE = old


def test_eligibility_gates():
    model = cas_register()
    es_like = lin.SearchDims(n_det_pad=64, n_crash_pad=32, window=32,
                             k=4, state_width=1, frontier=16)
    assert plev.eligible(model, es_like)
    wide = lin.SearchDims(n_det_pad=64, n_crash_pad=32, window=128,
                          k=4, state_width=1, frontier=16)
    assert not plev.eligible(model, wide)
    big_f = lin.SearchDims(n_det_pad=64, n_crash_pad=32, window=32,
                           k=4, state_width=1, frontier=128)
    assert not plev.eligible(model, big_f)

    class FakeModel:
        name = "fifo-queue"

    assert not plev.eligible(FakeModel(), es_like)


def test_auto_mode_stays_xla_on_cpu():
    """auto never picks pallas off-TPU (interpret mode would be a
    silent slowdown on hosts)."""
    model = cas_register()
    dims = lin.SearchDims(n_det_pad=64, n_crash_pad=32, window=32,
                          k=4, state_width=1, frontier=16)
    old = lin._ENGINE_MODE
    lin._ENGINE_MODE = "auto"
    try:
        assert lin._use_pallas(model, dims) is False
    finally:
        lin._ENGINE_MODE = old
