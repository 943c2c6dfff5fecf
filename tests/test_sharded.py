"""Mesh-sharded frontier search: verdicts must match the host oracle;
exploration must be deterministic and (with dominance pruning) explore
at most the oracle's configuration space.  Exactness of the all_to_all
routing is guarded indirectly: a config lost in routing flips a VALID
history's verdict (the witness path dies out), and the differential
cases here include uncorrupted, valid histories for exactly that
reason.  Runs on the virtual 8-device CPU mesh (conftest)."""

import random

import numpy as np
import pytest

import jax
from jax.sharding import Mesh

from jepsen_tpu.checker import linearizable as lin
from jepsen_tpu.checker import seq as oracle
from jepsen_tpu.history import encode_ops
from jepsen_tpu.models import cas_register
from jepsen_tpu.synth import corrupt_read, register_history


@pytest.fixture(scope="module")
def mesh():
    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("needs a multi-device mesh")
    return Mesh(np.array(devs), ("shard",))


@pytest.mark.parametrize("seed", range(6))
def test_sharded_agrees_with_oracle(mesh, seed):
    rng = random.Random(seed)
    model = cas_register()
    h = register_history(rng, n_ops=50, n_procs=6, overlap=4, crash_p=0.1)
    if seed % 2:
        h = corrupt_read(rng, h, at=0.9)
    s = encode_ops(h, model.f_codes)
    want = oracle.check_opseq(s, model)["valid"]
    got = lin.search_opseq_sharded(s, model, mesh, frontier_per_device=128)
    assert got["valid"] == want, f"oracle={want} sharded={got}"


def test_sharded_exact_and_deterministic(mesh):
    rng = random.Random(42)
    model = cas_register()
    h = register_history(rng, n_ops=220, n_procs=16, overlap=6,
                         crash_p=0.01, max_crashes=4)
    h = corrupt_read(rng, h, at=0.95)
    s = encode_ops(h, model.f_codes)
    ref = oracle.check_opseq(s, model)
    counts = set()
    for _ in range(3):
        out = lin.search_opseq_sharded(s, model, mesh,
                                       frontier_per_device=256)
        assert out["valid"] == ref["valid"]
        counts.add(out["configs"])
    # deterministic across runs; dominance pruning means the sharded
    # engine explores AT MOST the oracle's configuration space (the
    # crash-subset dimension collapses to minimal antichains)
    assert len(counts) == 1, f"nondeterministic: {counts}"
    c = counts.pop()
    assert c <= ref["configs"], \
        f"sharded explored {c}, oracle {ref['configs']}"


def test_sharded_escalates_on_overflow(mesh):
    rng = random.Random(7)
    model = cas_register()
    h = register_history(rng, n_ops=120, n_procs=12, overlap=8)
    h = corrupt_read(rng, h, at=0.9)
    s = encode_ops(h, model.f_codes)
    ref = oracle.check_opseq(s, model)
    # start absurdly narrow; the ladder must still converge to the truth
    out = lin.search_opseq_sharded(s, model, mesh, frontier_per_device=64)
    assert out["valid"] == ref["valid"]


def test_sharded_escalation_resumes(mesh, monkeypatch):
    """Tiny per-device frontier forces the sharded ladder to widen; the
    verdict must still match the oracle (resume-from-carry soundness on
    the mesh path)."""
    import random

    from jepsen_tpu.checker import linearizable as lin, seq as oracle
    from jepsen_tpu.history import encode_ops
    from jepsen_tpu.models import cas_register

    monkeypatch.setattr(lin, "_SLICE_LEVELS0", 4)
    monkeypatch.setattr(lin, "_adapt_lvl_cap", lambda cap, dt: cap)
    from test_linearizable import corrupt, random_register_history

    rng = random.Random(911)
    h = corrupt(rng, random_register_history(rng, n_procs=4, n_ops=40))
    model = cas_register()
    s = encode_ops(h, model.f_codes)
    want = oracle.check_opseq(s, model)["valid"]
    out = lin.search_opseq_sharded(s, model, mesh,
                                   frontier_per_device=8,
                                   budget=500_000)
    assert out["valid"] == want, f"oracle={want} sharded={out}"


# ---------------------------------------------------------------------------
# multi-host plumbing (jepsen_tpu.distributed) — standalone degradation:
# process_count == 1 means the DCN ("keys") axis has size 1, the whole
# batch stays on this host, and verdicts must be unchanged.
# ---------------------------------------------------------------------------


def test_distributed_standalone_degrades():
    from jepsen_tpu import distributed as dist

    assert dist.init_from_env() is False  # no cluster configured
    info = dist.process_info()
    assert info["process_index"] == 0 and info["process_count"] == 1
    mesh = dist.multihost_mesh()
    assert mesh.shape["keys"] == 1
    assert mesh.shape["shard"] == len(jax.devices())
    sh = dist.keys_sharding(mesh)
    # a batch checked under the degraded sharding still gives exact
    # verdicts (single-host path)
    import random

    from jepsen_tpu.checker import linearizable as lin, seq as oracle
    from jepsen_tpu.history import encode_ops
    from jepsen_tpu.models import cas_register
    from jepsen_tpu.synth import corrupt_read, register_history

    model = cas_register()
    seqs, want = [], []
    for k in range(8):
        rng = random.Random(4200 + k)
        h = register_history(rng, n_ops=24, n_procs=3, overlap=3)
        if k % 2 == 0:
            h = corrupt_read(rng, h, at=0.7)
        s = encode_ops(h, model.f_codes)
        seqs.append(s)
        want.append(oracle.check_opseq(s, model)["valid"])
    with mesh:
        got = lin.search_batch(seqs, model, budget=100_000, sharding=sh)
    assert [r["valid"] for r in got] == want


def test_sharded_batch_certificate_and_audit(mesh):
    """The mesh-sharded batch path's certificate/audit contract —
    ROADMAP noted it had 'never been exercised'.  Every per-key result
    coming back through the mesh route must either carry real evidence
    (greedy/hb witnesses) or state exactly why it cannot
    (witness_dropped / frontier_dropped), and the independent audit
    pass must replay every certificate clean (CPU mesh fallback)."""
    from jax.sharding import NamedSharding, PartitionSpec

    from jepsen_tpu.analyze.audit import audit as audit_fn

    model = cas_register()
    seqs, want = [], []
    for k in range(8):
        rng = random.Random(8800 + k)
        h = register_history(rng, n_ops=28, n_procs=4, overlap=3,
                             crash_p=0.1 if k % 3 == 0 else 0.0)
        if k % 2 == 0:
            h = corrupt_read(rng, h, at=0.75)
        s = encode_ops(h, model.f_codes)
        seqs.append(s)
        want.append(oracle.check_opseq(s, model, dpor=False)["valid"])
    sh = NamedSharding(mesh, PartitionSpec("shard"))
    got = lin.search_batch(seqs, model, budget=300_000, sharding=sh,
                           audit=True)
    assert [r["valid"] for r in got] == want
    for k, (s, r) in enumerate(zip(seqs, got)):
        if r["valid"] is True:
            assert "linearization" in r or "witness_dropped" in r, \
                (k, r)
        elif r["valid"] is False:
            assert ("final_ops" in r or "hb_cycle" in r
                    or "frontier_dropped" in r), (k, r)
        a = audit_fn(s, model, r)
        assert a["ok"], (k, [str(d) for d in a["diagnostics"]])


def test_sharded_single_history_certificate_and_audit(mesh):
    """search_opseq_sharded's own certificate: a whole-history mesh
    verdict states its witness/frontier drop reason and audits clean —
    for a valid, an invalid, and an hb-decided history."""
    from jepsen_tpu.analyze.audit import audit as audit_fn

    model = cas_register()
    rng = random.Random(4242)
    h_ok = register_history(rng, n_ops=40, n_procs=4, overlap=4)
    h_bad = corrupt_read(rng, register_history(
        random.Random(4243), n_ops=40, n_procs=4, overlap=4), at=0.8)
    for h in (h_ok, h_bad):
        s = encode_ops(h, model.f_codes)
        want = oracle.check_opseq(s, model, dpor=False)["valid"]
        # hb=False exercises the real mesh kernels (the prepass would
        # decide these statically); a second call with the prepass ON
        # must return the same verdict with an hb certificate
        out = lin.search_opseq_sharded(s, model, mesh,
                                       frontier_per_device=128,
                                       hb=False)
        assert out["valid"] == want
        if out["valid"] is True:
            assert "linearization" in out or "witness_dropped" in out
        elif out["valid"] is False:
            assert "final_ops" in out or "frontier_dropped" in out
        a = audit_fn(s, model, out)
        assert a["ok"], [str(d) for d in a["diagnostics"]]
        dec = lin.search_opseq_sharded(s, model, mesh,
                                       frontier_per_device=128,
                                       audit=True)
        assert dec["valid"] == want


def test_sharded_deadline_and_slice_hook(mesh):
    """The sharded drive honors a deadline (verdict unknown, not a
    hang) and delivers every slice's carry + dims to on_slice — the
    scale-out analog of the single-device checkpoint hook."""
    import time

    rng = random.Random(99)
    model = cas_register()
    h = register_history(rng, n_ops=120, n_procs=8, overlap=6,
                         crash_p=0.1)
    h = corrupt_read(rng, h, at=0.9)
    s = encode_ops(h, model.f_codes)
    seen = []
    out = lin.search_opseq_sharded(
        s, model, mesh, frontier_per_device=64,
        deadline=time.perf_counter() - 1.0,  # already past: one slice
        on_slice=lambda carry, dims: seen.append(
            (np.asarray(carry[0]).shape, dims.frontier)))
    assert out["valid"] in (True, False, "unknown")
    assert seen, "on_slice never fired"
    shape, f = seen[0]
    assert shape[0] == f * mesh.shape["shard"]


# ---------------------------------------------------------------------------
# bucket-then-shard scheduler (checker/bucket.search_batch_sharded_bucketed)
# ---------------------------------------------------------------------------


def _mixed_batch(seed0, *, n=12):
    """The differential-fuzz key mix: small/medium/big op counts,
    :info crashes, corrupt (invalid) and clean (valid) histories, plus
    non-CAS register keys whose corrupt reads the hb/constraint
    prepass decides statically — those must dispose BEFORE sharding."""
    model = cas_register()
    seqs = []
    for k in range(n):
        rng = random.Random(seed0 + k)
        n_ops = (28, 50, 90)[k % 3]
        cas = k % 4 != 3
        h = register_history(rng, n_ops=n_ops, n_procs=5, overlap=4,
                             crash_p=0.1 if k % 3 == 0 else 0.0,
                             cas=cas)
        if k % 2 == 0 or not cas:
            h = corrupt_read(rng, h, at=0.8)
        seqs.append(encode_ops(h, model.f_codes))
    return seqs, model


@pytest.mark.parametrize("seed0", [5200, 6300])
def test_bucketed_sharded_differential_fuzz(mesh, seed0):
    """Bucketed-sharded vs fused-sharded vs single-device vs oracle:
    verdict-identical key-for-key on a mixed-size batch, with every
    certificate audited on all three engine routes."""
    from jax.sharding import NamedSharding, PartitionSpec

    from jepsen_tpu.analyze.audit import audit as audit_fn

    seqs, model = _mixed_batch(seed0)
    want = [oracle.check_opseq(s, model, dpor=False)["valid"]
            for s in seqs]
    sh = NamedSharding(mesh, PartitionSpec("shard"))
    got_b = lin.search_batch(seqs, model, budget=400_000, sharding=sh,
                             audit=True)
    got_f = lin.search_batch(seqs, model, budget=400_000, sharding=sh,
                             bucket=False, audit=True)
    got_1 = lin.search_batch(seqs, model, budget=400_000, audit=True)
    assert [r["valid"] for r in got_b] == want
    assert [r["valid"] for r in got_f] == want
    assert [r["valid"] for r in got_1] == want
    for k, (s, rb) in enumerate(zip(seqs, got_b)):
        a = audit_fn(s, model, rb)
        assert a["ok"], (k, [str(d) for d in a["diagnostics"]])
    sb = got_b[0].get("shard_batch")
    assert sb, "bucketed-sharded stats block missing"
    assert sb["n_devices"] == mesh.shape["shard"]
    disposed = sb["greedy"] + sb["hb_decided"] \
        + sb["constraint_decided"] + sb["hard"]
    searched = sum(b["searched"] for b in sb["buckets"])
    assert disposed + searched == len(seqs)
    # non-CAS corrupt keys must never reach a device bucket
    assert sb["hb_decided"] + sb["constraint_decided"] > 0


#: per-bucket stats fields that must match `analyze.plan.explain_batch`
#: field-for-field (the closed-loop cost-model contract)
_EXPLAIN_BUCKET_FIELDS = ("searched", "dims", "lanes", "pad_lanes",
                          "useful_ops", "padded_ops")
_EXPLAIN_TOTAL_FIELDS = ("n_buckets", "greedy", "hb_decided",
                         "constraint_decided", "hard", "useful_ops",
                         "padded_ops", "fused_padded_ops")


def _stats_match_plan(sb: dict, plan: dict) -> tuple[bool, list]:
    """Field-for-field comparison of the live ``shard_batch`` stats
    against ``explain_batch(..., n_devices=...)``'s prediction."""
    diffs = []
    for f in _EXPLAIN_TOTAL_FIELDS:
        if sb.get(f) != plan.get(f):
            diffs.append({"field": f, "live": sb.get(f),
                          "plan": plan.get(f)})
    live_b, plan_b = sb.get("buckets", []), plan.get("buckets", [])
    if len(live_b) != len(plan_b):
        diffs.append({"field": "len(buckets)", "live": len(live_b),
                      "plan": len(plan_b)})
    else:
        for i, (lb, pb) in enumerate(zip(live_b, plan_b)):
            for f in _EXPLAIN_BUCKET_FIELDS:
                if lb.get(f) != pb.get(f):
                    diffs.append({"field": f"buckets[{i}].{f}",
                                  "live": lb.get(f),
                                  "plan": pb.get(f)})
    return not diffs, diffs


def test_bucketed_sharded_explain_match(mesh):
    """explain_batch(n_devices=...)'s prediction matches the live
    shard_batch stats field-for-field on mixed-size keys — the
    cost-model contract of the bucket planner."""
    from jax.sharding import NamedSharding, PartitionSpec

    from jepsen_tpu.analyze.plan import explain_batch

    model = cas_register()
    seqs = []
    for k in range(10):
        rng = random.Random(31000 + k)
        h = register_history(rng, n_ops=74 if k < 8 else 120,
                             n_procs=6, overlap=4)
        h = corrupt_read(rng, h, at=0.85)
        seqs.append(encode_ops(h, model.f_codes))
    sh = NamedSharding(mesh, PartitionSpec("shard"))
    got = lin.search_batch(seqs, model, budget=400_000, sharding=sh,
                           audit=False)
    sb = got[0].get("shard_batch")
    assert sb
    n_dev = mesh.shape["shard"]
    plan = explain_batch(seqs, model, n_devices=n_dev)
    match, diffs = _stats_match_plan(sb, plan)
    assert match, diffs
    assert plan["padding_efficiency"] == sb["padding_efficiency"]
    assert plan["fused_padded_ops"] == sb["fused_padded_ops"]


def test_sharded_pad_lanes_inert(mesh):
    """Mesh-divisibility pad lanes must not bill configs or occupancy:
    the same keys at the same dims, sharded (5 pad lanes on 8 devices)
    vs unsharded (no pads), produce identical per-key configs AND an
    identical telemetry block."""
    from jax.sharding import NamedSharding, PartitionSpec

    from jepsen_tpu.obs import telemetry as _tele

    model = cas_register()
    seqs = []
    for k in range(3):
        rng = random.Random(7100 + k)
        h = register_history(rng, n_ops=40, n_procs=5, overlap=4)
        h = corrupt_read(rng, h, at=0.85)
        seqs.append(encode_ops(h, model.f_codes))
    ess = [lin.encode_search(s) for s in seqs]
    dims = lin.batch_dims(ess, model, frontier=64)
    sh = NamedSharding(mesh, PartitionSpec("shard"))
    _tele.enable(True)
    try:
        got_s = lin.search_batch(seqs, model, budget=400_000, dims=dims,
                                 sharding=sh, audit=False)
        got_1 = lin.search_batch(seqs, model, budget=400_000, dims=dims,
                                 audit=False)
    finally:
        _tele.enable(None)
    assert [r["valid"] for r in got_s] == [r["valid"] for r in got_1]
    assert [r.get("configs") for r in got_s] \
        == [r.get("configs") for r in got_1]
    ts = got_s[0].get("search_telemetry")
    t1 = got_1[0].get("search_telemetry")
    assert ts is not None and t1 is not None
    for f in ("expanded", "mask_killed", "dedup_folds", "goals",
              "max_occupancy"):
        assert ts[f] == t1[f], \
            (f, ts[f], t1[f], "pad lanes leaked into telemetry")
