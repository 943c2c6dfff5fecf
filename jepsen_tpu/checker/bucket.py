"""Shape-bucketed device batching — tight pads, pipelined host prep.

`search_batch` pads EVERY key in a batch to the widest key's dims
(`batch_dims` takes maxes over the batch), so one contentious key
inflates the padded work of the other 255.  This module is the
scheduler in front of the device engine that fixes that — the
GPU-model-checking lesson (GPUexplore, arXiv:1801.05857) applied to
the batch axis: keep the accelerator saturated with uniformly-shaped
work instead of one ragged megabatch.

* **Bucketing** — keys group by their power-of-two-rounded SearchDims
  bucket (:func:`bucket_key`: the exact (n_det_pad, window,
  n_crash_pad) quantization `choose_dims`/`batch_dims` apply), so
  every key in a bucket shares the bucket's padded shape with zero
  extra padding.  Each bucket runs as its own
  `linearizable._search_batch_ladder` call at its own tight dims.
* **Kernel memoization** — buckets reuse compiled kernels per (model,
  dims, bucket-size-class) through the ordinary kernel cache
  (`get_batch_kernel`; hit/miss counters in `KERNEL_CACHE_STATS`), so
  a steady stream of same-shaped buckets never retraces; with JAX's
  persistent cache on (util.enable_compilation_cache, which every
  entry point calls) compiles survive processes too.
* **Pipelining** — while bucket k executes on device (the ladder
  blocks inside XLA executions, which release the GIL), a prep thread
  greedy-witnesses and tight-pads bucket k+1, so that host
  preprocessing hides under device time.  (Encoding itself happens
  upfront: bucket PLANNING needs every key's window, which only
  `encode_search` computes.)

The mesh-sharded route gets the same treatment
(:func:`search_batch_sharded_bucketed`): bucket first, then cover the
mesh per bucket via ``shard_map`` at that bucket's tight dims, padding
with inert keys only up to mesh divisibility within the bucket instead
of one fused batch-wide shape — ScalaBFS's bucket-then-distribute
applied to the device axis (arXiv:2105.11754).

Bucketing is verdict-identical to the fused batch by construction
(the searches are exact at any padding, and every key rides the same
escalation ladder); per-key ``configs``/``engine`` labels come
straight from the engines that produced them.  It wins when key
shapes are heterogeneous (mixed op counts / windows / crash counts);
uniform batches degenerate to ONE bucket — the fused path plus a
negligible plan.  Env knob: ``JEPSEN_TPU_BATCH_BUCKETS=0`` disables,
an integer caps the bucket count (cheapest buckets merge into their
nearest larger neighbor first), unset/auto = on, at most 8 buckets.
"""

from __future__ import annotations

import os
import time
from concurrent.futures import ThreadPoolExecutor

from .. import obs
from ..history import OpSeq
from ..models import ModelSpec
from ..obs import metrics as obs_metrics

#: flight-recorder counters: padded-vs-useful rows shipped to device
#: (padding efficiency on /metrics) and per-stage wall histograms —
#: the same numbers the per-run ``bucket_batch`` stats dict reports,
#: aggregated process-wide
_M_BUCKET_OPS = obs_metrics.REGISTRY.counter(
    "jtpu_bucket_ops_total",
    "Bucketed device batch rows, useful vs padded", ("kind",))
_M_BUCKET_S = obs_metrics.REGISTRY.histogram(
    "jtpu_bucket_seconds",
    "Wall seconds per bucket stage (prep/device)", ("stage",))
#: the mesh-sharded twins: rows here include the inert
#: mesh-divisibility pad lanes in "padded" (billed honestly against
#: efficiency, though they never touch configs/occupancy counters)
_M_SHARD_OPS = obs_metrics.REGISTRY.counter(
    "jtpu_shard_ops_total",
    "Mesh-sharded bucketed batch rows, useful vs padded", ("kind",))
_M_SHARD_S = obs_metrics.REGISTRY.histogram(
    "jtpu_shard_seconds",
    "Wall seconds per sharded bucket stage (prep/device)", ("stage",))

#: default cap on distinct buckets per batch: each bucket is a device
#: dispatch (and possibly a compile on first contact), so unbounded
#: fragmentation would trade padding waste for dispatch/compile waste
_DEFAULT_MAX_BUCKETS = 8


def _bucket_mode() -> tuple[bool, int]:
    """(enabled, max_buckets) from JEPSEN_TPU_BATCH_BUCKETS: "0"/"off"
    turns the DEFAULT routing off (an explicit ``bucket=True`` call
    still buckets at the default cap — the env knob must not silently
    neuter a per-call override), an integer caps the bucket count
    ("1" pins a single fused-shape bucket and counts as
    default-disabled), unset/other = on at the default cap."""
    v = os.environ.get("JEPSEN_TPU_BATCH_BUCKETS", "").strip().lower()
    if v in ("0", "off", "false", "no"):
        return False, _DEFAULT_MAX_BUCKETS
    if v.isdigit():
        n = int(v)
        return n > 1, max(1, n)
    return True, _DEFAULT_MAX_BUCKETS


def bucketing_enabled() -> bool:
    """The env-knob default `search_batch` consults when ``bucket`` is
    not passed explicitly."""
    return _bucket_mode()[0]


def bucket_key(es) -> tuple[int, int, int]:
    """The power-of-two-rounded dims bucket an EncodedSearch lands in.

    Exactly the (n_det_pad, window, n_crash_pad) quantization
    `choose_dims`/`batch_dims` apply to a single key, so a bucket of
    equal-keyed histories pads each member to the dims it would have
    chosen for itself — zero padding attributable to batching."""
    from .linearizable import _next_pow2, _round_up

    nd = max(64, _next_pow2(es.n_det))
    w = _round_up(es.window, 32)
    nc = _round_up(es.n_crash, 32) if es.n_crash else 32
    return nd, w, nc


def _bucket_cost(key: tuple[int, int, int], n_keys: int) -> int:
    """Padded rows a bucket ships to the device (its schedule weight)."""
    nd, _w, nc = key
    return (nd + nc) * n_keys


def plan_buckets(keys: list[tuple[int, int, int]],
                 max_buckets: int) -> list[list[int]]:
    """Group key indices by bucket, then merge down to ``max_buckets``.

    Merging always folds the cheapest bucket into its nearest
    neighbor in dims order (members re-pad to the elementwise-max dims
    of the pair, so adjacent dim tuples waste the least padding).
    Returns index groups ordered largest-padded-cost-first: the big
    bucket's device time hides the most pipelined host prep, and —
    like the ladder's largest-first key order — the straggler starts
    first."""
    groups: dict[tuple, list[int]] = {}
    for i, k in enumerate(keys):
        groups.setdefault(k, []).append(i)
    while len(groups) > max(1, max_buckets):
        order = sorted(groups)
        costs = [_bucket_cost(k, len(groups[k])) for k in order]
        j = min(range(len(order)), key=costs.__getitem__)
        t = j + 1 if j + 1 < len(order) else j - 1
        a, b = order[j], order[t]
        merged = tuple(max(x, y) for x, y in zip(a, b))
        rows = groups.pop(a) + groups.pop(b)
        groups.setdefault(merged, []).extend(rows)
    return [idxs for _k, idxs in
            sorted(groups.items(),
                   key=lambda kv: -_bucket_cost(kv[0], len(kv[1])))]


def search_batch_bucketed(seqs: list[OpSeq], model: ModelSpec, *,
                          budget: int = 2_000_000,
                          hb: bool | None = None,
                          dpor: bool | None = None) -> list[dict]:
    """Bucketed drop-in for `search_batch`'s ladder path.

    Per-key results are exactly what the underlying engines report
    (greedy-witness / device-batch ladder / host-linear fallback for
    keys past the device encoding limits); the FIRST result
    additionally carries the ``bucket_batch`` stats dict — per-bucket
    padding efficiency (useful_ops / padded_ops), the fused-batch
    counterfactual, and kernel-cache hit counts — the bench's evidence
    that bucketing actually cut wasted padded work.
    """
    from . import linearizable as lin
    from ..analyze.dpor import resolve_dpor
    from ..analyze.hb import maybe_hb, resolve_hb

    hb = resolve_hb(hb)
    dpor_on = resolve_dpor(dpor)
    n = len(seqs)
    t_start = time.perf_counter()
    kc0 = lin.kernel_cache_stats()
    ess = [lin.encode_search(s) for s in seqs]
    results: list = [None] * n
    hard, fit = [], []
    for i, e in enumerate(ess):
        (hard if e.window > lin.MAX_WINDOW
         or e.n_crash > lin.MAX_CRASH else fit).append(i)
    _enabled, max_buckets = _bucket_mode()
    plans = plan_buckets([bucket_key(ess[i]) for i in fit], max_buckets)
    plans = [[fit[p] for p in grp] for grp in plans]

    stats: dict = {"n_keys": n, "n_buckets": len(plans), "buckets": [],
                   "greedy": 0, "hard": len(hard), "hb_decided": 0,
                   "constraint_decided": 0}

    # pin span attribution to the run that started THIS drive: the
    # prep closure runs on the pipeline thread, where the process-wide
    # current run may have moved on under a multiplexing service by
    # the time the span closes (T001/T004 — the PR 17 race class)
    run_pin = obs.current_run()

    def prep(idxs: list[int]):
        """Host stage for one bucket: greedy-witness disposal, then
        tight dims + padding for the keys that must ride the device.
        Pure numpy/Python — safe to run in the pipeline thread while
        the previous bucket executes (its span lands on the prep
        thread's track, so the trace timeline SHOWS the overlap)."""
        t_prep = time.perf_counter()
        with obs.span("bucket.prep", cat="host", run=run_pin,
                      keys=len(idxs)):
            ready: dict[int, dict] = {}
            run: list[int] = []
            run_mask: dict[int, dict | None] = {}
            for i in idxs:
                s = seqs[i]
                if lin.greedy_witness(s, model):
                    # the certificate indexes the key's OWN OpSeq, so
                    # it survives bucket assignment and reordering
                    # untouched
                    ready[i] = {"valid": True, "configs": s.n_must,
                                "max_depth": s.n_must,
                                "engine": "greedy-witness",
                                "linearization":
                                    lin.greedy_linearization(s)}
                else:
                    r = mp = None
                    if hb:
                        hbres = maybe_hb(s, model, True, dpor)
                        if hbres is not None and \
                                hbres.decided is not None:
                            r = dict(hbres.decided)
                        elif hbres is not None and hbres.must_pred:
                            mp = hbres.must_pred
                    if r is not None:
                        # HB-decided next to the greedy disposal: the
                        # key never pads into the bucket's dims, never
                        # costs a device config (explain_batch mirrors
                        # this split exactly)
                        ready[i] = r
                    else:
                        run.append(i)
                        run_mask[i] = mp
            if not run:
                _M_BUCKET_S.observe(time.perf_counter() - t_prep,
                                    stage="prep")
                return ready, run, None, None
            dims = lin.batch_dims([ess[i] for i in run], model,
                                  frontier=32)
            if dpor_on:
                # thread the undecided keys' must-order maps into the
                # encodings as device planes + the dead-value table —
                # the bucket's ladder reads the flags off the padded
                # encodings and builds the masked kernel.  Buckets in
                # the pallas regime drop the optional prune and keep
                # the fused kernel instead (engine priority).
                for i in run:
                    lin.attach_reductions(ess[i], seqs[i], model,
                                          run_mask.get(i), dedup=True)
                    lin._strip_reductions_for_pallas(ess[i], model,
                                                     dims)
            dead_pad = lin.batch_dead_pad([ess[i] for i in run])
            esps = [lin.pad_search(ess[i], dims.n_det_pad,
                                   dims.n_crash_pad,
                                   dead_pad=dead_pad) for i in run]
        _M_BUCKET_S.observe(time.perf_counter() - t_prep, stage="prep")
        return ready, run, dims, esps

    useful_total = padded_total = 0
    run_all: list[int] = []
    if plans:
        ex = ThreadPoolExecutor(max_workers=1,
                                thread_name_prefix="bucket-prep")
        try:
            fut = ex.submit(prep, plans[0])
            for b, idxs in enumerate(plans):
                ready, run, dims, esps = fut.result()
                if b + 1 < len(plans):
                    # bucket b+1's host prep overlaps bucket b's device
                    # execution below
                    fut = ex.submit(prep, plans[b + 1])
                for i, r in ready.items():
                    results[i] = r
                n_hb = sum(1 for r in ready.values()
                           if r.get("engine") == "hb-decide")
                n_cs = sum(1 for r in ready.values()
                           if r.get("engine") == "constraint-decide")
                stats["hb_decided"] += n_hb
                stats["constraint_decided"] += n_cs
                stats["greedy"] += len(ready) - n_hb - n_cs
                t0 = time.perf_counter()
                if run:
                    with obs.span("bucket.device", cat="device",
                                  bucket=b, keys=len(run),
                                  dims=[dims.n_det_pad, dims.window,
                                        dims.n_crash_pad]):
                        sub = lin._search_batch_ladder(
                            [seqs[i] for i in run], esps, model, dims,
                            budget)
                    for i, r in zip(run, sub):
                        results[i] = r
                dt = time.perf_counter() - t0
                if run:
                    _M_BUCKET_S.observe(dt, stage="device")
                useful = sum(ess[i].n_det + ess[i].n_crash for i in run)
                padded = (len(run) * (dims.n_det_pad + dims.n_crash_pad)
                          if run else 0)
                useful_total += useful
                padded_total += padded
                run_all += run
                stats["buckets"].append({
                    "dims": ([dims.n_det_pad, dims.window,
                              dims.n_crash_pad] if run else None),
                    "n_keys": len(idxs), "searched": len(run),
                    "useful_ops": useful, "padded_ops": padded,
                    "padding_efficiency": (round(useful / padded, 4)
                                           if padded else None),
                    "seconds": round(dt, 3)})
        finally:
            ex.shutdown(wait=True)
    if hard:
        # past the device encoding limits: greedy witness FIRST (the
        # fused path disposes of well-behaved keys in O(n) before its
        # hard check — skipping it here could degrade a True verdict
        # to "unknown" via an exhausted host sweep), then the same
        # host-linear fallback per key
        from .linear import check_opseq_linear

        for i in hard:
            s = seqs[i]
            if lin.greedy_witness(s, model):
                results[i] = {"valid": True, "configs": s.n_must,
                              "max_depth": s.n_must,
                              "engine": "greedy-witness",
                              "linearization": lin.greedy_linearization(s)}
                stats["greedy"] += 1
                continue
            r = check_opseq_linear(seqs[i], model, lint=False, hb=hb,
                                   dpor=dpor)
            r["engine"] = "host-linear(fallback)"
            results[i] = r
    # the single-fused-batch counterfactual over the SAME device-ridden
    # keys: what `batch_dims` over the whole set would have padded to
    fused_padded = 0
    if run_all:
        fdims = lin.batch_dims([ess[i] for i in run_all], model)
        fused_padded = len(run_all) * (fdims.n_det_pad
                                       + fdims.n_crash_pad)
    kc1 = lin.kernel_cache_stats()
    if useful_total or padded_total:
        _M_BUCKET_OPS.inc(useful_total, kind="useful")
        _M_BUCKET_OPS.inc(padded_total, kind="padded")
    stats.update({
        "useful_ops": useful_total,
        "padded_ops": padded_total,
        "padding_efficiency": (round(useful_total / padded_total, 4)
                               if padded_total else None),
        "fused_padded_ops": fused_padded or None,
        "fused_padding_efficiency": (round(useful_total / fused_padded,
                                           4) if fused_padded else None),
        "kernel_cache": {k: kc1[k] - kc0[k] for k in kc1},
        "seconds": round(time.perf_counter() - t_start, 3),
    })
    # stats ride on the FIRST result only: attaching the shared dict
    # (with its per-bucket list) to every key would serialize it N
    # times through per-key report stores, and one shared mutable
    # object on N results invites spooky cross-key mutation
    if results:
        results[0].setdefault("bucket_batch", stats)
    return results


def search_batch_sharded_bucketed(seqs: list[OpSeq], model: ModelSpec,
                                  sharding, *,
                                  budget: int = 2_000_000,
                                  hb: bool | None = None,
                                  dpor: bool | None = None
                                  ) -> list[dict]:
    """Bucket-then-shard: the mesh analog of `search_batch_bucketed`.

    The fused sharded path pins EVERY key to one batch-wide
    `SearchDims` "to keep the mesh covered", so one contentious key
    inflates the padded rows of all shards.  Here keys bucket exactly
    like the single-device scheduler (same `bucket_key` quantization,
    same `plan_buckets` merge), and each bucket covers the mesh on its
    own via `linearizable._search_batch_sharded_fixed` — a `shard_map`
    dispatch at the bucket's tight dims, padded with inert keys only
    up to mesh divisibility WITHIN the bucket.  Host prep for bucket
    k+1 (greedy witness, HB/constraint disposal, DPOR attach, tight
    pad) pipelines under bucket k's device time on the same
    one-worker prep thread.

    Verdict- and certificate-identical to the fused sharded path by
    construction: every key runs the same exact search at its bucket's
    padding, results carry the same "device-batch" engine label and
    drop-reason certificates, and overflowed keys take the same solo
    redo.  The FIRST result carries the ``shard_batch`` stats dict —
    per-bucket padding efficiency (mesh pad lanes billed in
    padded_ops), the fused-shape counterfactual, kernel-cache hits,
    shard count — mirrored exactly by
    `analyze.plan.explain_batch(..., n_devices=...)`.
    """
    from . import linearizable as lin
    from ..analyze.dpor import resolve_dpor
    from ..analyze.hb import maybe_hb, resolve_hb
    from ..obs import telemetry as _tele

    hb = resolve_hb(hb)
    dpor_on = resolve_dpor(dpor)
    n = len(seqs)
    t_start = time.perf_counter()
    kc0 = lin.kernel_cache_stats()
    n_dev = getattr(sharding, "num_devices", 1) or 1
    tele_acc = _tele.SearchTelemetry("device-batch-sharded") \
        if _tele.enabled() else None
    ess = [lin.encode_search(s) for s in seqs]
    results: list = [None] * n
    hard, fit = [], []
    for i, e in enumerate(ess):
        (hard if e.window > lin.MAX_WINDOW
         or e.n_crash > lin.MAX_CRASH else fit).append(i)
    _enabled, max_buckets = _bucket_mode()
    plans = plan_buckets([bucket_key(ess[i]) for i in fit], max_buckets)
    plans = [[fit[p] for p in grp] for grp in plans]

    stats: dict = {"n_keys": n, "n_buckets": len(plans),
                   "n_devices": n_dev, "buckets": [],
                   "greedy": 0, "hard": len(hard), "hb_decided": 0,
                   "constraint_decided": 0}

    # same run pin as the single-device scheduler: prep spans close on
    # the pipeline thread, which must not read the racy process-wide
    # current run (T004)
    run_pin = obs.current_run()

    def prep(idxs: list[int]):
        """Host stage for one bucket — the single-device scheduler's
        prep with the sharded route's two differences: dims start at
        the wide frontier (no escalation ladder on a mesh), and DPOR
        planes are never stripped (the sharded kernel is always XLA,
        never pallas)."""
        t_prep = time.perf_counter()
        with obs.span("shard.prep", cat="host", run=run_pin,
                      keys=len(idxs)):
            ready: dict[int, dict] = {}
            run: list[int] = []
            run_mask: dict[int, dict | None] = {}
            for i in idxs:
                s = seqs[i]
                if lin.greedy_witness(s, model):
                    ready[i] = {"valid": True, "configs": s.n_must,
                                "max_depth": s.n_must,
                                "engine": "greedy-witness",
                                "linearization":
                                    lin.greedy_linearization(s)}
                else:
                    r = mp = None
                    if hb:
                        hbres = maybe_hb(s, model, True, dpor)
                        if hbres is not None and \
                                hbres.decided is not None:
                            r = dict(hbres.decided)
                        elif hbres is not None and hbres.must_pred:
                            mp = hbres.must_pred
                    if r is not None:
                        ready[i] = r
                    else:
                        run.append(i)
                        run_mask[i] = mp
            if not run:
                _M_SHARD_S.observe(time.perf_counter() - t_prep,
                                   stage="prep")
                return ready, run, None, None, None
            dims = lin.batch_dims([ess[i] for i in run], model,
                                  frontier=64)
            if dpor_on:
                for i in run:
                    lin.attach_reductions(ess[i], seqs[i], model,
                                          run_mask.get(i), dedup=True)
            dead_pad = lin.batch_dead_pad([ess[i] for i in run])
            esps = [lin.pad_search(ess[i], dims.n_det_pad,
                                   dims.n_crash_pad,
                                   dead_pad=dead_pad) for i in run]
        _M_SHARD_S.observe(time.perf_counter() - t_prep, stage="prep")
        return ready, run, dims, esps, dead_pad

    useful_total = padded_total = 0
    pad_lanes_total = redo_total = 0
    shard_map_all = True
    device_keys: dict = {}  # device id -> real keys placed there
    run_all: list[int] = []
    if plans:
        ex = ThreadPoolExecutor(max_workers=1,
                                thread_name_prefix="shard-prep")
        try:
            fut = ex.submit(prep, plans[0])
            for b, idxs in enumerate(plans):
                ready, run, dims, esps, dead_pad = fut.result()
                if b + 1 < len(plans):
                    # bucket b+1's host prep overlaps bucket b's mesh
                    # execution below
                    fut = ex.submit(prep, plans[b + 1])
                for i, r in ready.items():
                    results[i] = r
                n_hb = sum(1 for r in ready.values()
                           if r.get("engine") == "hb-decide")
                n_cs = sum(1 for r in ready.values()
                           if r.get("engine") == "constraint-decide")
                stats["hb_decided"] += n_hb
                stats["constraint_decided"] += n_cs
                stats["greedy"] += len(ready) - n_hb - n_cs
                t0 = time.perf_counter()
                info = None
                if run:
                    with obs.span("shard.device", cat="device",
                                  bucket=b, keys=len(run),
                                  shards=n_dev,
                                  dims=[dims.n_det_pad, dims.window,
                                        dims.n_crash_pad]):
                        sub, info = lin._search_batch_sharded_fixed(
                            [seqs[i] for i in run],
                            [ess[i] for i in run], model, dims,
                            sharding, budget, tele_acc=tele_acc,
                            esps=esps, dead_pad=dead_pad)
                    for i, r in zip(run, sub):
                        results[i] = r
                dt = time.perf_counter() - t0
                if run:
                    _M_SHARD_S.observe(dt, stage="device")
                useful = sum(ess[i].n_det + ess[i].n_crash for i in run)
                lanes = info["batch_lanes"] if info else 0
                # mesh-divisibility pad lanes bill into padded_ops
                # (they occupy device rows) even though they never
                # touch configs/occupancy counters
                padded = lanes * (dims.n_det_pad + dims.n_crash_pad) \
                    if run else 0
                useful_total += useful
                padded_total += padded
                if info:
                    pad_lanes_total += info["pad_lanes"]
                    redo_total += info["overflow_redo"]
                    shard_map_all &= info["shard_map"]
                    for d, k in info["device_keys"].items():
                        device_keys[d] = device_keys.get(d, 0) + k
                run_all += run
                stats["buckets"].append({
                    "dims": ([dims.n_det_pad, dims.window,
                              dims.n_crash_pad] if run else None),
                    "n_keys": len(idxs), "searched": len(run),
                    "lanes": lanes,
                    "pad_lanes": info["pad_lanes"] if info else 0,
                    "useful_ops": useful, "padded_ops": padded,
                    "padding_efficiency": (round(useful / padded, 4)
                                           if padded else None),
                    "seconds": round(dt, 3)})
        finally:
            ex.shutdown(wait=True)
    if hard:
        from .linear import check_opseq_linear

        for i in hard:
            s = seqs[i]
            if lin.greedy_witness(s, model):
                results[i] = {"valid": True, "configs": s.n_must,
                              "max_depth": s.n_must,
                              "engine": "greedy-witness",
                              "linearization": lin.greedy_linearization(s)}
                stats["greedy"] += 1
                continue
            r = check_opseq_linear(seqs[i], model, lint=False, hb=hb,
                                   dpor=dpor)
            r["engine"] = "host-linear(fallback)"
            results[i] = r
    # the fused-shape counterfactual over the SAME device-ridden keys:
    # one batch at global max dims, rounded up to cover the mesh once
    fused_padded = 0
    if run_all:
        fdims = lin.batch_dims([ess[i] for i in run_all], model,
                               frontier=64)
        fused_padded = lin._round_up(len(run_all), n_dev) \
            * (fdims.n_det_pad + fdims.n_crash_pad)
    kc1 = lin.kernel_cache_stats()
    if useful_total or padded_total:
        _M_SHARD_OPS.inc(useful_total, kind="useful")
        _M_SHARD_OPS.inc(padded_total, kind="padded")
    stats.update({
        "useful_ops": useful_total,
        "padded_ops": padded_total,
        "pad_keys": pad_lanes_total,
        "overflow_redo": redo_total,
        "shard_map": shard_map_all if run_all else None,
        "device_keys": device_keys,
        "padding_efficiency": (round(useful_total / padded_total, 4)
                               if padded_total else None),
        "fused_padded_ops": fused_padded or None,
        "fused_padding_efficiency": (round(useful_total / fused_padded,
                                           4) if fused_padded else None),
        "kernel_cache": {k: kc1[k] - kc0[k] for k in kc1},
        "seconds": round(time.perf_counter() - t_start, 3),
    })
    if tele_acc is not None and results and results[0] is not None:
        _tele.finalize_result(results[0], tele_acc)
    if results:
        results[0].setdefault("shard_batch", stats)
    return results


# ---------------------------------------------------------------------------
# kernel route registration — the bucket scheduler's half of the
# device-contract enumeration (see linearizable.KernelRoute)
# ---------------------------------------------------------------------------

from . import linearizable as _lin  # noqa: E402

_lin.register_route(_lin.KernelRoute(
    name="bucketed-batch", engine="xla", span_kind="batch",
    getter="get_batch_kernel", module=_lin.__name__,
    build=_lin._build_batch, request=_lin._request_batch,
    batched=True))
_lin.register_route(_lin.KernelRoute(
    name="mesh-sharded", engine="xla", span_kind="batch-sharded",
    getter="get_sharded_batch_kernel", module=_lin.__name__,
    build=_lin._build_sharded, request=_lin._request_sharded,
    batched=True, sharded=True))
