"""Search-plan explainer — predict the search without paying for it.

Everything the live engines decide host-side before (or instead of)
dispatching the exponential search is derivable from one cheap scan:
concurrency width, the real-time window, crash-word count, the quantized
``SearchDims``, the shape bucket, the engine route, and which
P-compositional decompositions (arXiv:1504.00204) apply.  :func:`explain`
computes all of it statically — the dry-run cost model the ROADMAP's
"measure bucketing on a real TPU window" item needs — and
:func:`explain_batch` does the same for a batch, mirroring the bucketed
scheduler's plan.

Prediction = implementation: this module calls the engines' OWN
primitives (``encode_search``, ``choose_dims``, ``batch_dims``,
``bucket_key``, ``plan_buckets``, ``greedy_witness``) rather than
re-deriving them, and the decomposition applicability gates
(:func:`key_partition_applies`, :func:`value_block_gate`,
:func:`quiescence_cuts`) live HERE and are consumed by
``decompose/partition.py`` — so the plan a user reads is by construction
the plan the engines execute (verified against recorded run stats in
tests/test_analyze.py).
"""

from __future__ import annotations

import numpy as np

from ..history import NIL, OpSeq, encode_ops
from ..models import R_READ, R_WRITE, ModelSpec

# ---------------------------------------------------------------------------
# Decomposition applicability gates — the ONE home (partition.py consumes)
# ---------------------------------------------------------------------------


def key_partition_applies(model: ModelSpec) -> bool:
    """Herlihy–Wing locality applies to the multi-register model: each
    key's projection checks independently as a single register."""
    return model.name == "multi-register"


def value_block_gate(seq: OpSeq, model: ModelSpec):
    """Eligibility gate for the per-value block decomposition.

    Returns ``(applies, reason, writes)``: ``reason`` names the first
    disqualifier when ``applies`` is False; ``writes`` maps value ->
    writing row (the scan's byproduct, reused by
    ``partition.value_block_verdict`` so gate and verdict cannot
    diverge).

    Eligible class (partition.py's docstring, the P-compositionality
    instance for registers): single-register model, every row :ok, only
    read/write ops, every written value distinct and distinct from the
    initial value.
    """
    if model.name not in ("register", "cas-register"):
        return False, f"model {model.name!r} is not a single register", None
    if not bool(np.asarray(seq.ok).all()):
        return False, "crashed (:info) rows present", None
    n = len(seq)
    if n == 0:
        return True, None, {}
    f = np.asarray(seq.f)
    if not bool(np.isin(f, (R_READ, R_WRITE)).all()):
        return False, "non-read/write ops (cas or foreign codes)", None
    v1 = np.asarray(seq.v1)
    init = int(model.init[0])
    writes: dict[int, int] = {}  # value -> row
    for i in np.nonzero(f == R_WRITE)[0]:
        v = int(v1[i])
        if v == NIL:
            return False, "write of NIL", None
        if v == init:
            return False, "write of the initial value", None
        if v in writes:
            return False, f"duplicate write of value {v}", None
        writes[v] = int(i)
    return True, None, writes


def quiescence_cuts(seq: OpSeq) -> np.ndarray:
    """Row indices where a quiescence cut lands (segment STARTS, 0
    excluded): every op before row i returned before row i invokes
    (``max(ret[..i-1]) < inv[i]``).  A crashed row's +inf return
    suppresses every later cut.  Consumed by
    ``partition.quiescence_segments`` (row ranges) and the plan."""
    n = len(seq)
    if n <= 1:
        return np.zeros(0, dtype=np.int64)
    inv = np.asarray(seq.inv, dtype=np.int64)
    ret = np.asarray(seq.ret, dtype=np.int64)
    run_max = np.maximum.accumulate(ret)
    return np.nonzero(run_max[:-1] < inv[1:])[0] + 1


# ---------------------------------------------------------------------------
# Streaming applicability gate — the ONE home (stream/checker.py consumes)
# ---------------------------------------------------------------------------

#: model families whose segment folds can ride the device batch path
#: (stream/device.py's pseudo-write/pseudo-read state pinning needs a
#: single-value register)
STREAM_DEVICE_FAMILIES = ("register", "cas-register")

#: host-fold cost-proxy cap: a closed segment predicted past this folds
#: on the device batch path instead of the host sweep
STREAM_HOST_FOLD_MAX = 1 << 22


#: bounded `:info` lookahead — after this many post-crash :ok rows
#: accumulate at a pseudo-quiescent point, the stream runs a
#: speculative fork check (the `:info` op present at each frontier
#: position vs absent) so a kill-seeded violation flips the live
#: verdict mid-stream instead of at finalize.  0 disables (finalize-
#: only, the pre-lookahead behavior).
STREAM_INFO_LOOKAHEAD = 16

#: the legacy flat fork cap: past this many pending `:info` ops the
#: speculative check used to be skipped unconditionally — bounding what
#: the uncertain ops can do is what keeps the search online
#: (Parsimonious Optimal DPOR's point, arXiv:2405.11128); the verdict
#: still lands exactly at finalize.  Kept as the characteristic scale
#: the cost budget below is seeded from (6 pending infos over a
#: 64-row segment), and as the width-free predicate
#: :func:`info_fork_gate` still answers.
STREAM_INFO_FORK_MAX = 6

#: the cost budget the stream engine actually executes now: a fork
#: check is admitted while ``n_infos * (segment_rows + 1)`` stays under
#: this.  Seeded at STREAM_INFO_FORK_MAX x a 64-row characteristic
#: segment, so the old flat cap is recovered at that width while a
#: narrow crashed cell affords MORE pending infos and a wide one fewer
#: — the fork's host sub-search sweeps the whole open segment once per
#: carried state per placement, so infos x rows is its first-order
#: cost, not infos alone.
STREAM_INFO_FORK_BUDGET = STREAM_INFO_FORK_MAX * 64

#: absolute `:info` ceiling regardless of segment width: the
#: sub-search's crash dimension is padded in 32-lane words and capped
#: at 64 (checker.linearizable.MAX_CRASH); forking past what the
#: device path could even represent buys nothing
STREAM_INFO_FORK_HARD_MAX = 32


def info_fork_cost(n_infos: int, segment_rows: int) -> int:
    """The speculative fork check's cost proxy: pending `:info` count
    times the open segment's row count (+1 so an empty segment still
    prices each info).  The single number the budget gate compares."""
    return max(0, n_infos) * (max(0, segment_rows) + 1)


def info_fork_budget(n_infos: int, segment_rows: int, *,
                     budget: int | None = None) -> bool:
    """May the stream speculatively fork ``n_infos`` pending `:info`
    ops over a ``segment_rows``-row open segment?  The cost-model
    replacement for the old flat :func:`info_fork_gate` cap — THE rule
    the stream engine executes and :func:`stream_plan` predicts: small
    segments afford more pending infos, wide ones fewer, with
    :data:`STREAM_INFO_FORK_HARD_MAX` as the absolute ceiling."""
    cap = STREAM_INFO_FORK_BUDGET if budget is None else budget
    if not 0 < n_infos <= STREAM_INFO_FORK_HARD_MAX:
        return False
    return info_fork_cost(n_infos, segment_rows) <= cap


def info_fork_gate(n_infos: int, *, fork_max: int | None = None) -> bool:
    """The legacy width-free predicate: may the stream fork this many
    pending `:info` ops at the characteristic segment width?  Callers
    that know their segment width should use :func:`info_fork_budget`;
    this remains for width-free prediction surfaces."""
    cap = STREAM_INFO_FORK_MAX if fork_max is None else fork_max
    return 0 < n_infos <= cap


def segment_fold_cost(n_rows: int, window: int) -> int:
    """The host fold's cost proxy for one crash-free segment: rows times
    the window-bounded interleaving factor (``segment_states`` is the
    level-synchronous sweep, whose frontier is bounded by 2^(window-1)
    per prefix position)."""
    return (n_rows + 1) << min(max(window - 1, 0), 40)


def segment_fold_route(n_rows: int, window: int, model: ModelSpec, *,
                       host_fold_max: int | None = None) -> str:
    """``"host"`` or ``"device"`` for one closed streaming segment.

    The single routing rule the stream engine executes and
    :func:`stream_plan` predicts: device dispatch needs the register
    family (the state-pinning trick) AND a predicted host-fold cost
    past the cap; everything else folds on host."""
    if model.name not in STREAM_DEVICE_FAMILIES:
        return "host"
    cap = STREAM_HOST_FOLD_MAX if host_fold_max is None else host_fold_max
    return "device" if segment_fold_cost(n_rows, window) > cap else "host"


def stream_plan(seq: OpSeq, model: ModelSpec, *,
                host_fold_max: int | None = None,
                info_lookahead: int | None = None) -> dict:
    """The streaming-applicability gate: would the incremental checker
    (jepsen_tpu/stream/) pay off on this history, and how would it
    route?  Predicts quiescence-cut density, expected segment sizes,
    rows until the first closed segment (the time-to-first-verdict
    proxy), and the host-fold vs device-dispatch split — using the SAME
    cut primitive (:func:`quiescence_cuts`) and the SAME routing rule
    (:func:`segment_fold_route`) the stream engine executes, so the
    prediction cannot drift from the fold."""
    from ..decompose.partition import partition_by_key, subseq
    from ..history import max_concurrency

    cells_map, cell_model, early = (None, model, None)
    if key_partition_applies(model):
        cells_map, cell_model, early = partition_by_key(seq, model)
    cells = list(cells_map.values()) if cells_map else [seq]
    if cell_model is None:
        cell_model = model

    horizon = STREAM_INFO_LOOKAHEAD if info_lookahead is None \
        else max(0, int(info_lookahead))
    seg_rows: list[int] = []
    routes = {"host": 0, "device": 0}
    ttfv_rows = None
    crashed_cells = info_rows = spec_checks = 0
    forkable = True
    fork_cost_max = 0
    for cseq in cells:
        n = len(cseq)
        if n == 0:
            continue
        cuts = quiescence_cuts(cseq)
        bounds = [0, *cuts.tolist(), n]
        infos = int((~cseq.ok).sum())
        if infos:
            crashed_cells += 1
            info_rows += infos
            # the fork check sweeps the cell's OPEN segment (rows past
            # the last quiescence cut) — the budget's width term, and
            # the same basis the engine uses (its cell buffer holds
            # exactly the un-folded tail)
            open_rows = bounds[-1] - bounds[-2]
            fork_cost_max = max(fork_cost_max,
                                info_fork_cost(infos, open_rows))
            if not info_fork_budget(infos, open_rows):
                forkable = False
            elif horizon:
                # one speculative fork check per horizon's worth of
                # post-crash ok rows — the same counting basis the
                # stream engine uses (it counts post-crash ok
                # COMPLETIONS; statically, ok rows after the first
                # crash row approximate that)
                first = int(np.argmax(~cseq.ok))
                spec_checks += int(cseq.ok[first:].sum()) // horizon
        if len(cuts) and (ttfv_rows is None or int(cuts[0]) < ttfv_rows):
            ttfv_rows = int(cuts[0])
        for i in range(len(bounds) - 1):
            rows = bounds[i + 1] - bounds[i]
            seg_rows.append(rows)
            if i < len(bounds) - 2:  # closed segments fold mid-stream
                w = max_concurrency(
                    subseq(cseq, np.arange(bounds[i], bounds[i + 1])))
                routes[segment_fold_route(
                    rows, w, cell_model,
                    host_fold_max=host_fold_max)] += 1
    n_cells = max(1, len(cells))
    n_rows = max(1, len(seq))
    closed = sum(routes.values())
    return {
        "applies": closed > 0 and early is not False,
        "cells": n_cells,
        "segments": len(seg_rows),
        "closed_segments": closed,
        "cut_density": round(closed / n_rows, 4),
        "expected_segment_rows": {
            "mean": round(sum(seg_rows) / len(seg_rows), 2)
            if seg_rows else 0,
            "max": max(seg_rows) if seg_rows else 0,
        },
        "ttfv_rows": ttfv_rows,
        "routes": routes,
        "device_eligible": cell_model.name in STREAM_DEVICE_FAMILIES,
        "info_lookahead": {
            "horizon": horizon,
            "fork_max": STREAM_INFO_FORK_MAX,
            "fork_budget": STREAM_INFO_FORK_BUDGET,
            "fork_cost_max": fork_cost_max,
            "crashed_cells": crashed_cells,
            "info_rows": info_rows,
            "forkable": forkable,
            "speculative_checks": spec_checks,
        },
    }


def independent_keys(seq: OpSeq, model: ModelSpec):
    """Detect a jepsen.independent ``[k v]`` composite history encoded
    under a single-register model — the shape every keyed live family
    (pgwire, replicated, kv) records.

    ``encode_ops``'s default lanes split a pair value across (v1, v2),
    so a register WRITE row carrying a second lane can only be a keyed
    write (a plain register write never uses v2; cas rows legitimately
    do and are ignored here).  Returns the sorted key list when
    detected, else None.  Consumers (``explain``, the analyze CLI) use
    it to report the per-key demux route — the route
    ``independent.checker`` and the stream checker's independent mode
    actually execute — instead of mis-reading key lanes as values.
    """
    if model.name not in ("register", "cas-register"):
        return None
    f = np.asarray(seq.f)
    writes = f == R_WRITE
    if not bool(writes.any()):
        return None
    v2 = np.asarray(seq.v2)
    if not bool((v2[writes] != NIL).all()):
        return None
    v1 = np.asarray(seq.v1)
    keyed = np.isin(f, (R_READ, R_WRITE)) & (v1 != NIL)
    return sorted(int(k) for k in np.unique(v1[keyed]))


def schedule_weight(seq: OpSeq) -> int:
    """The cell schedulers' cost proxy (largest-first ordering in
    decompose/schedule.py's host pool and device batch).

    Row count — finer-grained than the bucket quantization's padded
    rows (``bucket_key`` rounds n_det to a power of two, so many cells
    tie) while strictly monotone with it; one home so the schedulers
    and the plan explainer rank cells identically."""
    return len(seq)


# ---------------------------------------------------------------------------
# The plan
# ---------------------------------------------------------------------------


def _dims_dict(dims) -> dict:
    return {"n_det_pad": dims.n_det_pad, "n_crash_pad": dims.n_crash_pad,
            "window": dims.window, "k": dims.k,
            "state_width": dims.state_width, "frontier": dims.frontier}


def _decompositions(seq: OpSeq, model: ModelSpec) -> dict:
    """Which decompositions the engine (decompose/engine.py's funnel)
    would apply, in funnel order: key partition -> per cell: value
    blocks -> quiescence cuts."""
    from ..decompose.partition import partition_by_key

    out: dict = {}
    cells_map = None
    cell_model = model
    if key_partition_applies(model):
        cells_map, cell_model, early = partition_by_key(seq, model)
        out["key_partition"] = {
            "applies": True,
            "cells": len(cells_map) if cells_map else 0,
            "early_verdict": early,
        }
        if early is False or not cells_map:
            out["value_blocks"] = {"applies": False,
                                   "reason": "decided by key partition"}
            out["quiescence"] = {"applies": False, "segments": 1}
            return out
    else:
        out["key_partition"] = {"applies": False,
                                "reason": f"model {model.name!r} is not "
                                          f"multi-register"}
    cells = list(cells_map.values()) if cells_map else [seq]

    vb_cells = 0
    vb_reason = None
    segs_total = 0
    cut_cells = 0
    for cseq in cells:
        applies, reason, _writes = value_block_gate(cseq, cell_model)
        if applies:
            vb_cells += 1
        elif vb_reason is None:
            vb_reason = reason
        nsegs = len(quiescence_cuts(cseq)) + 1
        segs_total += nsegs
        if nsegs > 1:
            cut_cells += 1
    out["value_blocks"] = {"applies": vb_cells > 0,
                           "eligible_cells": vb_cells}
    if vb_reason is not None:
        out["value_blocks"]["reason"] = vb_reason
    out["quiescence"] = {"applies": segs_total > len(cells),
                         "segments": segs_total,
                         "cells_with_cuts": cut_cells}
    return out


def _telemetry_block(engine: str) -> dict:
    """How this plan's PREDICTIONS become observations: whether the
    device telemetry layer (obs/telemetry.py) is on, and where its
    observed twin of the hb/dpor predicted prune ratios will land.
    Plans are predictions; a run of the predicted engine attaches the
    measured side, and the two are diffed everywhere downstream
    (result block, trace_report)."""
    from ..obs import telemetry as tele

    on = tele.enabled()
    out = {"enabled": on}
    if on:
        out["observed_at"] = (
            "search_telemetry.observed_prune_ratio on device results "
            "(prune_ratio_delta vs the predicted ratio above)"
            if engine == "device-bfs" else
            "search.telemetry trace span (observed=0 for a "
            "statically decided / host-routed history)")
    else:
        out["note"] = ("JEPSEN_TPU_TELEMETRY=0: predictions will not "
                       "be observable on results")
    return out


def explain(history, model: ModelSpec, *,
            frontier: int | None = None,
            host_threshold: int = 48) -> dict:
    """The static plan for ONE history: what the live engines would do.

    ``history`` is an event-level Op list or an encoded OpSeq.
    ``host_threshold`` mirrors ``Linearizable``'s small-history host
    routing; ``frontier`` pins the initial frontier as
    ``choose_dims`` would accept it.
    """
    from ..checker import linearizable as lin
    from ..checker.bucket import bucket_key

    seq = history if isinstance(history, OpSeq) else \
        encode_ops(history, model.f_codes)
    es = lin.encode_search(seq)
    dims = lin.choose_dims(es, model, frontier=frontier)

    greedy = lin.greedy_witness(seq, model)
    device_ok = (es.window <= lin.MAX_WINDOW
                 and es.n_crash <= lin.MAX_CRASH)
    if es.n_det == 0 and es.n_crash == 0:
        engine = "trivial"
    elif greedy:
        engine = "greedy-witness"
    elif not device_ok:
        engine = "host-linear(fallback)"
    else:
        engine = "device-bfs"

    # distinct reachable configs, model state EXCLUDED: det prefix
    # position x window mask (the first window bit is the prefix
    # boundary itself) x crash mask — the count the frontier + budget
    # must cover in the worst case
    ub_log2 = (max(0, es.window - 1) + es.n_crash)
    upper = (es.n_det + 1) << ub_log2

    from .constraints import plan_block as constraints_block
    from .dpor import plan_block as dpor_block
    from .hb import analyze_hb, plan_block

    # one HB solve shared by the hb and dpor blocks below
    hbres = analyze_hb(seq, model) if len(seq) else None

    # keyed-composite gate (the live pgwire/replicated/kv families):
    # a [k v] history under a register model routes per key — every
    # whole-history prediction below would mis-read key lanes as
    # values, so the plan says so instead of falling through
    ind = independent_keys(seq, model)
    independent = {"detected": ind is not None}
    if ind is not None:
        independent.update({
            "keys": len(ind),
            "route": "per-key demux (independent.checker post-hoc; "
                     "stream independent mode live)",
            "note": "whole-history dims/decomposition/hb predictions "
                    "below do not apply to a keyed composite — demux "
                    "first, then explain each key's subhistory",
        })

    return {
        "model": model.name,
        "independent": independent,
        "n_rows": len(seq),
        "n_det": es.n_det,
        "n_crash": es.n_crash,
        "window": es.window,
        "concurrency": es.concurrency,
        "crash_words": dims.crash_words,
        "config_words": dims.words,
        "search_dims": _dims_dict(dims),
        "bucket": list(bucket_key(es)),
        "greedy_witness": greedy,
        "device_eligible": device_ok,
        "host_threshold_route": len(seq) <= host_threshold,
        "engine": engine,
        "config_upper_bound": upper,
        "config_upper_bound_log2": round(
            ub_log2 + float(np.log2(max(1, es.n_det + 1))), 2),
        "hb": plan_block(seq, model, upper, es.n_crash, es.window,
                         hb_analysis=hbres),
        "constraints": constraints_block(seq, model),
        "dpor": dpor_block(seq, model, upper, hb_analysis=hbres),
        "decompositions": _decompositions(seq, model),
        "streaming": stream_plan(seq, model),
        "telemetry": _telemetry_block(engine),
    }


def explain_batch(seqs: list[OpSeq], model: ModelSpec, *,
                  hb: bool | None = None,
                  n_devices: int | None = None) -> dict:
    """The static plan for a BATCH: per-key routing plus the bucketed
    scheduler's exact bucket assignment (checker/bucket.py's
    ``plan_buckets`` over the same keys, merged to the same cap).

    Mirrors ``search_batch_bucketed``: greedy witnesses dispose keys
    host-side, window/crash outliers fall back to the host sweep, and
    the rest group into power-of-two dims buckets, each searched at its
    own tight dims.

    ``n_devices`` switches the mirror to the MESH scheduler
    (``search_batch_sharded_bucketed`` over that many devices): dims
    start at the wide frontier, every bucket's lane count rounds up to
    mesh divisibility (the inert pad lanes bill into ``padded_ops``
    exactly as the live ``shard_batch`` stats bill them), and the
    totals carry the fused single-shape counterfactual — so the
    prediction is field-for-field comparable with the stats the live
    run reports.
    """
    from ..checker import linearizable as lin
    from ..checker.bucket import _bucket_mode, bucket_key, plan_buckets

    ess = [lin.encode_search(s) for s in seqs]
    hard, fit = [], []
    for i, e in enumerate(ess):
        (hard if e.window > lin.MAX_WINDOW
         or e.n_crash > lin.MAX_CRASH else fit).append(i)
    _enabled, max_buckets = _bucket_mode()
    plans = plan_buckets([bucket_key(ess[i]) for i in fit], max_buckets)
    plans = [[fit[p] for p in grp] for grp in plans]

    greedy = [i for i in range(len(seqs))
              if lin.greedy_witness(seqs[i], model)]
    greedy_set = set(greedy)
    # the static prepass disposes decided keys next to the greedy
    # witness (checker/bucket.py's prep stage) — mirror the split
    # exactly, including the per-call flag resolution AND the solver
    # dispatch (HB for registers, the constraint compiler for
    # queue/lock families), so the predicted per-bucket dims match the
    # scheduler's under any hb setting
    from .constraints import analyze_prepass, family_of
    from .hb import resolve_hb

    hb_set: set[int] = set()
    constraint_set: set[int] = set()
    # HB-solver analyses kept for the dpor block below (one solve per
    # key, not one per block); constraint-family analyses don't fit
    # its HBAnalysis shape and are cheap for it to skip
    analyses: dict[int, object] = {}
    hb_solver = family_of(model) is None
    if resolve_hb(hb):
        for i in range(len(seqs)):
            if i in greedy_set:
                continue
            a = analyze_prepass(seqs[i], model)
            if hb_solver:
                analyses[i] = a
            if a.decided is not None:
                (constraint_set
                 if a.stats.get("solver") == "constraints"
                 else hb_set).add(i)
    disposed = greedy_set | hb_set | constraint_set
    # the dpor block, batch form — SAME primitive as explain()'s
    # (dpor.plan_block per undecided key), aggregated: what the device
    # planes will mask, what the dead-value dedup should collapse, and
    # the sleep-set bound the host legs would carry
    from .dpor import plan_block as dpor_block

    dpor_keys = [i for i in range(len(seqs)) if i not in disposed]
    per_key = [dpor_block(seqs[i], model,
                          (ess[i].n_det + 1)
                          << (max(0, ess[i].window - 1)
                              + ess[i].n_crash),
                          hb_analysis=analyses.get(i))
               for i in dpor_keys]
    dedup_rates = [b["dedup"].get("hit_rate_prediction", 0.0)
                   for b in per_key if b["dedup"].get("applies")]
    dpor_plan = {
        "enabled": per_key[0]["enabled"] if per_key else True,
        "keys": len(dpor_keys),
        "masked_keys": sum(1 for b in per_key if b["masked_rows"]),
        "dedup_keys": sum(1 for b in per_key
                          if b["dedup"].get("applies")),
        "dup_edges": sum(b["dup_edges"] for b in per_key),
        "mask_coverage": (round(sum(b["mask_coverage"]
                                    for b in per_key)
                                / len(per_key), 4) if per_key else 0.0),
        "dedup_hit_rate_prediction": (round(sum(dedup_rates)
                                            / len(dedup_rates), 4)
                                      if dedup_rates else 0.0),
        "sleep_set_bound": max((b["sleep_set_bound"]
                                for b in per_key), default=0),
    }
    frontier = 64 if n_devices else 32
    buckets = []
    useful_total = padded_total = 0
    run_all: list[int] = []
    for idxs in plans:
        run = [i for i in idxs if i not in disposed]
        dims = (lin.batch_dims([ess[i] for i in run], model,
                               frontier=frontier)
                if run else None)
        useful = sum(ess[i].n_det + ess[i].n_crash for i in run)
        lanes = (lin._round_up(len(run), n_devices)
                 if run and n_devices else len(run))
        padded = (lanes * (dims.n_det_pad + dims.n_crash_pad)
                  if run else 0)
        useful_total += useful
        padded_total += padded
        run_all += run
        bk = {
            "keys": idxs,
            "n_keys": len(idxs),
            "searched": len(run),
            "dims": ([dims.n_det_pad, dims.window, dims.n_crash_pad]
                     if run else None),
            "useful_ops": useful,
            "padded_ops": padded,
            "padding_efficiency": (round(useful / padded, 4)
                                   if padded else None),
        }
        if n_devices:
            bk["lanes"] = lanes if run else 0
            bk["pad_lanes"] = (lanes - len(run)) if run else 0
        buckets.append(bk)
    out = {
        "n_keys": len(seqs),
        "n_buckets": len(plans),
        "bucketing": _enabled,
        "greedy": len(greedy),
        "hb_decided": len(hb_set),
        "constraint_decided": len(constraint_set),
        "hard": len(hard),
        "hard_keys": hard,
        "dpor": dpor_plan,
        "buckets": buckets,
    }
    if n_devices:
        fused_padded = 0
        if run_all:
            fdims = lin.batch_dims([ess[i] for i in run_all], model,
                                   frontier=frontier)
            fused_padded = lin._round_up(len(run_all), n_devices) \
                * (fdims.n_det_pad + fdims.n_crash_pad)
        out.update({
            "n_devices": n_devices,
            "useful_ops": useful_total,
            "padded_ops": padded_total,
            "padding_efficiency": (round(useful_total / padded_total,
                                         4) if padded_total else None),
            "fused_padded_ops": fused_padded or None,
            "fused_padding_efficiency": (
                round(useful_total / fused_padded, 4)
                if fused_padded else None),
        })
    return out


def _log2(x) -> float:
    return round(float(np.log2(max(1, int(x or 0)))), 1)


def render_plan(plan: dict, *, batch: bool = False) -> str:
    """Human-readable plan (the CLI --explain output)."""
    lines = []
    if batch or "buckets" in plan:
        lines.append(f"batch plan: {plan['n_keys']} keys -> "
                     f"{plan['n_buckets']} bucket(s), "
                     f"{plan['greedy']} greedy-disposed, "
                     f"{plan.get('hb_decided', 0)} hb-decided, "
                     f"{plan.get('constraint_decided', 0)} "
                     f"constraint-decided, "
                     f"{plan['hard']} host-fallback")
        if plan.get("n_devices"):
            lines.append(
                f"  sharded over {plan['n_devices']} device(s): "
                f"padding_efficiency={plan.get('padding_efficiency')} "
                f"(fused counterfactual "
                f"{plan.get('fused_padding_efficiency')})")
        dp = plan.get("dpor")
        if dp:
            lines.append(
                f"  dpor: {'on' if dp.get('enabled') else 'OFF'}; "
                f"{dp.get('masked_keys', 0)}/{dp.get('keys', 0)} keys "
                f"device-masked ({dp.get('dup_edges', 0)} dup edges), "
                f"{dp.get('dedup_keys', 0)} dedup-eligible "
                f"(predicted hit-rate "
                f"{dp.get('dedup_hit_rate_prediction')}), sleep-set "
                f"bound {dp.get('sleep_set_bound')}")
        for b, bk in enumerate(plan["buckets"]):
            dims = bk["dims"]
            eff = bk["padding_efficiency"]
            lines.append(
                f"  bucket {b}: {bk['n_keys']} keys, {bk['searched']} "
                f"searched, dims={dims}, padding_efficiency={eff}")
        return "\n".join(lines)
    d = plan["search_dims"]
    lines += [
        f"plan: {plan['n_rows']} rows ({plan['n_det']} det, "
        f"{plan['n_crash']} crashed) under model {plan['model']!r}",
        f"  window={plan['window']} concurrency={plan['concurrency']} "
        f"crash_words={plan['crash_words']} "
        f"config_words={plan['config_words']}",
        f"  SearchDims: n_det_pad={d['n_det_pad']} "
        f"n_crash_pad={d['n_crash_pad']} window={d['window']} "
        f"k={d['k']} frontier={d['frontier']}",
        f"  bucket={tuple(plan['bucket'])} engine={plan['engine']}"
        + (" (greedy witness exists)" if plan["greedy_witness"] else ""),
        f"  config upper bound ~2^"
        f"{plan['config_upper_bound_log2']}",
    ]
    dec = plan["decompositions"]
    kp = dec["key_partition"]
    vb = dec["value_blocks"]
    qc = dec["quiescence"]
    lines.append(
        "  decompositions: key-partition "
        + (f"applies ({kp.get('cells')} cells)" if kp["applies"]
           else "n/a")
        + "; value-blocks "
        + ("applies" if vb["applies"]
           else f"n/a ({vb.get('reason', '')})")
        + "; quiescence "
        + (f"applies ({qc['segments']} segments)" if qc["applies"]
           else "n/a"))
    ind = plan.get("independent")
    if ind and ind.get("detected"):
        lines.append(
            f"  KEYED COMPOSITE: {ind['keys']} independent key(s) — "
            f"engines route {ind['route']}; whole-history predictions "
            f"below are the un-demuxed counterfactual")
    hb = plan.get("hb")
    if hb:
        if not hb.get("applies"):
            line = f"n/a ({hb.get('reason')})"
        elif hb.get("decided") is not None:
            line = (f"DECIDES this history "
                    f"({'valid' if hb['decided'] else 'invalid'} via "
                    f"{hb.get('reason')}; no search needed)")
        else:
            line = (f"undecided; {hb.get('must_edges', 0)} must-order "
                    f"edge(s) {hb.get('edges')}, pruned bound "
                    f"~2^{_log2(hb.get('pruned_upper_bound', 0))} of "
                    f"raw ~2^{_log2(plan.get('config_upper_bound', 0))}"
                    f" (ratio {hb.get('prune_ratio')})")
        lines.append("  happens-before: " + line)
    cs = plan.get("constraints")
    if cs and cs.get("applies"):
        if cs.get("decided") is not None:
            line = (f"DECIDES this history "
                    f"({'valid' if cs['decided'] else 'invalid'} via "
                    f"{cs.get('reason')}; no search needed)")
        else:
            line = (f"undecided; {cs.get('must_edges', 0)} must-order "
                    f"edge(s) {cs.get('edges')}")
        sf = cs.get("stream_fold") or {}
        if sf.get("eligible"):
            line += f"; streamed fold route: {sf.get('route')}"
        lines.append(f"  constraints[{cs.get('family')}]: " + line)
    dp = plan.get("dpor")
    if dp:
        dd = dp.get("dedup", {})
        lines.append(
            f"  dpor: {'on' if dp.get('enabled') else 'OFF'}; "
            f"{dp.get('dup_edges', 0)} duplicate-op edge(s), "
            f"device-mask coverage {dp.get('mask_coverage')} "
            f"({dp.get('masked_rows', 0)} rows), dedup "
            + (f"applies ({dd.get('dead_values')}/{dd.get('values')} "
               f"values die; predicted hit-rate "
               f"{dd.get('hit_rate_prediction')})"
               if dd.get("applies") else "n/a")
            + f", sleep-set bound {dp.get('sleep_set_bound')}, "
              f"pruned bound ~2^"
              f"{_log2(dp.get('pruned_upper_bound', 0))}")
    tl = plan.get("telemetry")
    if tl:
        lines.append(
            "  telemetry: "
            + (f"on — observed at {tl.get('observed_at')}"
               if tl.get("enabled") else f"off ({tl.get('note')})"))
    st = plan.get("streaming")
    if st:
        lines.append(
            "  streaming: "
            + ("applies" if st["applies"] else "n/a")
            + f" ({st['closed_segments']} closed segment(s), cut "
              f"density {st['cut_density']}, ttfv ~{st['ttfv_rows']} "
              f"rows, routes {st['routes']})")
    return "\n".join(lines)
