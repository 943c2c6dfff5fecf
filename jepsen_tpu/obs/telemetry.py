"""Device-search telemetry — the aux counter block that opens the
device black box.

The BFS kernels (checker/linearizable.py: single-device, bucketed
batch, mesh-sharded; checker/pallas_level.py: the fused level loop)
run dozens-to-thousands of levels per bounded ``device.slice`` call,
and until this module the slice span was the *finest* observable unit:
total wall time, nothing about what the kernel did inside.  The
hb/dpor prune ratios that ``explain()`` *predicts* were therefore
never *observed*, and per-level frontier dynamics (the input every
remaining ROADMAP perf item needs) existed only as anecdotes.

The fix is GPUexplore's lesson (arXiv:1801.05857 — an accelerated
search is trustworthy when its progress is cheaply externally
checkable) applied to our own kernels, the way ScalaBFS
(arXiv:2105.11754) meters per-PE occupancy per level: each telemetry-
built kernel carries a small packed **aux counter block** — one int32
row per BFS level — through the slice loop and returns it next to the
search carry.  The block costs a handful of vector-sum ops per level
(near-zero against the mask/prune work) and NEVER feeds back into the
search: verdicts are byte-identical with telemetry on or off
(differential-fuzzed in tests/test_telemetry.py).

Aux block schema (``TELE_ROWS`` x ``TELE_COLS`` int32, row = one
level, additive — the final row aggregates any levels past the
buffer):

  col 0  occupancy     live frontier rows after the level's crash
                       closure (the width the det expansion actually
                       ran at — closure can merge crash successors in
                       above the entry count)
  col 1  expanded      valid candidate lanes (post-mask, post-closure)
  col 2  mask_killed   candidate lanes killed by the hb/dpor
                       must-order mask (0 when the search is unmasked)
  col 3  dedup_folds   successor states rewritten onto the dead-value
                       canonical token (0 when dedup is off)
  col 4  crash_rounds  crash-closure iterations the level ran
  col 5  next_count    rows surviving the dominance prune into the
                       next level
  col 6  overflow      1 iff this level newly overflowed (bailed
                       levels appear with overflow=1 and are re-run
                       wider — expect a duplicate row after escalation)
  col 7  goal          1 iff a goal configuration was found

Host side, :class:`SearchTelemetry` accumulates rows across slices,
emits ``device.level`` child spans under each ``device.slice`` (wall
time apportioned by occupancy — tracing-gated), feeds the
``jtpu_search_*`` registry metrics, and produces the
``search_telemetry`` result block whose ``observed_prune_ratio`` is
directly comparable against the prepass's *predicted* ``prune_ratio``
(``predicted_prune_ratio`` / ``prune_ratio_delta`` ride the block and
the ``search.telemetry`` span, which is what ``tools/trace_report.py``
reads out of a recorded trace).

Knob: ``JEPSEN_TPU_TELEMETRY`` (default ON; ``0``/``off`` disables,
the CLI's ``--no-telemetry``).  Off-mode kernels are the exact
pre-telemetry builds (the flag is part of every kernel cache key), so
off costs nothing beyond one cached flag check per drive.
"""

from __future__ import annotations

import os
import threading
import time

import numpy as np

from . import metrics as _metrics
from . import trace as _trace

#: aux block shape — one row per BFS level within a slice; levels past
#: the buffer fold additively into the last row (flagged by the host)
TELE_ROWS = 128
TELE_COLS = 8

#: column indices (see module doc for semantics)
C_OCC, C_EXP, C_KILL, C_DEDUP, C_ROUNDS, C_NEXT, C_OVF, C_GOAL = range(8)

COLUMNS = ("occupancy", "expanded", "mask_killed", "dedup_folds",
           "crash_rounds", "next_count", "overflow", "goal")

#: per-level detail cap on the result block (totals are exact; the
#: per_level list is a bounded sample so result dicts stay storable)
BLOCK_LEVEL_CAP = 512

_TRUTHY = ("1", "true", "on", "yes")

#: module override (tests, CLI); None = follow the env knob
_forced: bool | None = None
#: env knob read ONCE (the off-mode fast path must not pay an environ
#: lookup — or its string allocations — per search drive)
_env_on: bool | None = None
#: serializes the one-time env read against concurrent first callers
#: (fleet/stream threads drive searches too); the hot path stays
#: lock-free — double-checked locking under the GIL
_knob_lock = threading.Lock()


def enabled() -> bool:
    """Is device-search telemetry on?  Default ON; ``JEPSEN_TPU_
    TELEMETRY=0`` (the CLI's ``--no-telemetry``) or :func:`enable`
    turn it off."""
    global _env_on
    if _forced is not None:
        return _forced
    if _env_on is None:
        with _knob_lock:
            if _env_on is None:
                _env_on = os.environ.get(
                    "JEPSEN_TPU_TELEMETRY", "").strip().lower() \
                    not in ("0", "off", "false", "no")
    return _env_on


def enable(on: bool | None = True) -> None:
    """Force telemetry on/off for this process (``None`` reverts to
    the env knob, re-read on next use)."""
    global _forced, _env_on
    with _knob_lock:
        if on is None:
            # clear the cache BEFORE dropping the force: a concurrent
            # enabled() must not see the stale cached knob with the
            # force already gone
            _env_on = None
        _forced = on


# ---------------------------------------------------------------------------
# registry handles (declared in metrics._declare; re-obtained by name)
# ---------------------------------------------------------------------------

_M_LEVELS = _metrics.REGISTRY.counter(
    "jtpu_search_levels_total",
    "Device BFS levels executed (each slice's depth delta)")
_M_EXP = _metrics.REGISTRY.counter(
    "jtpu_search_expanded_total",
    "Valid candidate lanes expanded by device BFS levels")
_M_KILL = _metrics.REGISTRY.counter(
    "jtpu_search_mask_killed_total",
    "Candidate lanes killed on-device by the hb/dpor must-order mask")
_M_DEDUP = _metrics.REGISTRY.counter(
    "jtpu_search_dedup_folds_total",
    "Successor states folded onto the dead-value canonical token")
_M_ROUNDS = _metrics.REGISTRY.counter(
    "jtpu_search_crash_rounds_total",
    "Crash-closure rounds executed inside device BFS levels")
_M_OVF = _metrics.REGISTRY.counter(
    "jtpu_search_overflows_total",
    "Device BFS levels that overflowed their frontier width")
_M_RATIO = _metrics.REGISTRY.gauge(
    "jtpu_search_observed_prune_ratio",
    "Observed surviving-lane fraction of the most recent device "
    "search (expanded / (expanded + mask_killed + dedup_folds); "
    "0 = decided without search)")
_M_OCC = _metrics.REGISTRY.histogram(
    "jtpu_search_level_occupancy",
    "Live frontier rows per device BFS level",
    buckets=(1, 8, 64, 512, 4096, 32768, 262144))
_M_DEV_S = _metrics.REGISTRY.counter(
    "jtpu_device_seconds_total",
    "Wall seconds spent inside device.slice executions")
_M_XFER = _metrics.REGISTRY.counter(
    "jtpu_device_transfer_bytes_total",
    "Host<->device bytes staged for search dispatch, by direction",
    ("direction",))
_M_DEVMEM = _metrics.REGISTRY.gauge(
    "jtpu_device_memory_bytes",
    "bytes_in_use reported by the primary device (0 where the "
    "backend has no memory_stats)")


# ---------------------------------------------------------------------------
# host-side unpack + accumulation
# ---------------------------------------------------------------------------


def unpack_levels(tele: np.ndarray) -> list[dict]:
    """Unpack one aux block ([TELE_ROWS, TELE_COLS] int32) into level
    dicts, dropping never-written rows (occupancy 0 — the kernel's
    ``cond`` requires a live frontier, so every executed level has
    occupancy >= 1)."""
    t = np.asarray(tele)
    if t.ndim != 2 or t.shape[1] != TELE_COLS:
        raise ValueError(f"aux block must be [rows, {TELE_COLS}], "
                         f"got {t.shape}")
    out = []
    for r in t:
        if int(r[C_OCC]) <= 0:
            continue
        out.append({name: int(r[i]) for i, name in enumerate(COLUMNS)})
    return out


def observed_prune_ratio(expanded: int, killed: int, folds: int):
    """Surviving-lane fraction — the observed twin of the prepass's
    predicted ``prune_ratio`` (both in (0, 1], smaller = more pruned;
    0 is reserved for statically decided searches).  ``None`` when
    nothing expanded and nothing was killed (no device work)."""
    den = expanded + killed + folds
    if den <= 0:
        return None
    return round(expanded / den, 6)


class SearchTelemetry:
    """Accumulates aux blocks across device slices for ONE search.

    ``add_slice`` ingests a 2-D block (optionally with the slice's
    wall window, for ``device.level`` span emission); ``add_totals``
    ingests batched/aggregated blocks where per-level alignment across
    keys is meaningless (the vmapped ladder) and only totals are kept.
    ``block()`` renders the ``search_telemetry`` result dict.
    """

    def __init__(self, engine: str = "device-bfs"):
        self.engine = engine
        self.levels: list[dict] = []
        self.totals = {name: 0 for name in COLUMNS}
        self.n_levels = 0
        self.max_occupancy = 0
        self.slices = 0
        self.truncated = False  # some slice folded levels into its
        #                         last row (lvl_cap > TELE_ROWS)

    def _tally(self, rows: list[dict], levels: int) -> None:
        for r in rows:
            for name in COLUMNS:
                self.totals[name] += r[name]
            self.max_occupancy = max(self.max_occupancy, r["occupancy"])
        self.n_levels += levels

    def add_slice(self, tele: np.ndarray, t0: float | None = None,
                  t1: float | None = None,
                  frontier: int | None = None, *, levels: int,
                  parent=_trace._ON_THREAD) -> None:
        """Ingest one slice's aux block.  ``t0``/``t1`` (perf_counter
        readings of the slice window) enable ``device.level`` child
        span emission, apportioned by occupancy — per-level cost is
        proportional to frontier width, so occupancy is the honest
        cheap estimator.  ``levels`` is the levels the slice ran (the
        carry's depth after it less before): the level count takes it,
        where the block's rows would miss every level folded into the
        last row.  ``parent`` is the id of the slice's ``device.slice``
        span, when it has closed."""
        rows = unpack_levels(tele)
        self.slices += 1
        t = np.asarray(tele)
        if rows and int(t[TELE_ROWS - 1, C_OCC]) > 0 \
                and len(rows) == TELE_ROWS:
            # the last row is additive: with every row written it may
            # hold the fold of any levels past the buffer
            self.truncated = True
        base_level = len(self.levels)
        self._tally(rows, levels)
        if not rows:
            return
        self.levels.extend(rows)
        if t0 is not None and t1 is not None and _trace.enabled():
            rec = _trace.recorder(_trace.current_run())
            occ_sum = sum(r["occupancy"] for r in rows) or 1
            cur = t0
            span = max(0.0, t1 - t0)
            for i, r in enumerate(rows):
                frac = r["occupancy"] / occ_sum
                end = min(t1, cur + span * frac)
                args = {"level": base_level + i, **r}
                if frontier is not None:
                    args["frontier"] = frontier
                rec.record("device.level", "device", cur, end, args,
                           parent=parent)
                cur = end

    def add_totals(self, tele: np.ndarray, *, levels: int) -> None:
        """Ingest an aggregate block (e.g. a batch's lane-sum): totals
        and level count only — per-level rows across differently-paced
        keys do not align, so none are kept.  ``levels`` is the levels
        the batch kernel ran (its deepest lane's depth delta), counted
        as in `add_slice`."""
        t = np.asarray(tele)
        if t.ndim == 3:
            t = t.sum(axis=0)
        self.slices += 1
        self._tally(unpack_levels(t), levels)

    def block(self, predicted: float | None = None) -> dict:
        """The ``search_telemetry`` result block.  ``predicted`` is
        the prepass's prune_ratio (hb/dpor) when one was computed —
        recorded next to the observed ratio so the two can be diffed
        everywhere downstream.  Deterministic: counters only, no wall
        times (byte-identity across reruns of the same search)."""
        tt = self.totals
        obs_ratio = observed_prune_ratio(
            tt["expanded"], tt["mask_killed"], tt["dedup_folds"])
        out = {
            "levels": self.n_levels,
            "slices": self.slices,
            "max_occupancy": self.max_occupancy,
            "expanded": tt["expanded"],
            "mask_killed": tt["mask_killed"],
            "dedup_folds": tt["dedup_folds"],
            "crash_rounds": tt["crash_rounds"],
            "overflows": tt["overflow"],
            "goals": tt["goal"],
            "observed_prune_ratio": obs_ratio,
            "truncated": self.truncated,
        }
        if predicted is not None:
            out["predicted_prune_ratio"] = predicted
            if obs_ratio is not None:
                out["prune_ratio_delta"] = round(obs_ratio - predicted,
                                                 6)
        per = [[r[name] for name in COLUMNS]
               for r in self.levels[:BLOCK_LEVEL_CAP]]
        if per:
            out["per_level"] = per
            out["per_level_columns"] = list(COLUMNS)
            if len(self.levels) > len(per):
                out["per_level_capped"] = True
        return out


def emit_shard_levels(tele: np.ndarray, n_used: int, n_shards: int,
                      t0: float, t1: float) -> None:
    """Per-shard ``device.level`` spans from one batched aux block.

    ``tele`` is the [B, TELE_ROWS, TELE_COLS] lane-stacked block a
    mesh-sharded batch slice returned; the lane axis partitions into
    ``n_shards`` contiguous device blocks (B divisible by the mesh —
    that is what the inert pad lanes guarantee).  Lanes at or past
    ``n_used`` are those mesh-divisibility pads and are EXCLUDED: pad
    lanes must not appear in observed occupancy.  Each shard's lane-sum
    unpacks into its own ``device.level`` spans (args carry
    ``shard=i``), apportioned over the slice window by occupancy — the
    per-shard twin of :meth:`SearchTelemetry.add_slice`'s emission, so
    a trace shows which shards carried the level work and which sat on
    pad-free but idle lanes.  Tracing-gated; totals are NOT tallied
    here (the caller's accumulator ingests the pad-stripped block)."""
    if not _trace.enabled():
        return
    t = np.asarray(tele)
    if t.ndim != 3 or n_shards <= 0 or t.shape[0] % n_shards:
        return
    per = t.shape[0] // n_shards
    rec = _trace.recorder(_trace.current_run())
    span = max(0.0, t1 - t0)
    for s in range(n_shards):
        lo = s * per
        used = min(max(0, n_used - lo), per)
        if used <= 0:
            continue  # all-pad shard: nothing real ran here
        rows = unpack_levels(t[lo:lo + used].sum(axis=0))
        if not rows:
            continue
        occ_sum = sum(r["occupancy"] for r in rows) or 1
        cur = t0
        for i, r in enumerate(rows):
            end = min(t1, cur + span * (r["occupancy"] / occ_sum))
            rec.record("device.level", "device", cur, end,
                       {"level": i, "shard": s, "lanes": used, **r})
            cur = end


def _predicted_ratio(result: dict | None, hbres=None):
    """The prepass's predicted prune_ratio for this search, if any —
    preferring the live hb stats (hbres), falling back to the result's
    attached ``hb`` block."""
    st = None
    if hbres is not None:
        st = getattr(hbres, "stats", None)
    if st is None and isinstance(result, dict):
        hb = result.get("hb")
        if isinstance(hb, dict):
            st = hb
    if isinstance(st, dict) and "prune_ratio" in st:
        try:
            return float(st["prune_ratio"])
        except (TypeError, ValueError):
            return None
    return None


def finalize_result(result: dict, acc: "SearchTelemetry | None", *,
                    hbres=None, attach: bool = True) -> dict:
    """Close out one search's telemetry: compute the block, attach it
    to the result (``attach=True``), bump the ``jtpu_search_*``
    registry, and emit the ``search.telemetry`` span (tracing-gated)
    so traces are self-contained — ``tools/trace_report.py`` reads
    predicted-vs-observed from the span args."""
    if acc is None:
        return result
    predicted = _predicted_ratio(result, hbres)
    blk = acc.block(predicted=predicted)
    tt = acc.totals
    if acc.n_levels:
        _M_LEVELS.inc(acc.n_levels)
        _M_EXP.inc(tt["expanded"])
        _M_KILL.inc(tt["mask_killed"])
        _M_DEDUP.inc(tt["dedup_folds"])
        _M_ROUNDS.inc(tt["crash_rounds"])
        _M_OVF.inc(tt["overflow"])
        for r in acc.levels[:BLOCK_LEVEL_CAP]:
            _M_OCC.observe(r["occupancy"])
    if blk.get("observed_prune_ratio") is not None:
        _M_RATIO.set(blk["observed_prune_ratio"])
    update_device_memory()
    if attach:
        result["search_telemetry"] = blk
    _emit_span(blk)
    return result


def emit_decided(result: dict, hbres=None) -> dict:
    """Telemetry for a search the prepass decided WITHOUT device work:
    an all-zero block whose observed ratio is 0.0 (everything pruned),
    diffed against the predicted 0.0.  Span-only — decided results
    keep their certificate-centric shape (no ``search_telemetry``
    key), but traces still carry the predicted-vs-observed row (the
    10kuniq bench tier is exactly this case)."""
    if not enabled():
        return result
    predicted = _predicted_ratio(result, hbres)
    blk = {"levels": 0, "slices": 0, "max_occupancy": 0, "expanded": 0,
           "mask_killed": 0, "dedup_folds": 0, "crash_rounds": 0,
           "overflows": 0, "goals": 0, "observed_prune_ratio": 0.0,
           "decided": True, "truncated": False}
    blk["predicted_prune_ratio"] = predicted if predicted is not None \
        else 0.0
    blk["prune_ratio_delta"] = round(0.0 - blk["predicted_prune_ratio"],
                                     6)
    _M_RATIO.set(0.0)
    _emit_span(blk)
    return result


def _emit_span(blk: dict) -> None:
    if not _trace.enabled():
        return
    now = time.perf_counter()
    args = {k: v for k, v in blk.items()
            if k not in ("per_level", "per_level_columns")}
    _trace.recorder(_trace.current_run()).record(
        "search.telemetry", "telemetry", now, now, args)


# ---------------------------------------------------------------------------
# compile / transfer / memory accounting
# ---------------------------------------------------------------------------


def record_device_seconds(dt: float) -> None:
    """One device.slice execution's wall seconds — the numerator of
    the derived ``device_idle_fraction`` gauge (/api/stats)."""
    if dt > 0:
        _M_DEV_S.inc(dt)


def record_transfer(nbytes: int, direction: str = "h2d") -> None:
    """Byte-counted host->device staging, next to a ``device.
    transfer`` span when tracing is on."""
    if nbytes <= 0:
        return
    _M_XFER.inc(nbytes, direction=direction)
    if _trace.enabled():
        now = time.perf_counter()
        _trace.recorder(_trace.current_run()).record(
            "device.transfer", "device", now, now,
            {"bytes": int(nbytes), "direction": direction})


def transfer_bytes(arrays) -> int:
    """Total nbytes of a host-array tuple about to be staged."""
    total = 0
    for a in arrays:
        nb = getattr(a, "nbytes", None)
        if nb:
            total += int(nb)
    return total


def persistent_cache_configured() -> bool:
    """Whether a persistent XLA compile cache is configured (jax's
    ``jax_compilation_cache_dir``, which ``JAX_COMPILATION_CACHE_DIR``
    and util.enable_compilation_cache both set).  Compile spans record
    it per miss and the fleet warm-boot gate (fleet/warmup.py) reports
    it per worker, so cold-start compile tax is attributable either
    way."""
    import jax

    return bool(jax.config.jax_compilation_cache_dir)


def compile_span(**attrs):
    """The ``device.compile`` span wrapping one kernel build+jit on a
    cache MISS (hits never enter it — the lookup is a dict get).  Args
    carry the cache verdict and whether a persistent XLA compile cache
    is configured, so cold-start compile tax is attributable from the
    trace alone (the fleet-warmup ROADMAP item's signal)."""
    from .. import obs

    return obs.span("device.compile", cat="device", cache="miss",
                    persistent_cache=persistent_cache_configured(),
                    **attrs)


def update_device_memory() -> None:
    """Refresh the device-memory gauge from the primary device's
    ``memory_stats`` (TPU/GPU report bytes_in_use; CPU backends have
    none and the gauge stays 0)."""
    try:
        import jax

        dev = jax.local_devices()[0]
        stats = dev.memory_stats() if hasattr(dev, "memory_stats") \
            else None
        if stats and "bytes_in_use" in stats:
            _M_DEVMEM.set(float(stats["bytes_in_use"]))
    except Exception:  # noqa: BLE001 — accounting must never raise
        pass
