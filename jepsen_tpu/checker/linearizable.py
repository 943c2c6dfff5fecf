"""TPU linearizability engine — batched JAX frontier search.

This is the rebuild's replacement for the external ``knossos`` JVM library
the reference delegates linearizability checking to (used from
jepsen/src/jepsen/checker.clj:114-139; algorithms selected at
checker.clj:122-126).  knossos explores configurations — (set of
linearized ops, model state) — by depth-first search with a visited memo,
sized at -Xmx32g (jepsen/project.clj:25).  Here the same configuration
space is explored breadth-first on device: a frontier of configurations is
expanded in lockstep under ``vmap`` (one lane per configuration ×
candidate), deduplicated exactly per level, and compacted into the next
frontier.  The BFS runs as a sequence of bounded device calls — a
``lax.while_loop`` capped at ``lvl_cap`` levels per call, with the search
state as an explicit carry — so a deadline, a stop request or a
checkpoint acts between two calls; the carry doubles as the checkpoint
and as the resume point for in-place frontier escalation.

Configuration encoding (the "hashing model states on TPU" problem,
SURVEY.md §7): a naive linearized-set needs n bits per config.  Instead we
exploit the real-time order:

  * Determinate ops (ok completions; they MUST linearize) are kept sorted
    by invocation.  In any reachable configuration, if ``p`` is the first
    unlinearized determinate op, every linearized op j > p was linearized
    while p was pending, so ``inv[j] < ret[p]``.  The number of such j is
    bounded and host-computable (``window_width``); hence the set of
    linearized determinate ops is exactly (prefix ``p``, bitmask over the
    next W ops).
  * Indeterminate ops (:info — crashed; ``ret = +inf``; they MAY linearize
    at any point after invocation, forever — core.clj:387-397) break that
    bound, so they live in their own bitmask of width ≤ 64; a history has
    at most #processes of them.

A config is then ``[p | window words | crash words | model state]`` — a
handful of int32 lanes instead of n bits, so millions of configs fit in
HBM and hash in a few vector ops.

Soundness: a "valid" verdict always carries a real witness path (every
transition was model-checked on device, and the goal test runs on every
candidate lane).  Dedup is *exact*: candidates are hash-sorted (one
packed uint32 key at moderate widths, a variadic (hash, iota) sort
above) and equal-key neighbors are compared on their full config words
before dropping either — hash collisions cost duplicate work, never a
merge — so an "invalid" verdict is not subject to fingerprinting.
Capacity is handled by the adaptive width driver (`_run_kernel`): the
frontier width moves both ways on a power-of-two grid — an overflowing
level is uncommitted by the kernel and the search resumes 4x wider from
the very level that overflowed (zero levels re-run); a shrunken live
frontier truncates back down.  Only at MAX_FRONTIER does
an overflow degrade the verdict, and then always to "unknown", never to
a wrong answer; exhausted budgets and deadlines also report "unknown".
Histories whose window or crash count exceed the device encoding fall
back to the exact `linear` host sweep (checker/linear.py);
Linearizable.check additionally re-runs short failing prefixes
(≤ witness_threshold ops) on the WGL host oracle (checker/seq.py) to
reconstruct a human-readable witness, and `check_competition` races
both host engines against the device search outright (the knossos
`competition` analog).

Batching: `search_batch` vmaps the whole search over a leading key axis —
the TPU analog of the reference's independent-key sharding
(jepsen/src/jepsen/independent.clj:247-298, bounded-pmap per key).  The
key axis shards across a device mesh with `jax.sharding`; searches are
embarrassingly parallel so the only collective is the final verdict
gather.
"""

from __future__ import annotations

import os
import sys
import threading
import time
from dataclasses import dataclass, replace as _dc_replace

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .. import obs
from ..history import INF_RET, NIL, OpSeq, encode_ops
from ..models import ModelSpec
from ..obs import metrics as _obs_metrics
from ..obs import telemetry as _tele
from ..obs.telemetry import (C_DEDUP, C_EXP, C_GOAL, C_KILL, C_NEXT,
                             C_OCC, C_OVF, C_ROUNDS, TELE_COLS,
                             TELE_ROWS)

#: flight-recorder twin of KERNEL_CACHE_STATS (module handle: a
#: registry get-or-create per lookup would tax the dispatch path)
_M_KCACHE = _obs_metrics.REGISTRY.counter(
    "jtpu_kernel_cache_total",
    "Compiled-kernel cache lookups (hit/miss)", ("event",))

# int32 value standing in for "+infinity" event rank on device.
INF32 = np.int32(2**31 - 1)

# ---------------------------------------------------------------------------
# Host-side preprocessing
# ---------------------------------------------------------------------------


#: must-order predecessor slots per det/crash row shipped to device —
#: rows with more keep their LATEST (largest-position) preds; masking
#: with a subset of the predecessor set is sound, just a weaker prune
MASK_PREDS = 4

#: widest dead-value lookup table the device dedup will carry —
#: CANDIDATE state values only (compared-but-never-written values sit
#: outside it by design); wider value ranges simply skip the device
#: rewrite (host engines use the dict form and have no span limit).
#: 64k entries = 256 KB per solo key; batches stack to their max.
DEAD_TABLE_MAX = 1 << 16


@dataclass
class EncodedSearch:
    """Device-ready arrays for one history (padded to static shapes).

    The ``*_mpred``/``*_cpred`` planes and the ``dead_*`` table are the
    state-space-reduction phase-2 payload (attach_reductions): per-row
    must-order predecessors from the HB/constraint/dup-edge prepass,
    and the dead-value quotient table from decompose/canonical.py.
    ``masked``/``dedup`` say whether the kernels should emit the
    corresponding checks (the arrays are always materialized by
    pad_search so batch stacking stays uniform)."""

    det_f: np.ndarray  # int32 [n_det_pad]
    det_v1: np.ndarray
    det_v2: np.ndarray
    det_inv: np.ndarray  # int32; INF32 padding
    det_ret: np.ndarray  # int32; INF32 padding
    suffix_min_ret: np.ndarray  # int32 [n_det_pad + 1]
    crash_f: np.ndarray  # int32 [n_crash_pad]
    crash_v1: np.ndarray
    crash_v2: np.ndarray
    crash_inv: np.ndarray
    n_det: int
    n_crash: int
    window: int  # exact upper bound on linearized-beyond-prefix span
    concurrency: int  # max simultaneously-enabled candidates
    #: must-order mask (None until attach_reductions / pad_search):
    #: det positions of up to MASK_PREDS predecessors per row (-1 pad)
    det_mpred: np.ndarray | None = None   # int32 [n_det(_pad), P]
    det_cpred: np.ndarray | None = None   # uint64 [n_det] crash bitmask
    crash_mpred: np.ndarray | None = None  # int32 [n_crash(_pad), P]
    crash_cpred: np.ndarray | None = None  # uint64 [n_crash]
    #: packed crash-pred words (pad_search output only)
    det_cpredw: np.ndarray | None = None   # int32 [n_det_pad, CW]
    crash_cpredw: np.ndarray | None = None  # int32 [n_crash_pad, CW]
    #: dead-value quotient table (attach_reductions / pad_search)
    dead_from: np.ndarray | None = None    # int32 [VT]
    dead_lo: int = 0
    dead_tok: int = 0
    masked: bool = False
    mask_has_crash: bool = False
    dedup: bool = False


def split_rows(seq: OpSeq):
    """Partition OpSeq rows into determinate (ok) and crashed (info)."""
    ok = np.asarray(seq.ok, dtype=bool)
    det = np.nonzero(ok)[0]
    crash = np.nonzero(~ok)[0]
    return det, crash


def window_width(det_inv: np.ndarray, det_ret: np.ndarray) -> int:
    """Exact window bound: max over b of #{j >= b : inv[j] < ret[b]}.

    det rows are sorted by invocation, so the count is a searchsorted.
    Any linearized determinate op beyond the first unlinearized one b
    satisfies inv[j] < ret[b]; the window must cover all such j plus b
    itself.
    """
    n = len(det_inv)
    if n == 0:
        return 1
    # positions with inv < ret[b], among indices >= b
    upper = np.searchsorted(det_inv, det_ret, side="left")
    spans = upper - np.arange(n)
    return max(1, int(spans.max()))


def max_enabled(seq: OpSeq) -> int:
    """Upper bound on simultaneously-enabled candidates per config.

    Enabled candidates pairwise overlap in real time (each invoked before
    every other's return), and pairwise-intersecting intervals on a line
    share a common point (Helly, d=1), so the count is bounded by the
    history's max concurrency — crashed ops stay open forever and are
    counted by the sweep in history.max_concurrency.
    """
    events = []
    for i in range(len(seq)):
        events.append((int(seq.inv[i]), 1))
        if int(seq.ret[i]) != INF_RET:
            events.append((int(seq.ret[i]), -1))
    events.sort()
    cur = peak = 0
    for _, d in events:
        cur += d
        peak = max(peak, cur)
    return max(1, peak)


def encode_search(seq: OpSeq) -> EncodedSearch:
    det_idx, crash_idx = split_rows(seq)
    det_inv = np.asarray(seq.inv, dtype=np.int64)[det_idx]
    det_ret64 = np.asarray(seq.ret, dtype=np.int64)[det_idx]

    W = window_width(det_inv, det_ret64)
    C = max_enabled(seq)

    n_det = len(det_idx)
    n_crash = len(crash_idx)

    def i32(a):
        return np.asarray(a, dtype=np.int32)

    det = EncodedSearch(
        det_f=i32(seq.f[det_idx]),
        det_v1=i32(seq.v1[det_idx]),
        det_v2=i32(seq.v2[det_idx]),
        det_inv=i32(np.minimum(det_inv, INF32)),
        det_ret=i32(np.minimum(det_ret64, INF32)),
        suffix_min_ret=np.zeros(0, dtype=np.int32),  # filled below
        crash_f=i32(seq.f[crash_idx]),
        crash_v1=i32(seq.v1[crash_idx]),
        crash_v2=i32(seq.v2[crash_idx]),
        crash_inv=i32(np.minimum(np.asarray(seq.inv, np.int64)[crash_idx],
                                 INF32)),
        n_det=n_det,
        n_crash=n_crash,
        window=W,
        concurrency=C,
    )
    # suffix minima of det returns; suffix_min_ret[i] = min(ret[i:]), with
    # suffix_min_ret[n] = +inf
    sfx = np.full(n_det + 1, INF32, dtype=np.int32)
    for i in range(n_det - 1, -1, -1):
        sfx[i] = min(int(det.det_ret[i]), int(sfx[i + 1]))
    det.suffix_min_ret = sfx
    return det


def attach_reductions(es: EncodedSearch, seq: OpSeq, model: ModelSpec,
                      must_pred: dict | None, *,
                      dedup: bool = True) -> EncodedSearch:
    """Attach the phase-2 reduction payload to an EncodedSearch.

    ``must_pred`` is the prepass's row-index predecessor map
    (HB/constraint forced + canonical edges, plus dpor's duplicate-op
    edges) — split here into det-position / crash-index tables the
    kernels' ``expand_mask`` consumes.  ``dedup`` additionally builds
    the dead-value quotient table (decompose/canonical.py) when the
    model family and value range allow.  Mutates and returns ``es``.
    """
    det_rows, crash_rows = split_rows(seq)
    if must_pred:
        det_pos_of = {int(r): p for p, r in enumerate(det_rows)}
        crash_of = {int(r): c for c, r in enumerate(crash_rows)}
        dmp = np.full((es.n_det, MASK_PREDS), -1, np.int32)
        # unsigned: crash index 63 (MAX_CRASH - 1) sets bit 63, which
        # does not fit a signed int64
        dcp = np.zeros(es.n_det, np.uint64)
        cmp_ = np.full((es.n_crash, MASK_PREDS), -1, np.int32)
        ccp = np.zeros(es.n_crash, np.uint64)
        any_mask = False
        has_crash_pred = False
        for dst, srcs in must_pred.items():
            dp = sorted(det_pos_of[s] for s in srcs if s in det_pos_of)
            cp = 0
            for s in srcs:
                c = crash_of.get(s)
                if c is not None:
                    cp |= 1 << c
            if not dp and not cp:
                continue
            dp = dp[-MASK_PREDS:]  # keep the latest (binding longest)
            if dst in det_pos_of:
                p = det_pos_of[dst]
                dmp[p, :len(dp)] = dp
                dcp[p] = cp
            else:
                c = crash_of[dst]
                cmp_[c, :len(dp)] = dp
                ccp[c] = cp
            any_mask = True
            has_crash_pred = has_crash_pred or bool(cp)
        if any_mask:
            es.det_mpred, es.det_cpred = dmp, dcp
            es.crash_mpred, es.crash_cpred = cmp_, ccp
            es.masked = True
            es.mask_has_crash = has_crash_pred
    if dedup and model.state_width == 1:
        from ..decompose.canonical import NEVER_DEAD, dead_value_cutoffs

        dv = dead_value_cutoffs(seq, model)
        if dv is not None:
            lo, hi = dv.value_range()
            span = hi - lo + 1
            if span <= DEAD_TABLE_MAX:
                t = np.full(span, NEVER_DEAD, np.int32)
                for v, c in dv.cutoffs.items():
                    # compared-but-never-written values sit outside
                    # the candidate span by design: states never hold
                    # them, so they need no entry
                    if lo <= v < lo + span:
                        t[v - lo] = min(c, NEVER_DEAD)
                es.dead_from = t
                es.dead_lo = lo
                es.dead_tok = dv.token
                es.dedup = True
    return es


def _pack_cpred(bits: np.ndarray | None, n_rows: int,
                cw: int) -> np.ndarray:
    """uint64 per-row crash-pred bitmasks -> int32 words [n_rows, cw]."""
    out = np.zeros((n_rows, cw), np.int32)
    if bits is not None:
        b = bits.astype(np.uint64)
        for w in range(min(cw, 2)):
            out[:len(b), w] = ((b >> np.uint64(32 * w))
                               & np.uint64(0xFFFFFFFF)).astype(
                np.uint32).view(np.int32)
    return out


def pad_search(es: EncodedSearch, n_det_pad: int, n_crash_pad: int,
               dead_pad: int | None = None) -> EncodedSearch:
    """Pad arrays to static shapes (for jit caching / batching).

    The reduction planes are ALWAYS materialized here (empty = all -1
    preds / all-NEVER_DEAD table) so batch stacking and the kernel
    signature stay uniform whether or not a key carries reductions.
    ``dead_pad`` pins the dead-table width (batch callers pass the
    max over their keys so stacked shapes agree); default: this key's
    own power-of-two width."""
    from ..decompose.canonical import NEVER_DEAD

    def pad(a, n, fill):
        out = np.full(n, fill, dtype=np.int32)
        out[: len(a)] = a
        return out

    cw = max(1, n_crash_pad // 32)
    dmp = np.full((n_det_pad, MASK_PREDS), -1, np.int32)
    if es.det_mpred is not None:
        dmp[:len(es.det_mpred)] = es.det_mpred
    cmp_ = np.full((n_crash_pad, MASK_PREDS), -1, np.int32)
    if es.crash_mpred is not None:
        cmp_[:len(es.crash_mpred)] = es.crash_mpred
    if dead_pad is None:
        dead_pad = _next_pow2(len(es.dead_from)) \
            if es.dead_from is not None else 8
    dead_pad = max(8, dead_pad)
    dead = np.full(dead_pad, NEVER_DEAD, np.int32)
    if es.dead_from is not None:
        dead[:len(es.dead_from)] = es.dead_from
    return EncodedSearch(
        det_f=pad(es.det_f, n_det_pad, 0),
        det_v1=pad(es.det_v1, n_det_pad, NIL),
        det_v2=pad(es.det_v2, n_det_pad, NIL),
        det_inv=pad(es.det_inv, n_det_pad, INF32),
        det_ret=pad(es.det_ret, n_det_pad, INF32),
        suffix_min_ret=pad(es.suffix_min_ret, n_det_pad + 1, INF32),
        crash_f=pad(es.crash_f, n_crash_pad, 0),
        crash_v1=pad(es.crash_v1, n_crash_pad, NIL),
        crash_v2=pad(es.crash_v2, n_crash_pad, NIL),
        crash_inv=pad(es.crash_inv, n_crash_pad, INF32),
        n_det=es.n_det,
        n_crash=es.n_crash,
        window=es.window,
        concurrency=es.concurrency,
        det_mpred=dmp,
        det_cpredw=_pack_cpred(es.det_cpred, n_det_pad, cw),
        crash_mpred=cmp_,
        crash_cpredw=_pack_cpred(es.crash_cpred, n_crash_pad, cw),
        dead_from=dead,
        dead_lo=es.dead_lo,
        dead_tok=es.dead_tok,
        masked=es.masked,
        mask_has_crash=es.mask_has_crash,
        dedup=es.dedup,
    )


# ---------------------------------------------------------------------------
# Device kernel
# ---------------------------------------------------------------------------


def _hash_words(words, seed):
    """Vector fnv/murmur-style mix of int32 config words -> uint32.

    words: uint32 [..., w]; returns uint32 [...].
    """
    h = jnp.full(words.shape[:-1], np.uint32(seed), dtype=jnp.uint32)
    w = words.shape[-1]
    for i in range(w):
        h = (h ^ words[..., i]) * np.uint32(0x85EBCA6B)
        h = (h ^ (h >> 13)) * np.uint32(0xC2B2AE35)
    h = h ^ (h >> 16)
    return h


@dataclass(frozen=True)
class SearchDims:
    """Static kernel dimensions (jit cache key)."""

    n_det_pad: int
    n_crash_pad: int  # multiple of 32, <= 64
    window: int  # W, multiple of 32
    k: int  # successor lanes per config (>= max concurrency)
    state_width: int
    frontier: int  # F: max configs per BFS level

    @property
    def win_words(self) -> int:
        return self.window // 32

    @property
    def crash_words(self) -> int:
        return max(1, self.n_crash_pad // 32)

    @property
    def words(self) -> int:
        # p | win | crash | state
        return 1 + self.win_words + self.crash_words + self.state_width


def _pack_bits(bits, n_words):
    """bool [..., 32*n_words] -> int32 words [..., n_words]."""
    shape = bits.shape[:-1]
    b = bits.reshape(shape + (n_words, 32)).astype(jnp.uint32)
    shifts = jnp.arange(32, dtype=jnp.uint32)
    words = (b << shifts).sum(axis=-1, dtype=jnp.uint32)
    return words.astype(jnp.int32)


def _unpack_bits(words, n_words):
    """int32 words [..., n_words] -> bool [..., 32*n_words]."""
    shape = words.shape[:-1]
    w = words.astype(jnp.uint32)[..., :, None]
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (w >> shifts) & np.uint32(1)
    return bits.reshape(shape + (n_words * 32,)).astype(bool)


def _kth_bit_in_word(w, r):
    """Index of the (r+1)-th set bit of uint32 ``w`` (branchless binary
    search over chunk popcounts); garbage when w has <= r set bits —
    callers mask on the count."""
    idx = jnp.zeros_like(r)
    cur = w
    for half in (16, 8, 4, 2, 1):
        m = np.uint32((1 << half) - 1)
        lowc = lax.population_count(cur & m).astype(jnp.int32)
        go_hi = r >= lowc
        r = jnp.where(go_hi, r - lowc, r)
        idx = idx + jnp.where(go_hi, half, 0)
        cur = jnp.where(go_hi, cur >> half, cur & m)
    return idx


def _select_enabled(mask, k_out: int):
    """Indices of the first k_out set lanes of a SMALL bool mask, plus
    the count — the per-config candidate selection.  Packs the mask into
    uint32 words and extracts k-th set bits with pure ALU ops (popcount
    + branchless in-word binary search): no per-lane gathers, which cost
    ~3x more than this under vmap on both backends (the selection was
    ~70% of expand_mask with the cumsum+searchsorted form)."""
    n_lanes = mask.shape[0]
    nw = (n_lanes + 31) // 32
    pad = nw * 32 - n_lanes
    if pad:
        mask = jnp.concatenate([mask, jnp.zeros(pad, bool)])
    words = _pack_bits(mask, nw).astype(jnp.uint32)          # [nw]
    pc = lax.population_count(words).astype(jnp.int32)
    cum = jnp.cumsum(pc)
    n = cum[-1]
    cum_before = jnp.concatenate([jnp.zeros(1, jnp.int32), cum[:-1]])
    ks = jnp.arange(k_out, dtype=jnp.int32)
    wi = (cum[None, :] <= ks[:, None]).sum(axis=1).astype(jnp.int32)
    wi = jnp.minimum(wi, nw - 1)
    w = jnp.take(words, wi)
    r = jnp.maximum(ks - jnp.take(cum_before, wi), 0)
    return _kth_bit_in_word(w, r) + wi * 32, n


#: compaction implementation: "search" (cumsum + searchsorted),
#: "matrix" (one-hot reduce), or "auto" — matrix on TPU when the
#: [k_out, n] one-hot fits the element budget.  searchsorted compiles
#: to a while loop + ~15 fusions; on TPU that fixed op count floors
#: narrow levels (the compaction runs 2-3x per level), while the
#: matrix form is ~5 large VPU ops.
_COMPACT_MODE = os.environ.get("JEPSEN_TPU_COMPACT", "auto")
_COMPACT_ELEMS = int(os.environ.get("JEPSEN_TPU_COMPACT_ELEMS",
                                    str(1 << 24)))


def _backend() -> str:
    """The active JAX backend.  A backend that fails to start raises
    here: the selectors below must never build CPU-shaped kernels (or
    the Pallas interpreter) for a chip they could not see."""
    return jax.default_backend()


def _use_matrix_compact(k_out: int, n: int, batch: int = 1) -> bool:
    """``batch`` multiplies the [k_out, n] one-hot: a vmapped kernel
    (batch keys) or a vmap-over-destinations route materializes one
    instance per lane, exactly like `_use_allpairs`'s budget.

    Forced "matrix" still honors the element budget — an escalated
    frontier (width 256k was reached by the r4 wide-history fuzz)
    would otherwise ask for a >100 GB one-hot and OOM the process."""
    if _COMPACT_MODE == "matrix":
        return batch * k_out * n <= _COMPACT_ELEMS
    if _COMPACT_MODE == "search":
        return False
    backend = _backend()
    return backend == "tpu" and batch * k_out * n <= _COMPACT_ELEMS


def _compact_indices(mask, k_out: int, batch: int = 1):
    """Indices of the first k_out set lanes of a bool mask (stable), plus
    the total count.  Sort-free stream compaction; two forms with
    identical semantics (out-of-range output rows hold an arbitrary
    in-bounds index — callers mask on the count):

    * cumsum + binary-search gather — O(n + k log n);
    * one-hot matrix reduce — O(k*n) FLOPs but a handful of large ops
      (picked on TPU at narrow widths, where op COUNT is the floor).

    ``batch`` is the memory-budget hint for callers whose instance gets
    vmapped (the form choice is static per call site)."""
    csum = jnp.cumsum(mask.astype(jnp.int32))
    n = mask.shape[0]
    targets = jnp.arange(1, k_out + 1, dtype=jnp.int32)
    if _use_matrix_compact(k_out, n, batch):
        # rank[i] = 1-based rank of lane i among set lanes (0 if unset);
        # each target rank matches exactly one lane, so the masked
        # iota-reduce recovers its index (unmatched targets sum to 0 —
        # in-bounds, masked by the count downstream)
        rank = jnp.where(mask, csum, 0)
        onehot = rank[None, :] == targets[:, None]
        idx = (onehot * jnp.arange(n, dtype=jnp.int32)[None, :]).sum(
            axis=1)
        return idx.astype(jnp.int32), csum[-1]
    idx = jnp.searchsorted(csum, targets, side="left")
    return jnp.minimum(idx, n - 1).astype(jnp.int32), csum[-1]


#: dominance-pass window: each sorted row is tested for domination
#: against this many predecessors.  Misses past the window keep
#: redundant configs (wasted work), never drop reachable ones.
_DOM_WINDOW = 8


def _pw_parts(cfgs, dims: SearchDims):
    """(hash over the non-crash words, crash popcount) per row.

    The dominance sort groups rows by (p, window, state) — the crash
    words are excluded from the hash so every crash variant of one
    det-configuration lands in the same bucket, ordered small-mask-first
    by the popcount key."""
    u = cfgs.astype(jnp.uint32)
    a = 1 + dims.win_words
    b = a + dims.crash_words
    pw = jnp.concatenate([u[:, :a], u[:, b:]], axis=1)
    pwh = _hash_words(pw, 0x9E3779B1)
    popc = lax.population_count(u[:, a:b]).sum(
        axis=1, dtype=jnp.uint32)
    return pwh, popc


def _sort_dominance(pwh, popc, valid, cfgs, M: int, dims: SearchDims,
                    R: int = _DOM_WINDOW):
    """Sort rows so equal-(p, win, state) configs group together with
    smaller crash masks first, then drop every row *dominated* by an
    earlier row: same (p, win, state) and the earlier row's crash mask
    a subset of this row's.

    Soundness: crashed ops never block other ops (ret = +inf) and are
    never required to linearize, so any completion of the dominated row
    is a completion of the dominator — dropping the dominated row can
    never lose a reachable goal, and a frontier that dies without one
    still proves invalidity.  Domination is decided on FULL word
    equality + a real subset test (hashes only order), so a collision
    can only *miss* a drop, never cause a wrong one.  A dominator that
    was itself dropped is fine: ⊆ is transitive, so a kept row
    dominates transitively.

    Sort keys are (pw-hash, [crash-popcount | full-hash bits], iota):
    identical rows tie on 57 hash bits and so sort ADJACENT (the o=1
    window is exact dedup, modulo a ~2^-57 collision that merely keeps
    a duplicate), and any dominator of a row sorts earlier (equal
    pw-hash, smaller-or-equal popcount in the second key's top bits).
    Two reaches of the prune:

      * a backward window of R rows (nearby dominators, exact dups);
      * the row's RUN FIRST (run = maximal span of equal (p, win,
        state) words): the run's minimum-popcount row, tested at any
        distance — this is what keeps huge crash-variant buckets from
        retaining duplicates of their minimal masks.

    Returns (kept, sorted_cfgs, perm) — perm maps sorted rows to input
    rows (callers use it to detect which survivors came from which
    input block)."""
    big = np.uint32(0xFFFFFFFF)
    h2 = _hash_words(cfgs.astype(jnp.uint32), 0x7FEB352D)
    k1 = jnp.where(valid, pwh, big)
    # one packed secondary key: popcount (<= 64, 7 bits) above 25 bits
    # of the full-config hash — popcount-ascending within a pw bucket
    # (dominators first), identical rows adjacent on 32+25 hash bits.
    # A valid row's key2 top bits are < 127 << 25 so the all-ones
    # invalid marker still sorts strictly last.
    k2 = jnp.where(valid, (popc << np.uint32(25)) | (h2 >> np.uint32(7)),
                   big)
    _s1, _s2, perm = lax.sort(
        (k1, k2, jnp.arange(M, dtype=jnp.int32)), num_keys=2)
    svalid = jnp.take(valid, perm)
    scfgs = jnp.take(cfgs, perm, axis=0)
    a = 1 + dims.win_words
    b = a + dims.crash_words
    spw = jnp.concatenate([scfgs[:, :a], scfgs[:, b:]], axis=1)
    scr = scfgs[:, a:b].astype(jnp.uint32)
    drop = jnp.zeros(M, bool)
    for o in range(1, R + 1):
        eq = jnp.all(spw[o:] == spw[:-o], axis=1)
        sub = jnp.all((scr[:-o] & ~scr[o:]) == 0, axis=1)
        d = svalid[:-o] & eq & sub
        drop = drop | jnp.concatenate([jnp.zeros(o, bool), d])
    # run-first domination at any distance
    iota = jnp.arange(M, dtype=jnp.int32)
    boundary = jnp.concatenate(
        [jnp.ones(1, bool), jnp.any(spw[1:] != spw[:-1], axis=1)])
    starts = lax.cummax(jnp.where(boundary, iota, 0))
    fcr = jnp.take(scr, starts, axis=0)
    fdom = (jnp.all((fcr & ~scr) == 0, axis=1) & (iota != starts)
            & jnp.take(svalid, starts))
    drop = drop | fdom
    return svalid & ~drop, scfgs, perm


def _allpairs_dominance(cfgs, valid, dims: SearchDims):
    """EXACT dominance/dedup prune as one [M, M] comparison — the
    TPU-shaped alternative to `_sort_dominance`.

    The sort pipeline compiles to hundreds of tiny ops (bitonic stages,
    windowed compares, run-first gathers) whose fixed per-op overhead
    floors the on-chip level cost no matter how narrow the live
    frontier is.  This form is a handful of LARGE elementwise ops: for
    every pair (i, j), row i is dropped when a valid row j has the same
    (p, window, state) words and j's crash mask is a subset of i's —
    strictly, or with identical rows tie-broken to the lowest index.

    Unlike the sorted prune (window R=8 + run-first: may KEEP dominated
    rows), this is exact, so it can only shrink levels further — the
    soundness argument of `_sort_dominance` applies unchanged, and
    domination is decided on full words (hashes are never trusted).

    Returns kept over the INPUT row order (no permutation): callers
    compact against the original cfgs, and block-origin tests are plain
    index-range tests.  O(M^2 * WORDS) work and [M, M] intermediates:
    meant for the narrow rungs (S <= ~8k) where the op-count floor —
    not FLOPs — dominates; the driver picks per backend/width."""
    M = cfgs.shape[0]
    u = cfgs.astype(jnp.uint32)
    a = 1 + dims.win_words
    b = a + dims.crash_words
    pw = jnp.concatenate([u[:, :a], u[:, b:]], axis=1)
    cr = u[:, a:b]
    # pairwise equal (p, window, state): fold word compares into [M, M]
    eq_pw = jnp.ones((M, M), bool)
    for w in range(pw.shape[1]):
        col = pw[:, w]
        eq_pw &= col[:, None] == col[None, :]
    # pairwise crash-mask subset (j's ⊆ i's) and equality
    sub = jnp.ones((M, M), bool)   # sub[i, j]: cr_j subset of cr_i
    eq_cr = jnp.ones((M, M), bool)
    for w in range(cr.shape[1]):
        col = cr[:, w]
        sub &= (col[None, :] & ~col[:, None]) == 0
        eq_cr &= col[:, None] == col[None, :]
    iota = jnp.arange(M, dtype=jnp.int32)
    identical = eq_pw & eq_cr
    strict = eq_pw & sub & ~eq_cr
    dom = valid[None, :] & (strict
                            | (identical & (iota[None, :] < iota[:, None])))
    return valid & ~jnp.any(dom, axis=1)


#: dominance-prune implementation: "sort" (windowed sorted prune),
#: "allpairs" (exact [M,M] prune), or "auto" — allpairs on TPU at
#: S <= _ALLPAIRS_MAX rows (where per-op overhead, not FLOPs, floors
#: the level cost), sort everywhere else
_DOMINANCE_MODE = os.environ.get("JEPSEN_TPU_DOMINANCE", "auto")
_ALLPAIRS_MAX = int(os.environ.get("JEPSEN_TPU_ALLPAIRS_MAX", "8192"))
#: cap on batch * M * M elements for a vmapped all-pairs prune — the
#: pairwise masks are [batch, M, M]; past ~256M bools the intermediates
#: stop fitting comfortably between fusions
_ALLPAIRS_ELEMS = int(os.environ.get("JEPSEN_TPU_ALLPAIRS_ELEMS",
                                     str(1 << 28)))


def _use_allpairs(M: int, batch: int = 1) -> bool:
    """Decide the prune implementation for an M-row site.  Called at
    kernel BUILD time only (the builders hoist the result), so the
    decision is always consistent with the cache key computed from the
    same module state."""
    if _DOMINANCE_MODE == "allpairs":
        return batch * M * M <= _ALLPAIRS_ELEMS
    if _DOMINANCE_MODE == "sort":
        return False
    backend = _backend()
    return (backend == "tpu" and M <= _ALLPAIRS_MAX
            and batch * M * M <= _ALLPAIRS_ELEMS)


def _prune_rows(cfgs, valid, M: int, dims: SearchDims,
                use_allpairs: bool):
    """Dominance prune over M rows — the ONE dispatch point shared by
    the single-device, batch, and sharded kernels.  Returns (kept,
    cfgs_out, origin): origin[i] is the input row behind output row i
    (identity for the order-preserving all-pairs path, the sort
    permutation otherwise), so block-origin tests work uniformly."""
    if use_allpairs:
        return (_allpairs_dominance(cfgs, valid, dims), cfgs,
                jnp.arange(M, dtype=jnp.int32))
    pwh, popc = _pw_parts(cfgs, dims)
    return _sort_dominance(pwh, popc, valid, cfgs, M, dims)


def _level_mask(pieces, op_args, frontier, alive):
    """Run the mask phase (enabled candidates + model steps + goal test)
    over a frontier, with the per-level shared table slice."""
    base, sargs = _slice_tables(op_args, frontier, alive,
                                w2p=pieces["w2p"])
    return pieces["expand_mask"](frontier, alive, base, *sargs)


def _succ_block(pieces, frontier, validf, cand2, ns2, cap: int, K: int,
                batch: int = 1):
    """Compact the [F*K] valid lane mask to ``cap`` survivors and build
    their packed successor words.  ``batch`` is the vmap memory-budget
    hint for the compaction."""
    F = frontier.shape[0]
    vsrc, n_valid = _compact_indices(validf, cap, batch)
    row = vsrc // K
    src_cfg = jnp.take(frontier, row, axis=0)
    src_lane = jnp.take(cand2.reshape(F * K), vsrc)
    sw = ns2.shape[-1]
    src_state = jnp.take(ns2.reshape(F * K, sw), vsrc, axis=0)
    cvalid = jnp.arange(cap) < n_valid
    ccfgs, _p2s = pieces["succ"](src_cfg, src_lane, src_state)
    return ccfgs, cvalid, n_valid


def build_search_step_fn(model: ModelSpec, dims: SearchDims,
                         batch: int = 1, *, masked: bool = False,
                         masked_crash: bool = False,
                         dedup: bool = False,
                         telemetry: bool = False):
    """Compile one *slice* of the frontier search for a (model, dims) pair.

    ``batch`` is a hint for the dominance-prune selector only: a vmapped
    instance multiplies every [M, M] all-pairs intermediate by the batch
    size, so the selector needs it to stay inside the memory budget.
    ``masked``/``dedup`` emit the phase-2 reduction checks
    (see _make_kernel_pieces); the signature is identical either way —
    unreduced callers pass inert tables.

    Level-synchronous search where a level's depth counts DETERMINATE
    (:ok) linearizations only; crashed (:info) ops linearize *within* a
    level via an inner closure loop.  Per level:

      1. expand the frontier (mask phase: enabled candidates + model
         steps + goal test on every lane);
      2. crash closure: while any crash successor survives, merge crash
         successors into the level (sort + dominance prune) and
         re-expand — at most n_crash+1 rounds closes the level under
         crash linearization (each genuinely new config adds a crash
         bit), and levels with no enabled crash candidate (the common
         case) skip the loop entirely;
      3. expand determinate successors into the next level (sort +
         dominance prune).

    Co-locating every crash variant of a configuration in one level is
    what makes the dominance prune (`_sort_dominance`) possible — under
    the old depth-counts-everything scheme the variants sat at different
    depths and the crash-subset dimension exploded the frontier (8.5x
    more configs and ~40x wider levels on the 10k-op bench history).
    Depth remains a function of the configuration (d = p + popcount(win),
    crash bits excluded), so dedup still never needs to cross levels and
    there is no global visited table.

    The search state (frontier, count, status, configs, max_depth, ovf) is
    an explicit *carry* passed in and returned, and each call runs at most
    ``lvl_cap`` BFS levels: long searches are driven as a sequence of
    bounded device calls from the host.  A deadline or stop request is
    seen between two calls, and the carry is a natural checkpoint
    (SURVEY.md §5.4's device-side frontier checkpoint) and turns
    ``budget``/``bail`` into runtime scalars so every budget shares one
    compiled program.

    status: -1 running, 2 valid, 1 frontier died out (invalid; sound iff
    not overflowed), 0 unknown.  The final -1 -> verdict mapping happens
    host-side in the slice driver.
    """
    K = dims.k
    F = dims.frontier
    W = dims.window
    S = 4 * F
    pieces = _make_kernel_pieces(model, dims, masked=masked,
                                 masked_crash=masked_crash,
                                 dedup=dedup, telemetry=telemetry)
    # prune implementation per site, decided at BUILD time (consistent
    # with the cache keys, which carry _dominance_key())
    ap_cl = _use_allpairs(2 * F, batch)
    ap_det = _use_allpairs(S, batch)

    def step(det_f, det_v1, det_v2, det_inv, det_ret, sfx_min,
             crash_f, crash_v1, crash_v2, crash_inv, det_mpred,
             det_cpredw, crash_mpred, crash_cpredw, dead_from,
             n_det, n_crash, dead_lo, dead_tok,
             budget, lvl_cap, bail,
             frontier, count, status, configs, max_depth, ovf):
        # telemetry builds thread the per-level aux counter block
        # (obs/telemetry.py schema) through the loop carry and return
        # it as a 7th output; the block is write-only — nothing reads
        # it back, so verdicts stay byte-identical on/off
        carry0 = (frontier, count, status, configs, max_depth, ovf,
                  jnp.int32(0))
        if telemetry:
            carry0 = carry0 + (jnp.zeros((TELE_ROWS, TELE_COLS),
                                         jnp.int32),)
        op_args = (det_f, det_v1, det_v2, det_inv, det_ret, sfx_min,
                   crash_f, crash_v1, crash_v2, crash_inv, det_mpred,
                   det_cpredw, crash_mpred, crash_cpredw, dead_from,
                   n_det, n_crash, dead_lo, dead_tok)

        def mask_phase(frontier, alive):
            return _level_mask(pieces, op_args, frontier, alive)

        def succ_block(frontier, validf, cand2, ns2, cap: int):
            return _succ_block(pieces, frontier, validf, cand2, ns2,
                               cap, K, batch)

        def cond(c):
            _, count, status, configs, _, ovf, lvl = c[:7]
            go = ((status == -1) & (count > 0) & (configs < budget)
                  & (lvl < lvl_cap))
            # when a wider re-run is coming (bail), don't waste time on a
            # truncated (unsound-for-invalid) frontier
            return go & ~(bail & ovf)

        def body(c):
            frontier, count, status, configs, max_depth, ovf, lvl = c[:7]
            tele = c[7] if telemetry else None
            # entry snapshot: if THIS level overflows under bail, the
            # level is not committed and the carry exits at the last
            # clean state — the wider re-run resumes with zero lost
            # levels (the old behavior re-ran every level since the
            # slice began)
            f_in, c_in, cfg_in, md_in, ovf_in = (frontier, count,
                                                 configs, max_depth, ovf)
            alive = jnp.arange(F) < count

            mp = mask_phase(frontier, alive)
            valid2, cand2, ns2, goal2 = mp[:4]
            kil = mp[4].sum() if telemetry else None
            ded = mp[5].sum() if telemetry else None
            found = jnp.any(goal2)
            crash_any = jnp.any(valid2 & (cand2 >= W))

            # --- crash closure (within-level) --------------------------
            def cl_cond(cc):
                it, progress = cc[8], cc[9]
                first = it == 0
                return ((first & crash_any)
                        | (~first & progress & (it < n_crash + 1)))

            def cl_body(cc):
                (frontier, count, valid2, cand2, ns2, _goal2, configs,
                 ovf, it, _pr, found) = cc[:11]
                alive = jnp.arange(F) < count
                cvalidf = (valid2 & (cand2 >= W)).reshape(F * K)
                # crash successors are capped at F rows (not S): they
                # merge back into a <= F-row level, so more than F of
                # them overflows the level anyway — and the merge sort
                # stays at 2F rows instead of 5F
                ccfgs, cvalid, n_valid = succ_block(
                    frontier, cvalidf, cand2, ns2, F)
                ovf = ovf | (n_valid > F)
                merged = jnp.concatenate([frontier, ccfgs], axis=0)
                mvalid = jnp.concatenate([alive, cvalid])
                kept, scfgs, origin = _prune_rows(merged, mvalid, 2 * F,
                                                  dims, ap_cl)
                src, new_count = _compact_indices(kept, F, batch)
                new_frontier = jnp.take(scfgs, src, axis=0)
                ovf = ovf | (new_count > F)
                new_count = jnp.minimum(new_count, F)
                # progress iff any successor-block row survived the
                # merge (input rows >= F).  A merge that only DROPPED
                # existing rows does not require another round:
                # surviving rows' crash successors were all generated
                # and merged this round, and dropped rows are covered by
                # their dominators — the level is closed.
                progress = jnp.any(kept & (origin >= F))
                # configs is NOT bumped here: closure-added rows are
                # part of this level and the det phase counts the closed
                # level's rows once — counting per closure round would
                # inflate the figure (and eat the budget) k+1 times on
                # k-round levels, losing comparability with the host
                # checkers' per-config counts
                # re-expand so the carried expansion always aligns with
                # the (sorted, compacted) frontier rows the det phase
                # will gather from
                alive2 = jnp.arange(F) < new_count
                mp2 = mask_phase(new_frontier, alive2)
                v2, c2, n2, g2 = mp2[:4]
                found = found | jnp.any(g2)
                out = (new_frontier, new_count, v2, c2, n2, g2,
                       configs, ovf, it + 1, progress, found)
                if telemetry:
                    # accumulate closure-round mask kills / dedup folds
                    out = out + (cc[11] + mp2[4].sum(),
                                 cc[12] + mp2[5].sum())
                return out

            # progress starts False: the first iteration is gated on
            # crash_any, and an unentered loop must exit "closed"
            cc0 = (frontier, count, valid2, cand2, ns2, goal2, configs,
                   ovf, jnp.int32(0), jnp.bool_(False), found)
            if telemetry:
                cc0 = cc0 + (kil, ded)
            ccout = lax.while_loop(cl_cond, cl_body, cc0)
            (frontier, count, valid2, cand2, ns2, goal2, configs, ovf,
             _it, pr_exit, found) = ccout[:11]
            if telemetry:
                kil, ded = ccout[11], ccout[12]
            # exiting via the iteration cap while still adding rows
            # means the level was NOT proven closed under crash
            # linearization; that must degrade like an overflow
            # (escalate / unknown), never decide invalid.  Real chains
            # add a crash bit per round (length <= n_crash < cap), so
            # this only fires on pathological duplicate survival.
            ovf = ovf | pr_exit
            alive = jnp.arange(F) < count

            # --- determinate expansion to the next level ---------------
            dvalidf = (valid2 & (cand2 < W)).reshape(F * K)
            dcfgs, dvalid, n_valid = succ_block(
                frontier, dvalidf, cand2, ns2, S)
            ovf = ovf | (n_valid > S)
            kept, scfgs, _origin = _prune_rows(dcfgs, dvalid, S, dims,
                                               ap_det)
            src, new_count = _compact_indices(kept, F, batch)
            new_frontier = jnp.take(scfgs, src, axis=0)
            ovf = ovf | (new_count > F)
            new_count = jnp.minimum(new_count, F)

            configs = configs + count
            max_depth = jnp.maximum(max_depth, jnp.max(
                jnp.where(alive, frontier[:, 0], 0)))
            status = jnp.where(found, 2, status)
            # uncommit an overflowing level when a wider re-run is
            # coming (bail) and no goal was found (a found goal is
            # sound regardless: it was reached through real rows)
            revert = bail & (ovf & ~ovf_in) & ~found
            new_frontier = jnp.where(revert, f_in, new_frontier)
            new_count = jnp.where(revert, c_in, new_count)
            configs = jnp.where(revert, cfg_in, configs)
            max_depth = jnp.where(revert, md_in, max_depth)
            out = (new_frontier, new_count, status, configs, max_depth,
                   ovf, lvl + 1)
            if telemetry:
                # one aux row per level (additive: levels past the
                # buffer fold into the last row; an uncommitted/bailed
                # level still records, flagged by overflow=1), built
                # by column index so kernel row order stays locked to
                # telemetry.COLUMNS
                cols = [None] * TELE_COLS
                cols[C_OCC] = count
                cols[C_EXP] = jnp.sum(valid2, dtype=jnp.int32)
                cols[C_KILL] = kil
                cols[C_DEDUP] = ded
                cols[C_ROUNDS] = _it
                cols[C_NEXT] = new_count
                cols[C_OVF] = (ovf & ~ovf_in).astype(jnp.int32)
                cols[C_GOAL] = found.astype(jnp.int32)
                idx = jnp.minimum(lvl, TELE_ROWS - 1)
                tele = tele.at[idx].add(jnp.stack(cols))
                out = out + (tele,)
            return out

        out = lax.while_loop(cond, body, carry0)
        if telemetry:
            return out[:6] + (out[7],)
        return out[:6]

    return step


# ---------------------------------------------------------------------------
# Mesh-sharded search — one big history's frontier across many devices
# ---------------------------------------------------------------------------


def build_sharded_search_step_fn(model: ModelSpec, dims: SearchDims,
                                 mesh, axis: str = "shard", *,
                                 masked: bool = False,
                                 masked_crash: bool = False,
                                 dedup: bool = False,
                                 telemetry: bool = False):
    """One *slice* of a search whose frontier is sharded over a mesh.

    Each device owns the hash partition ``pw_hash % D`` of the
    configuration space — the hash EXCLUDES the crash words, so every
    crash variant of one (p, window, state) configuration lands on the
    same shard and the local dominance prune (`_sort_dominance`) is
    globally complete, exactly as on a single device.  Per det level:
    devices expand their local slice, close it under crashed-op
    linearization (the closure loop routes crash successors to their
    home shard each round), then route determinate successors home and
    dominance-prune into the next level.  Termination, the goal test,
    closure progress, and overflow are `psum` reductions.  This is the
    scale-out path for histories whose levels outgrow one chip's
    frontier — the reference's analog is simply "buy a bigger JVM heap"
    (-Xmx32g, jepsen/project.clj:25).

    Like `build_search_step_fn`, the search state is an explicit carry
    and each call runs at most ``lvl_cap`` levels, so device executions
    stay bounded.  The per-device frontier slice travels as a global
    ``[D*F, WORDS]`` array sharded on its leading axis; loop-control
    scalars (status, configs, total, any_ovf, closure progress) are
    replicated (psum'd in the body, never in a cond — collectives
    inside a while cond can diverge between devices and deadlock or
    corrupt the all_to_alls; every shard must run the same number of
    closure rounds).

    dims.frontier is the PER-DEVICE frontier width.
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    K = dims.k
    F = dims.frontier
    S = 4 * F
    W = dims.window
    WORDS = dims.words
    D = mesh.shape[axis]
    # per-destination routing capacities (det successors / crash
    # successors per closure round)
    C_DET = max(64, _round_up(S // D, 32))
    C_CR = max(64, _round_up(2 * F // D, 32))

    pieces = _make_kernel_pieces(model, dims, masked=masked,
                                 masked_crash=masked_crash,
                                 dedup=dedup, telemetry=telemetry)
    # prune implementation per merge site, decided at BUILD time.  M
    # already counts every row a device can hold after routing (local F
    # + D routing buckets of C rows), and under shard_map each device
    # materializes exactly ONE [M, M] instance — so batch=1, not D
    # (ADVICE r4: batch=D made the budget test D^3*C^2 and all-pairs
    # was never selected on sharded runs even at widths where it fits,
    # which is the TPU-narrow-rung win the mode exists for).  On a
    # virtual CPU mesh all D instances share one host's RAM, but the
    # budget guards TPU HBM — hosts never select all-pairs anyway.
    ap_cl = _use_allpairs(F + D * C_CR)
    ap_det = _use_allpairs(D * C_DET)

    def route(cfgs, valid, cap: int):
        """all_to_all home-routing by pw-hash.  Returns the received
        rows + validity + a did-any-bucket-overflow flag."""
        pwh, _popc = _pw_parts(cfgs, dims)
        owner = (pwh % np.uint32(D)).astype(jnp.int32)

        def bucket(d):
            mask = valid & (owner == d)
            idx, cnt = _compact_indices(mask, cap, D)
            return jnp.take(cfgs, idx, axis=0), cnt

        send_cfgs, send_cnt = jax.vmap(bucket)(
            jnp.arange(D, dtype=jnp.int32))  # [D, cap, WORDS], [D]
        r_ovf = jnp.any(send_cnt > cap)
        send_cnt = jnp.minimum(send_cnt, cap)
        recv_cfgs = lax.all_to_all(send_cfgs, axis, 0, 0, tiled=False)
        recv_cnt = lax.all_to_all(send_cnt, axis, 0, 0, tiled=False)
        rcfgs = recv_cfgs.reshape(D * cap, WORDS)
        lane = jnp.arange(D * cap) % cap
        rvalid = lane < jnp.repeat(recv_cnt, cap)
        return rcfgs, rvalid, r_ovf

    def merge_dominance(local_cfgs, local_valid, in_cfgs, in_valid,
                        use_ap):
        """Dominance-prune the union of resident + received rows into a
        fresh F-row frontier.  Locality = globality: both inputs are
        pw-home on this shard.  (Exception: the root config starts on
        device 0 whatever its hash — at level 0 it has no siblings, so
        a missed prune there only wastes a row, never drops one.)

        Per-shard merges are narrow by construction (the global
        frontier splits D ways); ``use_ap`` is the build-time selector
        result for this site."""
        merged = jnp.concatenate([local_cfgs, in_cfgs], axis=0)
        mvalid = jnp.concatenate([local_valid, in_valid])
        kept, scfgs, origin = _prune_rows(merged, mvalid,
                                          merged.shape[0], dims, use_ap)
        src, new_count = _compact_indices(kept, F)
        new_frontier = jnp.take(scfgs, src, axis=0)
        m_ovf = new_count > F
        progress = jnp.any(kept & (origin >= local_cfgs.shape[0]))
        return new_frontier, jnp.minimum(new_count, F), m_ovf, progress

    def step_device(det_f, det_v1, det_v2, det_inv, det_ret, sfx_min,
                    crash_f, crash_v1, crash_v2, crash_inv, det_mpred,
                    det_cpredw, crash_mpred, crash_cpredw, dead_from,
                    n_det, n_crash, dead_lo, dead_tok,
                    budget, lvl_cap, bail,
                    frontier, count, status, configs, max_depth,
                    any_ovf, total):
        count = count[0]  # [1] local slice of the [D] count array

        carry0 = (frontier, count, status, configs, max_depth, any_ovf,
                  total, jnp.int32(0))
        if telemetry:
            # per-SHARD aux block: each device records its local
            # counters; the host sums shard blocks per level (levels
            # are lockstep — replicated loop control)
            carry0 = carry0 + (jnp.zeros((TELE_ROWS, TELE_COLS),
                                         jnp.int32),)
        op_args = (det_f, det_v1, det_v2, det_inv, det_ret, sfx_min,
                   crash_f, crash_v1, crash_v2, crash_inv, det_mpred,
                   det_cpredw, crash_mpred, crash_cpredw, dead_from,
                   n_det, n_crash, dead_lo, dead_tok)

        def cond(c):
            _, _, status, configs, _, any_ovf, total, lvl = c[:8]
            go = ((status == -1) & (total > 0) & (configs < budget)
                  & (lvl < lvl_cap))
            return go & ~(bail & any_ovf)

        def body(c):
            frontier, count, status, configs, max_depth, ovf, _total, \
                lvl = c[:8]
            tele = c[8] if telemetry else None
            ovf_in = ovf
            alive = jnp.arange(F) < count
            mp = _level_mask(pieces, op_args, frontier, alive)
            valid2, cand2, ns2, goal2 = mp[:4]
            kil = mp[4].sum() if telemetry else None
            ded = mp[5].sum() if telemetry else None
            found_loc = jnp.any(goal2)
            crash_any = lax.psum(
                jnp.any(valid2 & (cand2 >= W)).astype(jnp.int32),
                axis) > 0

            # --- crash closure (within-level; replicated control) ------
            def cl_cond(cc):
                it, progress = cc[8], cc[9]
                first = it == 0
                return ((first & crash_any)
                        | (~first & progress & (it < n_crash + 1)))

            def cl_body(cc):
                (frontier, count, valid2, cand2, ns2, _goal2, ovf,
                 found_loc, it, _pr) = cc[:10]
                alive = jnp.arange(F) < count
                cvalidf = (valid2 & (cand2 >= W)).reshape(F * K)
                ccfgs, cvalid, n_valid = _succ_block(
                    pieces, frontier, cvalidf, cand2, ns2, F, K)
                ovf = ovf | (n_valid > F)
                rcfgs, rvalid, r_ovf = route(ccfgs, cvalid, C_CR)
                ovf = ovf | r_ovf
                new_frontier, new_count, m_ovf, progress_loc = \
                    merge_dominance(frontier, alive, rcfgs, rvalid,
                                    ap_cl)
                ovf = ovf | m_ovf
                progress = lax.psum(progress_loc.astype(jnp.int32),
                                    axis) > 0
                alive2 = jnp.arange(F) < new_count
                mp2 = _level_mask(pieces, op_args,
                                  new_frontier, alive2)
                v2, c2, n2, g2 = mp2[:4]
                found_loc = found_loc | jnp.any(g2)
                out = (new_frontier, new_count, v2, c2, n2, g2, ovf,
                       found_loc, it + 1, progress)
                if telemetry:
                    out = out + (cc[10] + mp2[4].sum(),
                                 cc[11] + mp2[5].sum())
                return out

            cc0 = (frontier, count, valid2, cand2, ns2, goal2, ovf,
                   found_loc, jnp.int32(0), jnp.bool_(False))
            if telemetry:
                cc0 = cc0 + (kil, ded)
            ccout = lax.while_loop(cl_cond, cl_body, cc0)
            (frontier, count, valid2, cand2, ns2, goal2, ovf, found_loc,
             _it, pr_exit) = ccout[:10]
            if telemetry:
                kil, ded = ccout[10], ccout[11]
            # cap-exit while still adding rows: level not proven closed
            # — degrade like an overflow, never decide invalid
            ovf = ovf | pr_exit
            alive = jnp.arange(F) < count

            # --- determinate successors to the next level --------------
            dvalidf = (valid2 & (cand2 < W)).reshape(F * K)
            dcfgs, dvalid, n_valid = _succ_block(
                pieces, frontier, dvalidf, cand2, ns2, S, K)
            ovf = ovf | (n_valid > S)
            rcfgs, rvalid, r_ovf = route(dcfgs, dvalid, C_DET)
            ovf = ovf | r_ovf
            empty = jnp.zeros((0, WORDS), jnp.int32)
            new_frontier, new_count, m_ovf, _pr = merge_dominance(
                empty, jnp.zeros((0,), bool), rcfgs, rvalid, ap_det)
            ovf = ovf | m_ovf

            found = lax.psum(found_loc.astype(jnp.int32), axis) > 0
            configs = configs + lax.psum(count, axis)
            max_depth = jnp.maximum(max_depth, lax.pmax(jnp.max(
                jnp.where(alive, frontier[:, 0], 0)), axis))
            status = jnp.where(found, 2, status)
            total = lax.psum(new_count, axis)
            any_ovf = lax.psum(ovf.astype(jnp.int32), axis) > 0
            out = (new_frontier, new_count, status, configs, max_depth,
                   any_ovf, total, lvl + 1)
            if telemetry:
                cols = [None] * TELE_COLS
                cols[C_OCC] = count
                cols[C_EXP] = jnp.sum(valid2, dtype=jnp.int32)
                cols[C_KILL] = kil
                cols[C_DEDUP] = ded
                cols[C_ROUNDS] = _it
                cols[C_NEXT] = new_count
                cols[C_OVF] = (ovf & ~ovf_in).astype(jnp.int32)
                cols[C_GOAL] = found_loc.astype(jnp.int32)
                idx = jnp.minimum(lvl, TELE_ROWS - 1)
                out = out + (tele.at[idx].add(jnp.stack(cols)),)
            return out

        cout = lax.while_loop(cond, body, carry0)
        (frontier, count, status, configs, max_depth, any_ovf,
         total) = cout[:7]

        ret = (frontier, count[None], status, configs, max_depth,
               any_ovf, total)
        if telemetry:
            ret = ret + (cout[8],)
        return ret

    specs = (P(),) * 22
    carry_in = (P(axis), P(axis), P(), P(), P(), P(), P())
    carry_out = carry_in + ((P(axis),) if telemetry else ())
    return shard_map(step_device, mesh=mesh,
                     in_specs=specs + carry_in,
                     out_specs=carry_out, check_vma=False)


def _trailing_ones(w):
    """uint32 [..., n] -> per-word count of consecutive 1-bits from bit
    0 (32 when the word is all-ones): popcount((~w & -~w) - 1)."""
    inv = ~w
    lsb = inv & (~inv + np.uint32(1))
    t = lax.population_count(lsb - np.uint32(1))
    return jnp.where(inv == 0, np.uint32(32), t.astype(jnp.uint32))


def _make_kernel_pieces(model: ModelSpec, dims: SearchDims, *,
                        masked: bool = False,
                        masked_crash: bool = False,
                        dedup: bool = False,
                        telemetry: bool = False):
    """Kernel building blocks shared by the single-device, sharded, and
    batch step functions.

    ``masked`` emits the must-order linearized-predecessor check in
    ``expand_mask`` (state-space reduction phase 2): a candidate lane is
    enabled only once every must-predecessor — det positions via the
    prefix/window test ``q < p or win[q - p]``, crash indices via a
    packed-word subset test against the config's crash mask — is
    already linearized, mirroring exactly the host DFS's ``preds`` and
    the `linear` sweep's frame mask.  ``dedup`` emits the dead-value
    canonical-state rewrite (decompose/canonical.py's quotient) on
    successor states, so symmetric interleavings collapse in the
    dominance dedup BEFORE they are expanded apart.  Both default off:
    unreduced searches compile the exact pre-phase-2 kernels.

    The per-level pipeline is split so the expensive successor-word
    construction happens ONLY for compacted survivors:

      * ``expand_mask`` (vmapped over the frontier): per config, find the
        enabled candidates, step the model, and return validity + the
        chosen candidate lane + the successor model state — K lanes per
        config, but NO successor words are built;
      * the step fn compacts the [F*K] valid mask down to S rows;
      * ``succ`` (vmapped over the S survivors): build the packed
        successor words (set-bit, trailing-ones popcount, funnel shift)
        from (source config words, candidate lane, new state).

    At K=16 and S=4F this does the word construction for a quarter of
    the lanes the fused form paid for — and most candidate lanes are
    dead (narrow levels, disabled candidates, illegal steps).
    """
    out = {}
    W, K, NC = dims.window, dims.k, dims.n_crash_pad
    WW, CW, S = dims.win_words, dims.crash_words, dims.state_width
    WORDS = dims.words
    #: width of the per-level shared det-table slice (_slice_tables);
    #: capped at the table so small histories use it whole at base 0
    W2P = min(_round_up(2 * W + NC, 32), dims.n_det_pad)
    out["w2p"] = W2P
    jstep = model.jstep

    def unpack(cfg):
        p = cfg[0]
        win = _unpack_bits(cfg[1:1 + WW], WW)
        crash = _unpack_bits(cfg[1 + WW:1 + WW + CW], CW)[:NC]
        state = cfg[1 + WW + CW:]
        return p, win, crash, state

    def pack(p, win, crash, state):
        crash_pad = jnp.zeros(CW * 32, dtype=bool).at[:NC].set(crash)
        return jnp.concatenate([
            p[None].astype(jnp.int32),
            _pack_bits(win, WW),
            _pack_bits(crash_pad, CW),
            state.astype(jnp.int32),
        ])

    dedup = dedup and dims.state_width == 1

    def expand_mask_one(cfg, alive, base, det_f, det_v1, det_v2,
                        det_inv, det_ret, sfx_min, crash_f, crash_v1,
                        crash_v2, crash_inv, det_mpred, det_cpredw,
                        crash_mpred, crash_cpredw, dead_from, n_det,
                        n_crash, dead_lo, dead_tok):
        # det_* / sfx_min / det_mpred / det_cpredw are the per-level
        # W2P-entry shared slices starting at `base` (_slice_tables);
        # positions stay absolute for comparisons and are rebased only
        # for table lookups.
        p, win, crash, state = unpack(cfg)
        pos = p + jnp.arange(W, dtype=jnp.int32)
        rel = pos - base
        in_range = pos < n_det
        w_ret = jnp.where(in_range & ~win,
                          jnp.take(det_ret, rel, mode="clip"), INF32)
        w_inv = jnp.where(in_range,
                          jnp.take(det_inv, rel, mode="clip"), INF32)
        m1 = jnp.min(w_ret)
        am = jnp.argmin(w_ret)
        lanes = jnp.arange(W, dtype=jnp.int32)
        # second-min via select, not scatter (.at[am].set vmaps into a
        # serialized scatter on TPU)
        m2 = jnp.min(jnp.where(lanes == am, INF32, w_ret))
        sfx = jnp.take(sfx_min,
                       jnp.minimum(p + W, n_det) - base, mode="clip")
        m1_tot = jnp.minimum(m1, sfx)

        excl_w = jnp.where(lanes == am, m2, m1)
        excl_tot = jnp.minimum(excl_w, sfx)
        det_enabled = in_range & ~win & (w_inv < excl_tot)

        c_lanes = jnp.arange(NC, dtype=jnp.int32)
        c_enabled = (c_lanes < n_crash) & ~crash & (crash_inv < m1_tot)

        if telemetry and masked:
            # telemetry taps the PRE-mask enabled sets so the mask's
            # kill count is observable; pure reads — the search math
            # below is untouched (byte-identity fuzzed)
            pre_enabled = (det_enabled.sum(dtype=jnp.int32)
                           + c_enabled.sum(dtype=jnp.int32))

        if masked:
            # must-order mask: a lane stays enabled only once every
            # must-predecessor is linearized.  det preds q are done iff
            # q < p (inside the prefix) or q - p < W with the window
            # bit set; q >= p + W can never be linearized yet, so the
            # lane is blocked.  Crash preds are a packed-word subset
            # test against the config's crash mask.  -1 pads are < p.
            relc = jnp.clip(rel, 0, W2P - 1)
            mp = jnp.take(det_mpred, relc, axis=0)          # [W, P]
            qr = mp - p
            win_at = jnp.take(win, jnp.clip(qr, 0, W - 1))  # [W, P]
            done = (mp < p) | ((qr >= 0) & (qr < W) & win_at)
            det_enabled = det_enabled & done.all(axis=1)
            qc = crash_mpred - p                            # [NC, P]
            win_c = jnp.take(win, jnp.clip(qc, 0, W - 1))
            done_c = ((crash_mpred < p)
                      | ((qc >= 0) & (qc < W) & win_c))
            c_enabled = c_enabled & done_c.all(axis=1)
            if masked_crash:
                # crash-PRED word tests only when some edge actually
                # has a crashed source (identical crashed rows, rf off
                # anchored crashed writes) — det-only masks, the
                # common case, skip the gathers entirely
                crash_w_u = cfg[1 + WW:1 + WW + CW].astype(jnp.uint32)
                cw_u = jnp.take(det_cpredw, relc,
                                axis=0).astype(jnp.uint32)  # [W, CW]
                det_enabled = (det_enabled
                               & ((cw_u & ~crash_w_u[None, :]) == 0)
                               .all(axis=1))
                ccw_u = crash_cpredw.astype(jnp.uint32)     # [NC, CW]
                c_enabled = (c_enabled
                             & ((ccw_u & ~crash_w_u[None, :]) == 0)
                             .all(axis=1))

        enabled = jnp.concatenate([det_enabled, c_enabled])
        cand, n_enabled = _select_enabled(enabled, K)
        cand_on = jnp.arange(K) < n_enabled

        is_det = cand < W
        det_pos = jnp.clip(p + cand - base, 0, W2P - 1)
        c_id = jnp.clip(cand - W, 0, NC - 1)
        cf = jnp.where(is_det, jnp.take(det_f, det_pos),
                       jnp.take(crash_f, c_id))
        cv1 = jnp.where(is_det, jnp.take(det_v1, det_pos),
                        jnp.take(crash_v1, c_id))
        cv2 = jnp.where(is_det, jnp.take(det_v2, det_pos),
                        jnp.take(crash_v2, c_id))

        st = jnp.broadcast_to(state, (K, S))
        new_state, legal = jax.vmap(jstep)(st, cf, cv1, cv2)
        valid = alive & cand_on & legal

        if dedup:
            # dead-value canonical-state rewrite: a successor state
            # whose value every det comparer at positions < p already
            # consumed (and no crashed row ever compares) is
            # observation-equivalent to the token state — rewrite so
            # the dominance dedup collapses symmetric interleavings.
            # p (not p2) keeps the rule conservative: deadness is
            # monotone in the prefix.
            vt = dead_from.shape[0]
            v = new_state[:, 0]
            df = jnp.take(dead_from, jnp.clip(v - dead_lo, 0, vt - 1))
            is_dead = ((v >= dead_lo) & (v < dead_lo + vt)
                       & (p >= df))
            new_state = jnp.where(is_dead[:, None], dead_tok,
                                  new_state)

        # exact goal test WITHOUT successor words: a det candidate is a
        # goal iff it is the last unlinearized det (p2 >= n_det is
        # equivalent to p + popcount(win) + 1 >= n_det); a crash
        # candidate never advances p, so it is a goal only if every det
        # was already linearized.  Computed on ALL K lanes so a goal can
        # never be lost to the survivor cap, even at MAX_FRONTIER where
        # no wider re-run would come.
        remaining = n_det - (p + win.sum(dtype=jnp.int32))
        goal = valid & jnp.where(is_det, remaining <= 1, remaining <= 0)
        if not telemetry:
            return valid, cand, new_state, goal
        # per-config telemetry scalars (aux counter block, obs/
        # telemetry.py): mask-killed lanes and dead-value folds.
        # Computed only in telemetry builds — the off-mode kernel is
        # the exact pre-telemetry graph (separate cache key).
        zero = jnp.int32(0)
        if masked:
            post = (det_enabled.sum(dtype=jnp.int32)
                    + c_enabled.sum(dtype=jnp.int32))
            killed = jnp.where(alive, pre_enabled - post, zero)
        else:
            killed = zero
        if dedup:
            dedupct = jnp.where(
                alive, (valid & is_dead).sum(dtype=jnp.int32), zero)
        else:
            dedupct = zero
        return valid, cand, new_state, goal, killed, dedupct

    def succ_one(cfg, lane, ns):
        """Build one survivor's packed successor words."""
        p = cfg[0]
        win_words = cfg[1:1 + WW].astype(jnp.uint32)
        crash_words = cfg[1 + WW:1 + WW + CW].astype(jnp.uint32)

        is_d = lane < W
        d_lane = jnp.clip(lane, 0, W - 1)
        wi = d_lane >> 5
        bit = (d_lane & 31).astype(jnp.uint32)
        setmask = jnp.where(jnp.arange(WW) == wi,
                            np.uint32(1) << bit, np.uint32(0))
        nw = win_words | setmask  # window with the new bit set

        # shift = run of 1-bits from position 0, chained across words
        t = _trailing_ones(nw)  # [WW]
        shift = jnp.uint32(0)
        open_run = jnp.bool_(True)
        for i in range(WW):
            shift = shift + jnp.where(open_run, t[i], np.uint32(0))
            open_run = open_run & (t[i] == 32)

        # funnel shift right by `shift` across the word array
        s_words = (shift >> 5).astype(jnp.int32)
        s_bits = shift & np.uint32(31)
        idx = jnp.arange(WW) + s_words
        lo = jnp.take(nw, idx, mode="fill", fill_value=np.uint32(0))
        hi = jnp.take(nw, idx + 1, mode="fill",
                      fill_value=np.uint32(0))
        shifted = jnp.where(
            s_bits == 0, lo,
            (lo >> s_bits) | (hi << (np.uint32(32) - s_bits)))

        p2 = jnp.where(is_d, p + shift.astype(jnp.int32), p)
        win2 = jnp.where(is_d, shifted, win_words)

        cl = jnp.clip(lane - W, 0, NC - 1)
        csetmask = jnp.where(
            jnp.arange(CW) == (cl >> 5),
            np.uint32(1) << (cl & 31).astype(jnp.uint32),
            np.uint32(0))
        crash2 = jnp.where(is_d, crash_words,
                           crash_words | csetmask)
        cfg2 = jnp.concatenate([
            p2[None].astype(jnp.int32),
            win2.astype(jnp.int32),
            crash2.astype(jnp.int32),
            ns.astype(jnp.int32)])
        return cfg2, p2

    out["pack"] = pack
    out["expand_mask"] = jax.vmap(expand_mask_one,
                                  in_axes=(0, 0) + (None,) * 20)
    out["succ"] = jax.vmap(succ_one)
    return out


def _slice_tables(op_args, frontier, alive, *, w2p: int):
    """Per-level shared slice of the determinate-op tables.

    Every config in a BFS level shares the level's depth d = p +
    popcount(window) + popcount(crash), so prefix positions span at most
    window + n_crash and every table lookup the level performs lands in
    [min_p, min_p + 2*window + n_crash).  Slicing that strip ONCE per
    level turns every per-lane gather from an n_det_pad-entry table into
    a w2p-entry one — small enough to live in VMEM on TPU, where big-
    table gathers are the expensive lowering.  ``w2p`` is capped at
    n_det_pad by the caller, so small histories degrade to a full-table
    "slice" at base 0 and nothing changes.

    Returns (base, sliced op_args) — positions INSIDE the kernel remain
    absolute for comparisons; only table indexing is rebased.
    """
    (det_f, det_v1, det_v2, det_inv, det_ret, sfx_min, crash_f,
     crash_v1, crash_v2, crash_inv, det_mpred, det_cpredw,
     crash_mpred, crash_cpredw, dead_from, n_det, n_crash,
     dead_lo, dead_tok) = op_args
    n_det_pad = det_f.shape[0]
    p = frontier[:, 0]
    base = jnp.min(jnp.where(alive, p, INF32))
    base = jnp.clip(base, 0, n_det_pad - w2p)

    def sl(a):
        return lax.dynamic_slice(a, (base,), (w2p,))

    def sl2(a):
        return lax.dynamic_slice(a, (base, 0), (w2p, a.shape[1]))

    sfx = lax.dynamic_slice(sfx_min, (base,), (w2p + 1,))
    return base, (sl(det_f), sl(det_v1), sl(det_v2), sl(det_inv),
                  sl(det_ret), sfx, crash_f, crash_v1, crash_v2,
                  crash_inv, sl2(det_mpred), sl2(det_cpredw),
                  crash_mpred, crash_cpredw, dead_from, n_det,
                  n_crash, dead_lo, dead_tok)


_SHARDED_CACHE: dict = {}


def search_opseq_sharded(seq: OpSeq, model: ModelSpec, mesh, *,
                         axis: str = "shard",
                         budget: int = 20_000_000,
                         frontier_per_device: int = 1024,
                         deadline: float | None = None,
                         stop=None, on_slice=None,
                         lint: bool | None = None,
                         audit: bool | None = None,
                         hb: bool | None = None,
                         dpor: bool | None = None) -> dict:
    """Check one history with its frontier sharded over `mesh`.

    ``deadline``/``stop``/``on_slice(carry, dims)`` mirror
    `search_opseq`: the drive ends between slices past the deadline
    (verdict "unknown"), and every slice's carry reaches the hook.
    The sharded carry ([D*F, WORDS] frontier, [D] counts, replicated
    counters + total) is NOT `save_checkpoint`-compatible — that format
    is the single-device 6-tuple; the escalation loop here resumes
    from in-memory carries only.

    Certificates mirror `search_opseq`: greedy/trivial verdicts carry
    their ``linearization``; sharded device verdicts carry the explicit
    ``witness_dropped``/``frontier_dropped`` reasons (no shard keeps
    parent chains), so a mesh verdict is never silently witness-less;
    ``audit`` replays whatever certificate is emitted (None follows
    JEPSEN_TPU_AUDIT).  ``hb``/``dpor`` run the static prepass and
    thread the must-order/dedup planes exactly as on one device; the
    dead-token rewrite happens BEFORE shard routing, so every copy of
    a collapsed state still hashes to the same home shard and the
    local dominance prune stays globally complete."""
    from ..analyze.audit import maybe_audit
    from ..analyze.dpor import resolve_dpor
    from ..analyze.hb import attach, maybe_hb
    from ..analyze.lint import maybe_lint

    maybe_lint(seq, model, lint)
    hbres = maybe_hb(seq, model, hb, dpor)

    def finish(out: dict) -> dict:
        return maybe_audit(seq, model, attach(out, hbres), audit)

    if hbres is not None and hbres.decided is not None:
        return _tele.emit_decided(
            maybe_audit(seq, model, dict(hbres.decided), audit),
            hbres=hbres)
    es = encode_search(seq)
    if es.n_det == 0 and es.n_crash == 0:
        return finish({"valid": True, "configs": 0, "max_depth": 0,
                       "engine": "trivial", "linearization": []})
    if greedy_witness(seq, model):
        return finish({"valid": True, "configs": es.n_det,
                       "max_depth": es.n_det,
                       "engine": "greedy-witness",
                       "linearization": greedy_linearization(seq)})
    if es.window > MAX_WINDOW or es.n_crash > MAX_CRASH:
        from .linear import check_opseq_linear

        out = check_opseq_linear(seq, model, deadline=deadline,
                                 cancel=stop, lint=False, hb=hb,
                                 dpor=dpor)
        out["engine"] = "host-linear(fallback)"
        return finish(out)

    dims = choose_dims(es, model, frontier=frontier_per_device)
    if resolve_dpor(dpor):
        attach_reductions(es, seq, model,
                          hbres.must_pred if hbres is not None
                          else None, dedup=True)
    esp = pad_search(es, dims.n_det_pad, dims.n_crash_pad)
    _masked, _mcrash, _dedup, _vt = _reduction_key(esp)
    D = mesh.shape[axis]
    tele_on = _tele.enabled()
    acc = _tele.SearchTelemetry("device-sharded") if tele_on else None
    resume = None
    while True:
        bail = dims.frontier < MAX_FRONTIER
        mesh_key = (tuple(mesh.shape.items()),
                    tuple(d.id for d in mesh.devices.flat))
        key = (model.name, dims, axis, mesh_key, _dominance_key(),
               _masked, _mcrash, _dedup, _vt, tele_on)
        fn = _SHARDED_CACHE.get(key)
        _kc_record(fn is not None)
        if fn is None:
            # full cache-key coords, like every other route's span —
            # K007 (analyze/devlint.py) flags a device-sharded compile
            # span that only names the frontier as coord drift
            with _tele.compile_span(engine="device-sharded",
                                    shards=D, frontier=dims.frontier,
                                    n_det_pad=dims.n_det_pad,
                                    n_crash_pad=dims.n_crash_pad,
                                    window=dims.window, k=dims.k,
                                    masked=_masked,
                                    masked_crash=_mcrash,
                                    dedup=_dedup, vt=_vt,
                                    model=model.name,
                                    model_init=int(model.init[0]),
                                    model_width=model.state_width):
                fn = jax.jit(build_sharded_search_step_fn(
                    model, dims, mesh, axis, masked=_masked,
                    masked_crash=_mcrash, dedup=_dedup,
                    telemetry=tele_on))
            _SHARDED_CACHE[key] = fn
        args = search_args(esp, es)
        if resume is not None:
            carry0 = tuple(jnp.asarray(c) for c in resume)
        else:
            # global carry: device 0's frontier row 0 holds the root
            frontier0 = np.zeros((D * dims.frontier, dims.words),
                                 np.int32)
            frontier0[0] = _init_config(dims, model)
            count0 = np.zeros(D, np.int32)
            count0[0] = 1
            carry0 = (jnp.asarray(frontier0), jnp.asarray(count0),
                      jnp.int32(-1), jnp.int32(0), jnp.int32(0),
                      jnp.bool_(False), jnp.int32(1))

        def sc(carry, i):
            return int(np.asarray(carry[i]).reshape(-1)[0])

        def call(carry, lvl_cap):
            t0 = time.perf_counter()
            res = fn(*args, jnp.int32(budget), jnp.int32(lvl_cap),
                     jnp.bool_(bail), *carry)
            if acc is not None:
                # per-shard blocks [D*R, C] -> per-level shard sum
                # (levels run lockstep under replicated loop control)
                jax.block_until_ready(res)
                try:
                    t = np.asarray(res[7]).reshape(
                        D, TELE_ROWS, TELE_COLS).sum(axis=0)
                    acc.add_slice(t, t0, time.perf_counter(),
                                  frontier=dims.frontier)
                except Exception:  # noqa: BLE001 — non-addressable
                    pass           # multihost shards: totals only
                res = res[:7]
            return res

        def is_active(carry):
            return (sc(carry, 2) == -1 and sc(carry, 6) > 0
                    and sc(carry, 3) < budget
                    and not (bail and sc(carry, 5)))

        prev = [carry0]

        def track(carry):
            if not sc(carry, 5):  # clean (pre-overflow) carry
                prev[0] = carry
            if on_slice is not None:
                on_slice(carry, dims)

        carry = _drive_slices(call, carry0, is_active, on_slice=track,
                              deadline=deadline, stop=stop)
        status = sc(carry, 2)
        configs = sc(carry, 3)
        ovf = bool(sc(carry, 5))
        total = sc(carry, 6)
        timed_out = ((deadline is not None
                      and time.perf_counter() > deadline)
                     or (stop is not None and stop.is_set()))
        if status == -1:
            status = (UNKNOWN if ovf else INVALID) if total <= 0 \
                else UNKNOWN
        if (status == UNKNOWN and ovf and not timed_out
                and dims.frontier < MAX_FRONTIER):
            # escalate, resuming from the last clean carry: each
            # device's frontier block zero-pads from F to F' rows
            new_f = _grid_width(dims.frontier * 4)
            resume = _widen_sharded_carry(prev[0], D, dims.frontier,
                                          new_f)
            dims = SearchDims(**{**dims.__dict__, "frontier": new_f})
            continue
        break
    out = {"valid": _STATUS[status],
           "configs": configs,
           "max_depth": int(np.asarray(carry[4]).reshape(-1)[0]),
           "engine": f"device-sharded-x{mesh.shape[axis]}",
           "frontier_per_device": dims.frontier}
    # certificate contract (satellite of the phase-2 PR): the mesh
    # route states WHY a verdict ships without a witness/frontier,
    # exactly like the single-device engine — and the audit pass can
    # therefore replay it (W002 would flag a certificate-less verdict)
    if out["valid"] is True:
        out["witness_dropped"] = WITNESS_DROPPED_DEVICE
    elif out["valid"] is False:
        out["frontier_dropped"] = FRONTIER_DROPPED_DEVICE
    _tele.finalize_result(out, acc, hbres=hbres)
    return finish(out)


# ---------------------------------------------------------------------------
# Host driver
# ---------------------------------------------------------------------------

_KERNEL_CACHE: dict = {}

#: compiled-kernel cache accounting across get_kernel/get_batch_kernel/
#: the sharded cache — the bucketed batch scheduler's bench evidence
#: that steady-state runs never retrace (a memoized kernel costs a dict
#: lookup; a miss costs a trace + XLA compile)
KERNEL_CACHE_STATS = {"hits": 0, "misses": 0}


def kernel_cache_stats() -> dict:
    """Snapshot of the process-lifetime kernel-cache counters."""
    return dict(KERNEL_CACHE_STATS)


def _kc_record(hit: bool) -> None:
    """One kernel-cache lookup, counted in BOTH sinks: the legacy
    process dict (bucket_batch deltas, bench rows) and the flight-
    recorder registry (/metrics jtpu_kernel_cache_total)."""
    KERNEL_CACHE_STATS["hits" if hit else "misses"] += 1
    _M_KCACHE.inc(event="hit" if hit else "miss")

#: initial BFS levels per device call; the driver adapts from here so
#: each call lands near _SLICE_TARGET_S seconds of device time (the
#: host sees deadlines, stop requests and checkpoints only between
#: calls; longer slices amortize dispatch to near-zero overhead)
_SLICE_LEVELS0 = int(os.environ.get("JEPSEN_TPU_SLICE_LEVELS", "32"))
_SLICE_TARGET_S = float(os.environ.get("JEPSEN_TPU_SLICE_TARGET_S", "2.0"))
_SLICE_MAX = 16384

#: per-slice trace lines on stderr (width, cap, wall, live rows, configs,
#: depth) — with this on, the last trace line of a search that stalls
#: names the slice it stalled in
_TRACE_SLICES = os.environ.get("JEPSEN_TPU_TRACE_SLICES", "") not in ("",
                                                                      "0")


def _trace(msg: str) -> None:
    if _TRACE_SLICES:
        print(f"slice: {msg}", file=sys.stderr, flush=True)


_SLICE_HARD_S: float | None = None


def _slice_hard_s() -> float:
    """Hard bound on a single device call's predicted wall time.

    A device call cannot be interrupted, so its length is how late a
    deadline or stop request can be honoured and how much work a
    crash loses since the last checkpoint.  The level cap is clamped
    so a slice predicted from the measured per-level rate stays under
    this bound (JEPSEN_TPU_SLICE_HARD_S, default 20 s) on every
    backend."""
    global _SLICE_HARD_S
    if _SLICE_HARD_S is None:
        _SLICE_HARD_S = float(os.environ.get("JEPSEN_TPU_SLICE_HARD_S",
                                             "20"))
    return _SLICE_HARD_S


def _adapt_lvl_cap(lvl_cap: int, dt: float,
                   target_s: float | None = None) -> int:
    """Grow/shrink the per-call level cap toward the target slice time.

    The x16 rung matters where per-level cost is at the µs scale (the
    pallas engine): a x4-only ramp from 32 levels pays ~6 extra
    dispatches, each with its host round trip, before the cap covers a
    deep search."""
    t = _SLICE_TARGET_S if target_s is None else target_s
    if dt < t / 16:
        return min(lvl_cap * 16, _SLICE_MAX)
    if dt < t / 4:
        return min(lvl_cap * 4, _SLICE_MAX)
    if dt < t / 2:
        return min(lvl_cap * 2, _SLICE_MAX)
    if dt > t * 2:
        return max(lvl_cap // 2, 8)
    return lvl_cap


def _drive_slices(call, carry, is_active, *, on_slice=None,
                  deadline: float | None = None, stop=None):
    """Shared host loop for the batch and sharded kernels.  (The
    single-device path has its own driver inside ``_run_kernel``: it
    re-keys the kernel between slices as the frontier width adapts,
    which this fixed-kernel loop cannot express.)

    ``call(carry, lvl_cap)`` runs one bounded device slice;
    ``is_active(carry)`` says whether another slice is needed;
    ``on_slice(carry)`` is the checkpoint hook.  ``deadline``
    (perf_counter clock) / ``stop`` (threading.Event) end the drive
    between slices with the carry as-is — still-active carries map to
    an "unknown" verdict in the callers.  The first slice's wall time
    includes trace+compile, so it never feeds cap adaptation."""
    from .. import obs

    lvl_cap = _SLICE_LEVELS0
    first = True
    while True:
        t0 = time.perf_counter()
        with obs.span("device.slice", cat="device", levels=lvl_cap,
                      first=first):
            carry = call(carry, lvl_cap)
            jax.block_until_ready(carry)
        dt = time.perf_counter() - t0
        _tele.record_device_seconds(dt)
        if on_slice is not None:
            on_slice(carry)
        if not is_active(carry):
            return carry
        if deadline is not None and time.perf_counter() > deadline:
            return carry
        if stop is not None and stop.is_set():
            return carry
        if not first:
            lvl_cap = _adapt_lvl_cap(lvl_cap, dt)
        first = False


def _round_up(x: int, m: int) -> int:
    return ((max(1, x) + m - 1) // m) * m


def _init_config(dims: SearchDims, model: ModelSpec) -> np.ndarray:
    """Root configuration words: p=0, empty window/crash masks, init
    state."""
    cfg = np.zeros(dims.words, np.int32)
    cfg[1 + dims.win_words + dims.crash_words:] = np.asarray(
        model.init, np.int32)
    return cfg


def _init_carry(dims: SearchDims, model: ModelSpec):
    """Fresh single-device search carry (also the checkpoint format)."""
    frontier = np.zeros((dims.frontier, dims.words), np.int32)
    frontier[0] = _init_config(dims, model)
    return (frontier, np.int32(1), np.int32(-1), np.int32(0),
            np.int32(0), np.bool_(False))


def _widen_carry(carry, old_f: int, new_f: int):
    """Zero-pad a carry's frontier from old_f to new_f rows (frontier
    escalation without restarting the search)."""
    frontier = np.zeros((new_f, np.asarray(carry[0]).shape[1]), np.int32)
    frontier[:old_f] = np.asarray(carry[0])
    return (frontier,) + tuple(np.asarray(c) for c in carry[1:])


def _widen_sharded_carry(carry, d: int, old_f: int, new_f: int):
    """Widen a sharded carry's global [D*F, WORDS] frontier to
    [D*F', WORDS], keeping each device's rows in its own block."""
    fr = np.asarray(carry[0]).reshape(d, old_f, -1)
    fr2 = np.zeros((d, new_f, fr.shape[2]), np.int32)
    fr2[:, :old_f] = fr
    return (fr2.reshape(d * new_f, -1),) + tuple(
        np.asarray(c) for c in carry[1:])


def _dominance_key():
    """Everything the prune/compaction selectors depend on — part of
    the kernel cache key so a mode flip (tests; env overrides) can't
    reuse a kernel built for the other implementation."""
    backend = _backend()
    return (_DOMINANCE_MODE, _ALLPAIRS_MAX, _ALLPAIRS_ELEMS,
            _COMPACT_MODE, _COMPACT_ELEMS, backend)


#: level-kernel implementation: "xla" (build_search_step_fn),
#: "pallas" (pallas_level's fused level-loop kernel), or "auto" —
#: pallas on TPU whenever the dims/model are eligible (the narrow,
#: depth-dominated regime where the XLA body's op-count floor sets
#: the per-level cost), xla everywhere else
_ENGINE_MODE = os.environ.get("JEPSEN_TPU_ENGINE", "auto")

#: the ACTIVE single-device slice driver's cumulative "any slice
#: executed on pallas" flag (thread-local: the competition checker
#: races the device leg in a thread).  save_checkpoint reads it so a
#: checkpoint written mid-run records the search's real engine
#: history; None outside a driver.
_RUN_PALLAS = threading.local()


def _use_pallas(model: ModelSpec, dims: SearchDims, *,
                masked: bool = False, dedup: bool = False) -> bool:
    if _ENGINE_MODE == "xla":
        return False
    from . import pallas_level

    if not pallas_level.eligible(model, dims, masked=masked,
                                 dedup=dedup):
        return False
    if _ENGINE_MODE == "pallas":
        return True
    backend = _backend()
    return backend == "tpu"


def _reduction_key(esp: EncodedSearch | None) -> tuple:
    """(masked, dedup, dead-table width) — the phase-2 part of every
    kernel cache key.  The dead table's width is a traced SHAPE, so two
    histories with different widths cannot share a compiled kernel
    even when both have dedup off (the inert table still traces)."""
    if esp is None:
        return (False, False, False, 8)
    vt = esp.dead_from.shape[0] if esp.dead_from is not None else 8
    return (bool(esp.masked), bool(esp.mask_has_crash),
            bool(esp.dedup), int(vt))


def get_kernel(model: ModelSpec, dims: SearchDims, *,
               masked: bool = False, masked_crash: bool = False,
               dedup: bool = False, vt: int = 8,
               telemetry: bool = False):
    use_p = _use_pallas(model, dims, masked=masked, dedup=dedup)
    key = (model.name, dims, _dominance_key(), masked, masked_crash,
           dedup, vt, telemetry, "pallas" if use_p else "xla")
    fn = _KERNEL_CACHE.get(key)
    _kc_record(fn is not None)
    if fn is None:
        # a miss is a trace + XLA compile: the device.compile span is
        # the cold-start tax's trace evidence (the hit path is a dict
        # get and never enters here)
        # FULL cache-key coordinates (model descriptor + phase-2 flags
        # included): fleet/warmup.py reconstructs this exact kernel
        # from the recorded span, and analyze/devlint.py's K007 check
        # verifies the coord set against its static cache-key model
        with _tele.compile_span(engine="pallas" if use_p else "xla",
                                frontier=dims.frontier,
                                n_det_pad=dims.n_det_pad,
                                n_crash_pad=dims.n_crash_pad,
                                window=dims.window, k=dims.k,
                                masked=masked, masked_crash=masked_crash,
                                dedup=dedup, vt=vt,
                                model=model.name,
                                model_init=int(model.init[0]),
                                model_width=model.state_width):
            if use_p:
                from . import pallas_level

                # off-TPU the pallas kernel runs in interpret mode
                # (tests; forced-engine differential fuzz); on a TPU a
                # Mosaic lowering failure raises — there is no
                # fallback to the XLA kernel
                backend = _backend()
                fn = jax.jit(pallas_level.build_pallas_step_fn(
                    model, dims, interpret=backend != "tpu",
                    masked=masked, telemetry=telemetry))
            else:
                fn = jax.jit(build_search_step_fn(
                    model, dims, masked=masked,
                    masked_crash=masked_crash, dedup=dedup,
                    telemetry=telemetry))
        _KERNEL_CACHE[key] = fn
    return fn


def _strip_reductions_for_pallas(es: EncodedSearch, model: ModelSpec,
                                 dims: SearchDims) -> EncodedSearch:
    """Reduction-vs-engine priority call: where the pallas fused-loop
    kernel would be selected (narrow, depth-dominated searches on TPU
    or a forced-pallas mode), the must-order mask and dedup rewrite
    are DROPPED so the search keeps its zero-per-op-overhead engine —
    both reductions are optional prunes, and in that regime the fused
    loop's op-count win dominates anything the prune saves (see
    pallas_level's module doc).  Everywhere else the reductions stay
    and the XLA kernel emits the checks."""
    if (es.masked or es.dedup) and _use_pallas(model, dims):
        es.det_mpred = es.det_cpred = None
        es.crash_mpred = es.crash_cpred = None
        es.det_cpredw = es.crash_cpredw = None
        es.dead_from = None
        es.dead_lo = es.dead_tok = 0
        es.masked = es.mask_has_crash = es.dedup = False
    return es


def search_args(esp: EncodedSearch, es: EncodedSearch | None = None):
    """The positional device-arg tuple for the step kernels — ONE home
    for the signature (the single-device and sharded drivers consume
    it; the batch paths stack the same attributes via stack_batch).
    ``es`` supplies the true n_det/n_crash when ``esp`` is padded."""
    src = es if es is not None else esp
    # byte-counted host->device staging (obs/telemetry.py): these are
    # the argument tables the next device dispatch uploads
    _tele.record_transfer(_tele.transfer_bytes(
        (esp.det_f, esp.det_v1, esp.det_v2, esp.det_inv, esp.det_ret,
         esp.suffix_min_ret, esp.crash_f, esp.crash_v1, esp.crash_v2,
         esp.crash_inv, esp.det_mpred, esp.det_cpredw, esp.crash_mpred,
         esp.crash_cpredw, esp.dead_from)))
    return (
        jnp.asarray(esp.det_f), jnp.asarray(esp.det_v1),
        jnp.asarray(esp.det_v2), jnp.asarray(esp.det_inv),
        jnp.asarray(esp.det_ret), jnp.asarray(esp.suffix_min_ret),
        jnp.asarray(esp.crash_f), jnp.asarray(esp.crash_v1),
        jnp.asarray(esp.crash_v2), jnp.asarray(esp.crash_inv),
        jnp.asarray(esp.det_mpred), jnp.asarray(esp.det_cpredw),
        jnp.asarray(esp.crash_mpred), jnp.asarray(esp.crash_cpredw),
        jnp.asarray(esp.dead_from),
        jnp.int32(src.n_det), jnp.int32(src.n_crash),
        jnp.int32(esp.dead_lo), jnp.int32(esp.dead_tok))


def _next_pow2(x: int) -> int:
    return 1 << max(0, (x - 1)).bit_length()


def choose_dims(es: EncodedSearch, model: ModelSpec, *,
                frontier: int | None = None) -> SearchDims:
    """Pick kernel dimensions, quantized (powers of two / multiples of 32)
    so that differently-sized histories share compiled kernels."""
    W = _round_up(es.window, 32)
    NC = _round_up(es.n_crash, 32) if es.n_crash else 32
    K = _next_pow2(min(es.concurrency, W + es.n_crash))
    if frontier is None:
        # start narrow: most BFS levels are far smaller than the history;
        # the adaptive driver widens on overflow and narrows again when
        # the live frontier shrinks (on the power-of-two width grid)
        frontier = _grid_width(min(4096, (es.n_det + es.n_crash) // 8))
    return SearchDims(
        n_det_pad=max(64, _next_pow2(es.n_det)),
        n_crash_pad=NC,
        window=W,
        k=max(1, K),
        state_width=model.state_width,
        frontier=frontier,
    )


#: statuses
VALID, INVALID, UNKNOWN = 2, 1, 0
_STATUS = {2: True, 1: False, 0: "unknown"}

#: refuse device search past these (fall back to host oracle)
MAX_WINDOW = 512
MAX_CRASH = 64
#: widest shared-batch frontier rung; keys needing more go solo (the
#: solo ladder resumes from clean carries and widens to MAX_FRONTIER)
BATCH_FRONTIER_CAP = 512


#: frontier-width grid: powers of two from 64 to 256k.  Per-level cost
#: is proportional to width, so the finer grid (vs the old power-of-4
#: one) halves the cost of levels whose live width sits just past a
#: boundary — dominance pruning makes that the common case (e.g. the
#: 10k bench history peaks at ~1.2k rows: F=2048, not 4096).  The
#: adaptive driver still compiles only the widths a search visits, and
#: the persistent compile cache amortizes them across runs.
MAX_FRONTIER = 1 << 18


_WIDTH_FLOOR: int | None = None


def _width_floor() -> int:
    """Narrowest frontier rung, decided per backend (lazily — the
    backend may be pinned after import).

    CPU floor 16: near-deterministic histories (a mutex under low
    contention holds ONE live config for thousands of levels) ride the
    narrow rungs, where per-level cost tracks the frontier actually
    alive — at a floor of 64 such searches paid 64 lanes for 1 live
    row every level.  TPU floor 64: the TPU pads narrow shapes to its
    8x128 vector tiles anyway, so a narrower rung saves little per
    level, while every extra rung visited costs an escalation bail and
    one more kernel compile."""
    global _WIDTH_FLOOR
    if _WIDTH_FLOOR is not None:
        return _WIDTH_FLOOR
    want = 0
    env = os.environ.get("JEPSEN_TPU_WIDTH_FLOOR")
    if env:
        try:
            v = int(env)
        except ValueError:
            v = 0  # unparsable override: fall back to the backend
        # values below the 8-row minimum (incl. 0) also fall back —
        # "0" must mean "no override", not "narrowest possible"
        want = min(v, MAX_FRONTIER) if v >= 8 else 0
    if not want:
        backend = _backend()
        want = 64 if backend == "tpu" else 16
    # snap onto the power-of-two grid (and under MAX_FRONTIER) so
    # differently-sized histories keep sharing compiled kernels
    w = 8
    while w < want:
        w *= 2
    _WIDTH_FLOOR = min(w, MAX_FRONTIER)
    return _WIDTH_FLOOR


def _grid_width(f: int) -> int:
    """Snap up to the power-of-two width grid, clamped to MAX_FRONTIER
    and floored per backend (see :func:`_width_floor`)."""
    w = _width_floor()
    while w < f and w < MAX_FRONTIER:
        w *= 2
    return w


def _run_kernel(esp: EncodedSearch, es: EncodedSearch, model: ModelSpec,
                dims: SearchDims, budget: int, *,
                escalate: bool = True, on_slice=None, resume=None,
                deadline: float | None = None, stop=None,
                used_pallas0: bool = False):
    """Drive the sliced kernel to completion with an adaptive width.

    The frontier width moves both ways on the power-of-two grid
    (escalation climbs two steps at a time, the downshift settles one):

    * a level that overflows the current width is UNCOMMITTED by the
      kernel (the ``bail`` flag): the slice exits holding the last clean
      frontier, and the search resumes two grid steps (4x) wider from
      exactly there — zero levels re-run;
    * when the live frontier shrinks well below the current width, the
      carry (live rows are prefix-compacted by the kernel) is truncated
      a grid step down, so per-level cost tracks the frontier actually
      alive rather than its high-water mark.  Deep histories alternate
      narrow valleys with rare wide bursts; without the downshift one
      burst taxes every later level at the burst's width.

    Returns (status, configs, max_depth, dims, used_pallas):
    ``used_pallas`` is True iff any slice executed on the pallas
    level-loop engine, OR ``used_pallas0`` was passed (the resumed
    checkpoint's accumulated flag — label evidence); the live value is
    mirrored into the `_RUN_PALLAS` thread-local around each on_slice
    call so checkpoint saves record it; status is finalized
    (-1 never escapes), dims reflects the final width.  ``on_slice(carry,
    dims)`` fires after every device call (the checkpoint hook);
    ``resume`` accepts a previously captured carry at ``dims.frontier``
    width.  ``deadline`` (``time.perf_counter()`` clock) stops cleanly
    with status UNKNOWN when exceeded — for time-bounded throughput runs.
    """
    args = search_args(esp, es)
    _masked, _mcrash, _dedup, _vt = _reduction_key(esp)
    carry = tuple(jnp.asarray(c) for c in
                  (resume if resume is not None
                   else _init_carry(dims, model)))
    F = dims.frontier
    lvl_cap = _SLICE_LEVELS0
    first = True
    timed_out = False
    low_streak = 0  # consecutive slices whose live width fit a lower rung
    per_lvl: float | None = None  # measured seconds/level at width F
    prev_depth = int(np.asarray(carry[4]))
    hard_s = _slice_hard_s()
    tele_on = _tele.enabled()
    acc = _tele.SearchTelemetry() if tele_on else None

    def _clamp_cap(cap: int) -> int:
        # keep a slice's PREDICTED wall under the hard bound; the
        # estimate tracks the current width (scaled on width changes)
        if per_lvl and per_lvl > 0 and hard_s != float("inf"):
            return max(8, min(cap, int(hard_s / per_lvl)))
        return cap

    used_pallas = used_pallas0  # any slice (incl. resumed-from runs)
    #                             ran on the pallas engine
    while True:
        bail = escalate and F < MAX_FRONTIER
        want_pallas = _use_pallas(model, dims, masked=_masked,
                                  dedup=_dedup)
        fn = get_kernel(model, dims, masked=_masked,
                        masked_crash=_mcrash, dedup=_dedup, vt=_vt,
                        telemetry=tele_on)
        _trace(f"run F={F} cap={lvl_cap} first={int(first)} "
               f"depth={prev_depth}")
        t0 = time.perf_counter()
        tele_buf = None
        with obs.span("device.slice", cat="device", frontier=F,
                      levels=lvl_cap, first=first):
            res = fn(*args, jnp.int32(budget), jnp.int32(lvl_cap),
                     jnp.bool_(bail), *carry)
            if tele_on:
                carry, tele_buf = res[:6], res[6]
            else:
                carry = res
            jax.block_until_ready(carry)
        used_pallas = used_pallas or want_pallas
        dt = time.perf_counter() - t0
        _tele.record_device_seconds(dt)
        if acc is not None and tele_buf is not None:
            acc.add_slice(np.asarray(tele_buf), t0, t0 + dt,
                          frontier=F)
        if on_slice is not None:
            _RUN_PALLAS.flag = used_pallas
            try:
                on_slice(carry, dims)
            finally:
                _RUN_PALLAS.flag = None
        status = int(carry[2])
        count = int(carry[1])
        configs = int(carry[3])
        ovf = bool(carry[5])
        depth = int(carry[4])
        _trace(f"done F={F} cap={lvl_cap} dt={dt:.3f}s count={count} "
               f"configs={configs} depth={depth} ovf={int(ovf)} "
               f"status={status}")
        levels_run = depth - prev_depth
        prev_depth = depth
        if not first and levels_run > 0:
            per_lvl = dt / levels_run
        if status != -1 or count <= 0 or configs >= budget:
            break
        if deadline is not None and time.perf_counter() > deadline:
            timed_out = True
            break
        if stop is not None and stop.is_set():
            timed_out = True
            break
        if bail and ovf:
            # the kernel uncommits an overflowing level before bailing,
            # so the carry it returned IS the last clean state: resume
            # wider from right here, zero levels re-run.  climb fast
            # (x4): a growth phase that doubles per level would
            # otherwise pay a bailed slice per grid step; the downshift
            # below settles onto the tight width afterwards
            new_f = _grid_width(F * 4)
            base = tuple(carry[:5]) + (jnp.bool_(False),)
            carry = tuple(jnp.asarray(c) for c in
                          _widen_carry(base, F, new_f))
            low_streak = 0  # a burst just proved the width necessary
            # per-level cost scales with width: shrink the level cap by
            # the same ratio or the first wide slice runs lvl_cap
            # narrow-sized levels at 4x the cost (enough to blow a
            # wall-clock deadline)
            lvl_cap = max(8, lvl_cap * F // new_f)
            if per_lvl:
                per_lvl *= new_f / F  # per-level cost tracks width
            lvl_cap = _clamp_cap(lvl_cap)
            F = new_f
            dims = SearchDims(**{**dims.__dict__, "frontier": F})
            first = True  # next slice includes a compile
            continue
        if not first:
            # shorter slices while WIDE: the downshift check runs only
            # between slices, so a full-length slice at F=2048 would run
            # hundreds of post-burst narrow levels at 8x their cost
            # before the width could settle back down
            lvl_cap = _clamp_cap(_adapt_lvl_cap(
                lvl_cap, dt,
                target_s=(_SLICE_TARGET_S if F <= 512
                          else _SLICE_TARGET_S / 4)))
        first = False
        if not ovf and count > 0:
            # 4x headroom over the live width, with hysteresis: only
            # downshift after TWO consecutive slices fit the lower rung
            # (A/B'd against one-slice hysteresis: the register tier
            # thrashed 2x; see docs/perf-notes.md round 4).
            # A transient valley between wide bursts would otherwise
            # bounce the width (each bounce = a bailed slice + re-run
            # levels), which costs more than it saves — the register
            # tier thrashed 2x when the floor dropped to 16 without
            # this guard, while sustained-narrow searches (mutex) still
            # settle onto the tight width one slice later.
            # ONE grid step down, not straight to grid(4*count): the
            # overflow that sets the needed width is the EXPANSION burst
            # (successors before prune), which runs far above the pruned
            # live count — dropping to the count-derived width was
            # observed (r4 10k trace) to re-overflow within a level or
            # two, costing a bail + reclimb every few slices
            new_f = max(_grid_width(4 * count), F // 2)
            if new_f < F:
                low_streak += 1
            else:
                low_streak = 0
            if new_f < F and low_streak >= 2:
                low_streak = 0
                # live rows sit at the frontier's prefix: truncate
                carry = (carry[0][:new_f],) + tuple(carry[1:])
                # cheaper levels: grow the cap by the width ratio so
                # slice wall time stays near the target
                lvl_cap = min(_SLICE_MAX, lvl_cap * (F // new_f))
                if per_lvl:
                    per_lvl *= new_f / F
                lvl_cap = _clamp_cap(lvl_cap)
                F = new_f
                dims = SearchDims(**{**dims.__dict__, "frontier": F})
                first = True  # next slice may include a compile
    if status == -1:
        # frontier died out with no goal: invalid if we never overflowed,
        # otherwise unknown.  budget/deadline exceeded: unknown.
        if timed_out or count > 0:
            status = UNKNOWN
        else:
            status = UNKNOWN if ovf else INVALID
    return status, configs, int(carry[4]), dims, used_pallas, acc


def greedy_witness(seq: OpSeq, model: ModelSpec) -> bool:
    """Try ONE deterministic linearization host-side: ok ops in completion
    order, skipping crashed ops entirely.  Ops that returned earlier
    linearized earlier is always real-time consistent, so if every model
    step is legal this is a valid witness and the search is over — the
    O(n) analog of a DFS diving straight to the goal on a well-behaved
    history."""
    rows = sorted(range(len(seq)), key=lambda i: int(seq.ret[i]))
    state = model.init
    for i in rows:
        if not bool(seq.ok[i]):
            continue  # crashed ops may never linearize
        state = model.pystep(state, int(seq.f[i]), int(seq.v1[i]),
                             int(seq.v2[i]))
        if state is None:
            return False
    return True


def greedy_linearization(seq: OpSeq) -> list[int]:
    """The certificate behind a True `greedy_witness`: the ok rows in
    completion order — exactly the sequence the greedy replay already
    model-checked, emitted so the verdict is auditable
    (analyze/audit.py) instead of trust-me."""
    return [i for i in sorted(range(len(seq)),
                              key=lambda i: int(seq.ret[i]))
            if bool(seq.ok[i])]


#: certificate drop reasons for the device engines (the BFS keeps no
#: parent chains in HBM — by design: a frontier of millions of configs
#: times the search depth would not fit, and the user-facing checker
#: reconstructs witnesses host-side instead)
WITNESS_DROPPED_DEVICE = (
    "device-bfs keeps no parent chains; re-check with the host "
    "`linear` engine (witness_cap > 0) for a witness")
FRONTIER_DROPPED_DEVICE = (
    "device-bfs localizes the obstruction by depth/window only; "
    "Linearizable re-verifies invalid device verdicts host-side to "
    "extract the frontier")


#: sentinel distinguishing "prepass not run by the caller" from a
#: caller-supplied result (which may legitimately be None)
_HB_UNSET = object()


def search_opseq(seq: OpSeq, model: ModelSpec, *,
                 budget: int = 20_000_000,
                 dims: SearchDims | None = None,
                 on_slice=None, deadline: float | None = None,
                 stop=None, lint: bool | None = None,
                 audit: bool | None = None,
                 hb: bool | None = None,
                 dpor: bool | None = None,
                 _hbres=_HB_UNSET) -> dict:
    """Check one columnar history on device.  Returns a knossos-style map
    {"valid": True|False|"unknown", "configs": n, "max_depth": d}.

    ``on_slice(carry, dims)`` fires after every bounded device call — the
    checkpoint hook (see ``save_checkpoint``/``resume_opseq``); ``dims``
    reflects any frontier escalation, so checkpoints stay loadable.
    ``deadline`` (perf_counter clock) bounds wall time; an unexhausted
    search past it returns "unknown" with throughput still reported.
    ``stop`` (a ``threading.Event``) aborts between slices — the
    competition hook.  ``lint`` runs the O(n) well-formedness linter
    first (None follows JEPSEN_TPU_LINT; errors raise
    HistoryLintError).  Certificates: greedy/trivial verdicts carry
    their ``linearization``; device verdicts carry explicit
    ``witness_dropped``/``frontier_dropped`` reasons (the BFS keeps no
    parent chains); ``audit`` replays whatever certificate is emitted
    (None follows JEPSEN_TPU_AUDIT).

    ``hb`` (None follows JEPSEN_TPU_HB) runs the unified static
    prepass: decided histories return immediately with an audited
    certificate and zero device configs.  ``dpor`` (None follows
    JEPSEN_TPU_DPOR) threads the prepass's must-order predecessor
    tables into the ENCODING as extra packed planes and turns on the
    kernels' linearized-predecessor lane mask plus the dead-value
    canonical-state rewrite — device lanes masked exactly like the
    host DFS/frame candidate sets, symmetric states collapsed in the
    on-device dedup.  Verdict-identical by construction; off = the
    exact pre-phase-2 kernels."""
    from ..analyze.audit import maybe_audit
    from ..analyze.dpor import _M_MASK, resolve_dpor
    from ..analyze.hb import attach, maybe_hb
    from ..analyze.lint import maybe_lint

    maybe_lint(seq, model, lint)

    # _hbres: search_batch's fallback path hands over the prepass it
    # already ran per key, so the solve (and its metrics) fire once
    hbres = (maybe_hb(seq, model, hb, dpor)
             if _hbres is _HB_UNSET else _hbres)

    def finish(out: dict) -> dict:
        return maybe_audit(seq, model, attach(out, hbres), audit)

    if hbres is not None and hbres.decided is not None:
        # statically decided: no device work, but the telemetry span
        # still records observed=0 vs predicted=0 so traces (and
        # obs_guard's prune-delta check) cover decided tiers too
        return _tele.emit_decided(
            maybe_audit(seq, model, dict(hbres.decided), audit),
            hbres=hbres)

    es = encode_search(seq)
    if es.n_det == 0 and es.n_crash == 0:
        return finish({"valid": True, "configs": 0, "max_depth": 0,
                       "engine": "trivial", "linearization": []})
    if greedy_witness(seq, model):
        return finish({"valid": True, "configs": es.n_det,
                       "max_depth": es.n_det,
                       "engine": "greedy-witness",
                       "linearization": greedy_linearization(seq)})
    if es.window > MAX_WINDOW or es.n_crash > MAX_CRASH:
        # past the device encoding limits: the linear host sweep has no
        # window/crash caps and dominates the WGL DFS on exactly the
        # crash-heavy histories that land here
        from .linear import check_opseq_linear

        out = check_opseq_linear(seq, model, deadline=deadline,
                                 cancel=stop, lint=False, hb=hb,
                                 dpor=dpor)
        out["engine"] = "host-linear(fallback)"
        return finish(out)

    dims = dims or choose_dims(es, model)
    dpor_stats = None
    if resolve_dpor(dpor):
        attach_reductions(es, seq, model,
                          hbres.must_pred if hbres is not None
                          else None, dedup=True)
        _strip_reductions_for_pallas(es, model, dims)
        n_mask_rows = 0
        if es.det_mpred is not None:
            n_mask_rows = int(
                ((es.det_mpred[:, 0] >= 0)
                 | (es.det_cpred != 0)).sum()
                + ((es.crash_mpred[:, 0] >= 0)
                   | (es.crash_cpred != 0)).sum())
        dpor_stats = {"enabled": True, "device_masked": es.masked,
                      "device_mask_rows": n_mask_rows,
                      "dedup": es.dedup}
        if es.masked:
            _M_MASK.inc(dpor_stats["device_mask_rows"],
                        site="device-rows")
    esp = pad_search(es, dims.n_det_pad, dims.n_crash_pad)
    status, configs, max_depth, dims, used_pallas, tele_acc = \
        _run_kernel(esp, es, model, dims, budget, on_slice=on_slice,
                    deadline=deadline, stop=stop)
    out = {"valid": _STATUS[status], "configs": configs,
           "max_depth": max_depth,
           "engine": _engine_label(used_pallas),
           "frontier": dims.frontier,
           "window": es.window, "concurrency": es.concurrency}
    if dpor_stats is not None:
        out["dpor"] = dpor_stats
    if out["valid"] is True:
        out["witness_dropped"] = WITNESS_DROPPED_DEVICE
    elif out["valid"] is False:
        out["frontier_dropped"] = FRONTIER_DROPPED_DEVICE
    _tele.finalize_result(out, tele_acc, hbres=hbres)
    return finish(out)


def check_competition(seq: OpSeq, model: ModelSpec, *,
                      budget: int = 20_000_000,
                      max_configs: int = 50_000_000,
                      lint: bool | None = None,
                      audit: bool | None = None,
                      hb: bool | None = None,
                      dpor: bool | None = None) -> dict:
    """Race the exact host checkers against the device BFS search; the
    first conclusive verdict wins and retires the losers.

    The knossos `competition` analog (jepsen/src/jepsen/checker.clj:122-126
    selects between :linear, :wgl and :competition — the latter races
    algorithms and takes whichever finishes first).  The portfolio here is
    complementary three ways: the WGL host DFS can lucky-dive to a witness
    on well-behaved histories; the `linear` host sweep (checker/linear.py —
    memoized, dominance-pruned) kills invalid histories whose crash-subset
    space strands both DFS and BFS; the device BFS brute-forces wide state
    spaces at device throughput.  Host legs run in daemon threads (they
    release the GIL only at cancellation checks, but the device thread
    spends its time blocked in XLA executions, which do release it).

    The winner's CERTIFICATE propagates with its verdict: host legs
    carry real witnesses/frontiers (the wgl DFS for free, the linear
    sweep under a bounded witness_cap), the device leg explicit drop
    reasons; ``audit`` replays whichever certificate won (None follows
    JEPSEN_TPU_AUDIT).
    """
    import threading

    from . import seq as seqmod
    from .linear import DEFAULT_WITNESS_CAP, check_opseq_linear

    # one lint at the race's boundary; the legs run lint-free (they
    # share the seq, and a loser leg raising HistoryLintError inside a
    # daemon thread would be swallowed as a leg error)
    from ..analyze.audit import maybe_audit
    from ..analyze.lint import maybe_lint

    maybe_lint(seq, model, lint)

    def finish(out: dict) -> dict:
        return maybe_audit(seq, model, out, audit)

    # the host DFS memoizes each config TWICE (visited + parent_of) as a
    # (bigint linearized-set, state tuple) pair: ~n/8 bytes of mask plus
    # a couple hundred bytes of object overhead per copy.  Cap its
    # configs to a ~4 GB footprint so the loser thread cannot eat the
    # machine while the device grinds a long history (the reference
    # answers this with -Xmx32g; we'd rather lose the race than the
    # host).
    per_cfg = 2 * (len(seq) // 8 + 200)
    max_configs = min(max_configs, 4_000_000_000 // per_cfg)

    done = threading.Event()
    lock = threading.Lock()
    result: dict = {}

    def submit(r: dict, engine: str) -> bool:
        """Atomically claim the race for a CONCLUSIVE verdict."""
        if r.get("valid") == "unknown":
            return False
        with lock:
            if result:
                return False
            result.update(r)
            result["engine"] = engine
            done.set()
            return True

    def wgl_leg():
        try:
            r = seqmod.check_opseq(seq, model, max_configs=max_configs,
                                   cancel=done, lint=False, hb=hb,
                                   dpor=dpor)
        except Exception:  # noqa: BLE001 — loser errors must not win
            return
        submit(r, "competition(host-wgl)")

    def linear_leg():
        try:
            # a bounded witness_cap: the leg's verdict stays the same,
            # but a win carries a real certificate instead of a drop
            r = check_opseq_linear(seq, model, max_configs=max_configs,
                                   cancel=done,
                                   witness_cap=DEFAULT_WITNESS_CAP,
                                   lint=False, hb=hb, dpor=dpor)
        except Exception:  # noqa: BLE001
            return
        submit(r, "competition(host-linear)")

    threads = [threading.Thread(target=wgl_leg, daemon=True,
                                name="competition-host-wgl"),
               threading.Thread(target=linear_leg, daemon=True,
                                name="competition-host-linear")]
    for t in threads:
        t.start()

    es = encode_search(seq)
    if es.window > MAX_WINDOW or es.n_crash > MAX_CRASH:
        # the device search would itself fall back to a host DFS; let the
        # two host legs decide it (linear has no encoding limits)
        for t in threads:
            t.join()
        with lock:
            if result:
                out = dict(result)
                out["engine"] += "+device-skipped(encoding limits)"
                return finish(out)
        return {"valid": "unknown", "configs": 0,
                "engine": "competition(exhausted; device encoding limits)"}

    dev = search_opseq(seq, model, budget=budget, stop=done,
                       lint=False, hb=hb, dpor=dpor)
    submit(dev, "competition(device)")
    if not result:
        # device inconclusive: the race is only over when the hosts' own
        # bounded searches finish too (knossos competition waits for a
        # winner, not for the first to give up)
        for t in threads:
            t.join()
    else:
        done.set()  # retire still-running losers
        for t in threads:
            t.join(timeout=5.0)
    with lock:
        if result:
            return finish(dict(result))
    # all inconclusive (budgets exhausted)
    return {**dev, "engine": "competition(exhausted)"}


# ---------------------------------------------------------------------------
# Search checkpointing (SURVEY §5.4 — device-side frontier checkpoint)
# ---------------------------------------------------------------------------


def history_digest(seq: OpSeq, model: ModelSpec) -> str:
    """Identity of (history, model) — resuming against the wrong history
    would silently produce a garbage verdict.  The model's PARAMETERS
    bind too, not just its name: register(0) and register(7) share a
    name but give different verdicts."""
    import hashlib

    h = hashlib.sha256()
    for a in (seq.f, seq.v1, seq.v2, seq.inv, seq.ret, seq.ok):
        h.update(np.ascontiguousarray(np.asarray(a)).tobytes())
    h.update(model.name.encode())
    h.update(repr((model.init, model.state_width)).encode())
    return h.hexdigest()


def _engine_label(used_pallas: bool, resumed: bool = False,
                  base: str = "device-bfs") -> str:
    """One place assembles the engine strings (three emit sites)."""
    tags = [t for t, on in (("pallas", used_pallas),
                            ("resumed", resumed)) if on]
    return base + (f"({','.join(tags)})" if tags else "")


def save_checkpoint(path: str, carry, dims: SearchDims, model: ModelSpec,
                    budget: int, seq: OpSeq | None = None) -> None:
    """Persist a live search carry (as delivered to ``on_slice``).

    The BFS carry is the *entire* search state — frontier configs plus
    progress counters — so a checkpoint is one npz.  The reference's
    knossos search has no analog: a killed -Xmx32g JVM search restarts
    from scratch (jepsen/project.clj:25).  Pass ``seq`` to bind the
    checkpoint to its history so `resume_opseq` can refuse a mismatch.

    The checkpoint also carries ``used_pallas`` — whether any slice of
    the SEARCH SO FAR executed on the pallas engine (the engine label
    of a cross-window accumulated verdict must not forget a window
    that ran on-chip pallas just because a later CPU window saved
    last).  The truth comes from the ACTIVE slice driver via a
    thread-local (`_run_kernel` maintains it, seeded with the resumed
    checkpoint's flag) — never from re-reading the target file, which
    callers like bench.py write through a tmp-path + rename and which
    would therefore never show the prior state."""
    c = [np.asarray(x) for x in carry]
    digest = history_digest(seq, model) if seq is not None else ""
    used_p = getattr(_RUN_PALLAS, "flag", None)
    if used_p is None:
        # called outside a live slice driver (tests, tools): nothing
        # has executed, so nothing ran on pallas — recording mere
        # *eligibility* here would make a verdict resumed from this
        # checkpoint claim pallas execution that never happened
        used_p = False
    np.savez_compressed(
        path, frontier=c[0], count=c[1], status=c[2], configs=c[3],
        max_depth=c[4], ovf=c[5], budget=np.int64(budget),
        model=np.bytes_(model.name.encode()),
        digest=np.bytes_(digest.encode()),
        used_pallas=np.bool_(used_p),
        dims=np.asarray([dims.n_det_pad, dims.n_crash_pad, dims.window,
                         dims.k, dims.state_width, dims.frontier],
                        np.int64))


def load_checkpoint(path: str):
    """Returns (carry, dims, model_name, budget, digest, used_pallas)."""
    z = np.load(path)
    d = z["dims"]
    dims = SearchDims(n_det_pad=int(d[0]), n_crash_pad=int(d[1]),
                      window=int(d[2]), k=int(d[3]), state_width=int(d[4]),
                      frontier=int(d[5]))
    carry = (z["frontier"], z["count"][()], z["status"][()],
             z["configs"][()], z["max_depth"][()], z["ovf"][()])
    digest = bytes(z["digest"][()]).decode() if "digest" in z else ""
    used_p = bool(z["used_pallas"][()]) if "used_pallas" in z else False
    return (carry, dims, bytes(z["model"][()]).decode(), int(z["budget"]),
            digest, used_p)


def resume_opseq(seq: OpSeq, model: ModelSpec, path: str, *,
                 on_slice=None, deadline: float | None = None,
                 stop=None) -> dict:
    """Continue a checkpointed `search_opseq` from `save_checkpoint`.

    ``deadline``/``stop`` bound the continued run exactly as in
    `search_opseq` — a resumed search interrupted AGAIN is still a
    checkpoint."""
    carry, dims, model_name, budget, digest, prior_pallas = \
        load_checkpoint(path)
    if model_name != model.name:
        raise ValueError(
            f"checkpoint is for model {model_name!r}, got {model.name!r}")
    if digest and digest != history_digest(seq, model):
        raise ValueError(
            "checkpoint was taken on a different history (digest mismatch)")
    es = encode_search(seq)
    esp = pad_search(es, dims.n_det_pad, dims.n_crash_pad)
    status, configs, max_depth, dims, used_pallas, tele_acc = \
        _run_kernel(esp, es, model, dims, budget, on_slice=on_slice,
                    resume=carry, deadline=deadline, stop=stop,
                    used_pallas0=prior_pallas)
    out = {"valid": _STATUS[status], "configs": configs,
           "max_depth": max_depth,
           "engine": _engine_label(used_pallas, resumed=True),
           "frontier": dims.frontier,
           "window": es.window, "concurrency": es.concurrency}
    return _tele.finalize_result(out, tele_acc)


# ---------------------------------------------------------------------------
# Checker wrapper (drop-in for checker/linearizable, checker.clj:114-139)
# ---------------------------------------------------------------------------


# ---------------------------------------------------------------------------
# Batched search — vmap over independent keys, sharded over a device mesh
# ---------------------------------------------------------------------------


def batch_dims(ess: list[EncodedSearch], model: ModelSpec, *,
               frontier: int = 32) -> SearchDims:
    """Common static dims covering every history in the batch.  The
    shared frontier starts narrow — every key pays every lane of it
    each level, so the batch is sized for the typical key, not the
    worst: keys that outgrow a rung escalate TOGETHER through 4x-wider
    batch rungs (search_batch's ladder) up to BATCH_FRONTIER_CAP, and
    only past that fall back to solo adaptive-ladder runs."""
    W = _round_up(max(e.window for e in ess), 32)
    ncr = max(e.n_crash for e in ess)
    NC = _round_up(ncr, 32) if ncr else 32
    K = _next_pow2(max(1, min(max(e.concurrency for e in ess),
                              W + ncr)))
    nd = max(64, _next_pow2(max(e.n_det for e in ess)))
    return SearchDims(
        n_det_pad=nd, n_crash_pad=NC, window=W, k=K,
        state_width=model.state_width, frontier=frontier)


def batch_dead_pad(ess: list[EncodedSearch]) -> int:
    """The common dead-table width a batch pads to (stacked shapes
    must agree; keys without a table stack the inert 8-entry one)."""
    w = 8
    for e in ess:
        if e.dead_from is not None:
            w = max(w, _next_pow2(len(e.dead_from)))
    return w


def get_batch_kernel(model: ModelSpec, dims: SearchDims,
                     batch: int = 256, allow_pallas: bool = True,
                     masked: bool = False, masked_crash: bool = False,
                     dedup: bool = False, vt: int = 8,
                     telemetry: bool = False):
    # the batch size reaches the built HLO only through the prune and
    # compaction SELECTIONS — the two dominance sites (closure merge at
    # 2F, det expansion at 4F) and the four matrix-compaction sites
    # (crash/det succ-blocks over F*K lanes; closure-merge and
    # det-expansion compacts) — so key on those booleans, not the raw
    # count: a ladder whose live set shrinks between rungs keeps
    # sharing compiled kernels, while a kernel built under a small
    # batch can never be reused by a larger batch whose one-hot
    # [batch, k_out, n] exceeds the element budget (ADVICE r4: that
    # reuse could OOM the TPU — or pessimize the small batch)
    F, K = dims.frontier, dims.k
    S = 4 * F
    use_p = allow_pallas and _use_pallas(model, dims, masked=masked,
                                         dedup=dedup)
    sel = (_use_allpairs(2 * F, batch),
           _use_allpairs(S, batch),
           _use_matrix_compact(F, F * K, batch),
           _use_matrix_compact(S, F * K, batch),
           _use_matrix_compact(F, 2 * F, batch),
           _use_matrix_compact(F, S, batch))
    key = ("batch", model.name, dims, sel, _dominance_key(),
           masked, masked_crash, dedup, vt, telemetry,
           "pallas" if use_p else "xla")
    fn = _KERNEL_CACHE.get(key)
    _kc_record(fn is not None)
    if fn is None:
        with _tele.compile_span(engine="pallas" if use_p else "xla",
                                batch=batch, frontier=dims.frontier,
                                n_det_pad=dims.n_det_pad,
                                n_crash_pad=dims.n_crash_pad,
                                window=dims.window, k=dims.k,
                                masked=masked,
                                masked_crash=masked_crash,
                                dedup=dedup, vt=vt,
                                model=model.name,
                                model_init=int(model.init[0]),
                                model_width=model.state_width):
            if use_p:
                # vmap of the fused level-loop kernel: the pallas
                # batching rule runs one grid program per key, each a
                # whole level loop with zero per-op overhead (verified
                # row-equal to the vmapped XLA kernel,
                # tests/test_pallas_level.py)
                from . import pallas_level

                backend = _backend()
                base = pallas_level.build_pallas_step_fn(
                    model, dims, interpret=backend != "tpu",
                    masked=masked, telemetry=telemetry)
            else:
                base = build_search_step_fn(model, dims, batch=batch,
                                            masked=masked,
                                            masked_crash=masked_crash,
                                            dedup=dedup,
                                            telemetry=telemetry)
            fn = jax.jit(jax.vmap(
                base,
                in_axes=(0,) * 19 + (None, None, None) + (0,) * 6))
        _KERNEL_CACHE[key] = fn
    return fn


def _shard_map_target(sharding):
    """(mesh, axis) when ``sharding`` is a single-axis NamedSharding a
    batch kernel can be shard_map'd over, else (None, None).

    The bucketed scheduler's per-bucket dispatch wraps the vmapped
    batch kernel in shard_map so each device loops over ONLY its own
    lane block (a vmapped while_loop under plain GSPMD runs until the
    globally slowest lane; under shard_map the cond is local, so a
    shard whose keys resolve early goes quiet instead of spinning
    masked).  Meshes with extra axes (the DCN "keys"x"shard" layout)
    and non-addressable shards keep the device_put/GSPMD path — same
    math, compiler-chosen partitioning."""
    mesh = getattr(sharding, "mesh", None)
    spec = getattr(sharding, "spec", None)
    if mesh is None or spec is None or getattr(mesh, "empty", False):
        return None, None
    if not getattr(sharding, "is_fully_addressable", False):
        return None, None
    names = [n for n in spec if n is not None]
    if len(spec) != 1 or len(names) != 1 \
            or not isinstance(names[0], str):
        return None, None
    axis = names[0]
    try:
        if len(mesh.shape) != 1 or mesh.shape[axis] < 1:
            return None, None
    except (KeyError, TypeError):
        return None, None
    return mesh, axis


def get_sharded_batch_kernel(model: ModelSpec, dims: SearchDims, *,
                             batch: int, mesh, axis: str,
                             masked: bool = False,
                             masked_crash: bool = False,
                             dedup: bool = False, vt: int = 8,
                             telemetry: bool = False):
    """The mesh twin of :func:`get_batch_kernel`: the vmapped XLA batch
    kernel wrapped in ``shard_map`` over the key axis, so every device
    runs ``batch / D`` lanes at the bucket's tight dims and loops only
    until ITS lanes resolve.  ``batch`` must be mesh-divisible (the
    caller pads with inert keys).  Cached under the mesh's device set
    next to the other kernels, so steady-state bucket shapes are dict
    hits and warm-bootable (fleet/warmup.py)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    D = mesh.shape[axis]
    per = batch // D
    F, K = dims.frontier, dims.k
    S = 4 * F
    # the prune/compaction selections see the PER-SHARD lane count —
    # that is the batch the inner kernel is built at
    sel = (_use_allpairs(2 * F, per),
           _use_allpairs(S, per),
           _use_matrix_compact(F, F * K, per),
           _use_matrix_compact(S, F * K, per),
           _use_matrix_compact(F, 2 * F, per),
           _use_matrix_compact(F, S, per))
    key = ("batch-sharded", model.name, dims, sel, _dominance_key(),
           masked, masked_crash, dedup, vt, telemetry, axis, D,
           tuple(d.id for d in mesh.devices.flat))
    fn = _KERNEL_CACHE.get(key)
    _kc_record(fn is not None)
    if fn is None:
        # the span carries the FULL cache-key coordinates (per-shard
        # lanes, shard count, phase-2 flags) so fleet/warmup.py can
        # reconstruct and pre-compile exactly this kernel from a
        # recorded trace
        with _tele.compile_span(engine="xla", sharded=True, shards=D,
                                batch=per, frontier=dims.frontier,
                                n_det_pad=dims.n_det_pad,
                                n_crash_pad=dims.n_crash_pad,
                                window=dims.window, k=dims.k,
                                masked=masked,
                                masked_crash=masked_crash,
                                dedup=dedup, vt=vt,
                                model=model.name,
                                model_init=int(model.init[0]),
                                model_width=model.state_width):
            base = build_search_step_fn(model, dims, batch=per,
                                        masked=masked,
                                        masked_crash=masked_crash,
                                        dedup=dedup,
                                        telemetry=telemetry)
            vm = jax.vmap(base,
                          in_axes=(0,) * 19 + (None, None, None)
                          + (0,) * 6)
            fn = jax.jit(shard_map(
                vm, mesh=mesh,
                in_specs=(P(axis),) * 19 + (P(), P(), P())
                + (P(axis),) * 6,
                out_specs=P(axis), check_vma=False))
        _KERNEL_CACHE[key] = fn
    return fn


#: per-key array attributes, in the exact positional order of
#: build_search_step_fn's signature — the single source of truth for
#: both batch stackers
_BATCH_ARG_ATTRS = ("det_f", "det_v1", "det_v2", "det_inv", "det_ret",
                    "suffix_min_ret", "crash_f", "crash_v1", "crash_v2",
                    "crash_inv", "det_mpred", "det_cpredw",
                    "crash_mpred", "crash_cpredw", "dead_from")


def stack_batch(esps: list[EncodedSearch], *, pad_to: int | None = None):
    """Stack padded EncodedSearches along a leading key axis.  Rows past
    ``len(esps)`` (up to ``pad_to``) replicate row 0's arrays with
    n_det = n_crash = 0 — inert pad keys."""
    b = pad_to or len(esps)
    pad = b - len(esps)
    nbytes = [0]

    def st(attr):
        rows = [getattr(e, attr) for e in esps]
        rows += [rows[0]] * pad
        stacked = np.stack(rows)
        nbytes[0] += stacked.nbytes
        return jnp.asarray(stacked)

    def sc(vals):
        return jnp.asarray(np.array(list(vals) + [0] * pad, np.int32))

    out = tuple(st(a) for a in _BATCH_ARG_ATTRS) + (
        sc(e.n_det for e in esps),
        sc(e.n_crash for e in esps),
        sc(e.dead_lo for e in esps),
        sc(e.dead_tok for e in esps))
    _tele.record_transfer(nbytes[0])
    return out


def _init_batch_carry(n: int, dims: SearchDims, model: ModelSpec):
    """Stacked fresh carries for an n-key batch."""
    one = _init_config(dims, model)
    frontier = np.zeros((n, dims.frontier, dims.words), np.int32)
    frontier[:, 0] = one
    return (frontier, np.ones(n, np.int32),
            np.full(n, -1, np.int32), np.zeros(n, np.int32),
            np.zeros(n, np.int32), np.zeros(n, bool))


# ---------------------------------------------------------------------------
# kernel route registry — the static device contract's enumeration
# ---------------------------------------------------------------------------
#
# Every way a compiled search kernel can be requested is one ROUTE:
# single-device XLA, bucketed batch (vmapped), mesh-sharded batch
# (shard_map of the vmapped kernel), and the pallas fused level loop.
# ``analyze/devlint.py`` abstractly stages each route over
# representative SearchDims and walks the jaxpr for the K-codes; the
# declared fields ARE the contract the lint checks the live code
# against (donation policy, int-only dtypes, compile-span coords).


@dataclass(frozen=True)
class KernelRoute:
    """One kernel dispatch route and its device contract.

    ``build(model, dims)`` returns ``(fn, args)`` — the UNJITTED step
    callable and the exact positional example arguments the driver
    passes, so ``jax.make_jaxpr(fn)(*args)`` stages the route the way
    the driver traces it (weak types and python-scalar leaks included).
    ``request(model, dims)`` goes through the real cached getter
    (``get_kernel`` & co.), so a fresh process emits the route's
    ``device.compile`` span for the K007 coord check.

    ``donate_carry`` is the K004 policy: the slice drivers keep each
    pre-overflow carry (``prev[0]``) and re-feed it widened after a
    frontier escalation, so donating the carry buffers would hand XLA
    a buffer the host still needs — every shipped route declares
    False, and the lint flags a ``donate_argnums`` in the getter's
    ``jax.jit`` call as a contract break (and the reverse: a route
    declaring True whose jit never donates)."""

    name: str
    engine: str        # "xla" | "pallas"
    span_kind: str     # compile-span coord generation (devlint model)
    getter: str        # cache-getter function name (K004 AST anchor)
    module: str        # dotted module defining the getter
    build: object      # (model, dims) -> (fn, args) for staging
    request: object    # (model, dims) -> compiled fn via the cache
    int_only: bool = True
    donate_carry: bool = False
    carry_args: int = 6
    batched: bool = False
    sharded: bool = False


KERNEL_ROUTES: dict[str, KernelRoute] = {}


def register_route(route: KernelRoute) -> KernelRoute:
    KERNEL_ROUTES[route.name] = route
    return route


def route_sample_inputs(model: ModelSpec, dims: SearchDims, *,
                        batch: int = 0):
    """The positional example arguments a route's driver would pass at
    ``dims`` for a minimal one-op history — shared by devlint staging
    and the route builders below.  ``batch > 0`` stacks the batch-route
    form.  Returns the FULL operand tuple
    ``(*tables, budget, lvl_cap, bail, *carry)``."""
    from ..history import encode_ops, invoke_op, ok_op

    fc = model.f_codes
    try:
        names = list(fc)
    except TypeError:  # _AnyFCodes (noop model): accepts anything
        names = ["write"]
    f = next((c for c in ("write", "enqueue", "acquire")
              if c in names), names[0])
    v = 1 if f in ("write", "enqueue") else None
    seq = encode_ops([invoke_op(0, f, v), ok_op(0, f, v)], fc)
    es = encode_search(seq)
    esp = pad_search(es, dims.n_det_pad, dims.n_crash_pad)
    tail = (jnp.int32(64), jnp.int32(4), jnp.bool_(False))
    if batch:
        args = stack_batch([esp] * batch)
        carry = tuple(jnp.asarray(c)
                      for c in _init_batch_carry(batch, dims, model))
        return args + tail + carry
    args = search_args(esp, es)
    carry = tuple(jnp.asarray(c) for c in _init_carry(dims, model))
    return args + tail + carry


def _route_mesh():
    """A minimal single-axis mesh over the local devices (the sharded
    route's staging target; 1 device is a valid mesh)."""
    from jax.sharding import Mesh

    devs = jax.devices()
    return Mesh(np.array(devs[:1]), ("shard",)), "shard", 1


def _build_single(model: ModelSpec, dims: SearchDims):
    fn = build_search_step_fn(model, dims)
    return fn, route_sample_inputs(model, dims)


def _request_single(model: ModelSpec, dims: SearchDims):
    return get_kernel(model, dims)


def _build_pallas(model: ModelSpec, dims: SearchDims):
    from . import pallas_level

    fn = pallas_level.build_pallas_step_fn(
        model, dims, interpret=_backend() != "tpu")
    return fn, route_sample_inputs(model, dims)


def _request_pallas(model: ModelSpec, dims: SearchDims):
    global _ENGINE_MODE
    prev = _ENGINE_MODE
    _ENGINE_MODE = "pallas"
    try:
        return get_kernel(model, dims)
    finally:
        _ENGINE_MODE = prev


_ROUTE_BATCH = 4  # representative lane count for the batch routes


def _build_batch(model: ModelSpec, dims: SearchDims):
    base = build_search_step_fn(model, dims, batch=_ROUTE_BATCH)
    fn = jax.vmap(base, in_axes=(0,) * 19 + (None, None, None)
                  + (0,) * 6)
    return fn, route_sample_inputs(model, dims, batch=_ROUTE_BATCH)


def _request_batch(model: ModelSpec, dims: SearchDims):
    return get_batch_kernel(model, dims, batch=_ROUTE_BATCH,
                            allow_pallas=False)


def _build_sharded(model: ModelSpec, dims: SearchDims):
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    mesh, axis, d = _route_mesh()
    per = _ROUTE_BATCH // d or 1
    base = build_search_step_fn(model, dims, batch=per)
    vm = jax.vmap(base, in_axes=(0,) * 19 + (None, None, None)
                  + (0,) * 6)
    fn = shard_map(vm, mesh=mesh,
                   in_specs=(P(axis),) * 19 + (P(), P(), P())
                   + (P(axis),) * 6,
                   out_specs=P(axis), check_vma=False)
    return fn, route_sample_inputs(model, dims, batch=per * d)


def _request_sharded(model: ModelSpec, dims: SearchDims):
    mesh, axis, d = _route_mesh()
    per = _ROUTE_BATCH // d or 1
    return get_sharded_batch_kernel(model, dims, batch=per * d,
                                    mesh=mesh, axis=axis)


register_route(KernelRoute(
    name="single-xla", engine="xla", span_kind="solo",
    getter="get_kernel", module=__name__,
    build=_build_single, request=_request_single))
register_route(KernelRoute(
    name="pallas-fused", engine="pallas", span_kind="solo",
    getter="get_kernel", module=__name__,
    build=_build_pallas, request=_request_pallas,
    # the fused kernel deliberately lowers the level fold through
    # float32 matmuls (MXU-shaped reductions in pallas_level.py), so
    # its dtype contract is "no 64-bit widening", not "int lanes only"
    int_only=False))
# the two batch routes are dispatched by the bucket scheduler, which
# registers them on import (checker/bucket.py; kernel_routes() below
# forces that import so the enumeration is always complete)


def kernel_routes() -> dict[str, KernelRoute]:
    """All registered routes (importing the bucket scheduler so its
    batch/mesh registrations are in)."""
    from . import bucket  # noqa: F401 — registers its routes on import

    return dict(KERNEL_ROUTES)


def _drive_batch_compacting(fn, esps, model: ModelSpec, dims: SearchDims,
                            budget: int, *, bail: bool = False,
                            tele_acc=None):
    """Slice driver for the vmapped batch kernel with active-key
    compaction.

    A vmapped `while_loop` runs until its SLOWEST lane finishes — already
    -resolved keys keep executing the (masked) body, so a long-tail key
    makes every finished key burn device time with it.  Between slices,
    finished keys are recorded host-side and, once the live set fits
    HALF the current lanes, the stacked args/carry are rebuilt at the
    smaller grid size (pad lanes carry status=VALID, count=0: they mask
    out immediately).  The grid steps in multiples of 32 above 32 lanes
    (pow2 below); the shrink rule (live set fits HALF the lanes on
    hosts, a QUARTER on TPU where each re-stack is a costly fresh
    compile) bounds re-traces to ~log2(n) / ~log4(n) batch sizes per
    drive, all served by the persistent compile cache.

    Returns final (status, count, configs, depth, ovf) arrays over ALL
    keys, in input order.
    """
    n = len(esps)

    fin = {}  # key -> (status, count, configs, depth, ovf)

    def grid(k: int) -> int:
        # pow2 up to 32 lanes, then multiples of 32: a 84-key batch runs
        # at 96 lanes instead of 128 (25% less padded work) while the
        # shape set stays small enough for the persistent compile cache
        if k <= 32:
            return max(4, _next_pow2(k))
        return _round_up(k, 32)

    def stack(keys, carry_rows):
        b = grid(len(keys))
        pad = b - len(keys)
        args = stack_batch([esps[k] for k in keys], pad_to=b)
        cs = []
        for j, proto in enumerate(carry_rows[0]):
            rows = [np.asarray(carry_rows[i][j]) for i in
                    range(len(keys))]
            pad_row = np.zeros_like(rows[0])
            if j == 2:
                pad_row = pad_row + VALID  # pad lanes: masked out
            cs.append(jnp.asarray(np.stack(rows + [pad_row] * pad)))
        return args, tuple(cs)

    # every re-stack is a fresh vmapped-kernel shape; an uncached TPU
    # compile costs far more than the padded lanes it saves, so the
    # accelerator waits for a QUARTER fit (~log4(n) sizes) where hosts
    # re-stack at HALF (~log2(n))
    shrink = 4 if _backend() == "tpu" else 2

    row0 = tuple(np.asarray(c)[0]
                 for c in _init_batch_carry(1, dims, model))
    lanes = list(range(n))  # lane position -> key id (fixed between
    # re-stacks, so carry rows and keys never misalign; retired keys
    # keep their dead lane until the next grid shrink)
    args, carry = stack(lanes, [row0] * n)

    lvl_cap = _SLICE_LEVELS0
    first = True
    while True:
        t0 = time.perf_counter()
        res = fn(*args, jnp.int32(budget), jnp.int32(lvl_cap),
                 jnp.bool_(bail), *carry)
        if tele_acc is not None:
            # per-lane aux blocks [B, R, C]: keys pace differently, so
            # only the lane-sum aggregate is meaningful — totals-only
            carry = res[:6]
            jax.block_until_ready(carry)
            tele_acc.add_totals(np.asarray(res[6]))
        else:
            carry = res
            jax.block_until_ready(carry)
        dt = time.perf_counter() - t0
        _tele.record_device_seconds(dt)
        status = np.asarray(carry[2])
        count = np.asarray(carry[1])
        configs = np.asarray(carry[3])
        depth = np.asarray(carry[4])
        ovf = np.asarray(carry[5])
        live = []  # lane indices still running
        for i, k in enumerate(lanes):
            if k in fin:
                continue
            # with bail, an overflowed lane halts inside the kernel (a
            # wider re-run is coming): it must retire here or the driver
            # would spin on it forever
            if (status[i] != -1 or count[i] <= 0
                    or configs[i] >= budget or (bail and ovf[i])):
                fin[k] = (status[i], count[i], configs[i], depth[i],
                          ovf[i])
            else:
                live.append(i)
        if not live:
            break
        if not first:
            lvl_cap = _adapt_lvl_cap(lvl_cap, dt)
        first = False
        if grid(len(live)) * shrink <= grid(len(lanes)):
            rows = [tuple(np.asarray(c)[i] for c in carry) for i in live]
            lanes = [lanes[i] for i in live]
            args, carry = stack(lanes, rows)
            first = True  # new shape: next slice may include a compile

    out = np.zeros((5, n), np.int64)
    for k, vals in fin.items():
        out[:, k] = [int(v) for v in vals]
    return (out[0].astype(np.int32), out[1].astype(np.int32),
            out[2].astype(np.int32), out[3].astype(np.int32),
            out[4].astype(bool))


def _audit_batch(seqs: list[OpSeq], model: ModelSpec,
                 results: list[dict], audit: bool) -> list[dict]:
    """Per-key certificate audit for the batch routes (one shared exit
    so every return path of `search_batch` applies the same policy;
    `search_batch` resolves the three-state flag to a bool at entry)."""
    if audit:
        from ..analyze.audit import maybe_audit

        for s, r in zip(seqs, results):
            maybe_audit(s, model, r, True)
    return results


def search_batch(seqs: list[OpSeq], model: ModelSpec, *,
                 budget: int = 2_000_000,
                 dims: SearchDims | None = None,
                 sharding=None,
                 decompose: bool = False,
                 decompose_cache=None,
                 bucket: bool | None = None,
                 lint: bool | None = None,
                 audit: bool | None = None,
                 hb: bool | None = None,
                 dpor: bool | None = None,
                 _prepass: list | None = None) -> list[dict]:
    """Check a batch of independent per-key histories in one device call.

    This is the TPU analog of jepsen.independent's bounded-pmap over
    per-key subhistories (independent.clj:247-298): the key axis becomes a
    batch dimension, vmap'd in one compiled search; pass a
    ``jax.sharding.NamedSharding`` (key axis) to spread the batch over a
    mesh — searches are embarrassingly parallel, so XLA partitions them
    with no communication beyond the verdict gather.

    ``decompose=True`` puts the canonical-hash verdict cache
    (jepsen_tpu/decompose/) in front of the batch: keys are
    canonicalized (process renaming, event-rank erasure, value
    renaming) and hashed; cached shapes return instantly, duplicate
    shapes within the batch run once, and only the remaining distinct
    shapes ride to the device.  ``decompose_cache`` is a VerdictCache,
    a jsonl path, or None for an in-memory cache (dedup only).

    ``bucket`` selects the shape-bucketed scheduler (checker/bucket.py):
    keys group by their power-of-two-rounded SearchDims bucket and each
    bucket runs at its own tight dims with pipelined host prep, instead
    of every key padding to the batch-wide max.  With a mesh
    ``sharding`` each bucket covers the mesh via ``shard_map`` at that
    bucket's dims (inert pad keys only up to mesh divisibility within
    the bucket); ``bucket=False`` pins the fused single-shape sharded
    dispatch.  ``None`` follows the JEPSEN_TPU_BATCH_BUCKETS env knob
    (default on); bucketing is verdict-identical either way; an
    explicit ``dims`` pins the fused shape.

    Per-key certificates: greedy-disposed keys carry their
    ``linearization``, host-fallback keys whatever the host engine
    emits, device-ridden keys explicit drop reasons — witnesses
    survive bucket padding/reordering because row indices always index
    the key's OWN OpSeq.  ``audit`` replays every key's certificate
    (None follows JEPSEN_TPU_AUDIT).

    ``hb`` (None follows JEPSEN_TPU_HB, default on) runs the
    happens-before pre-pass (analyze/hb.py) per key: statically decided
    keys are disposed host-side with certificates — right next to the
    greedy-witness disposal, and before any device padding is sized —
    so they never cost a device config at all.

    ``dpor`` (None follows JEPSEN_TPU_DPOR, default on) threads the
    undecided keys' must-order predecessor maps into their encodings
    as device mask planes and enables the dead-value dedup rewrite —
    the same phase-2 reductions `search_opseq` applies, batched.
    ``_prepass`` is internal: per-key must_pred maps a caller already
    computed (the post-disposal recursion), so the pre-pass never runs
    twice per key.
    """
    if not seqs:
        return []
    from ..analyze.dpor import resolve_dpor
    from ..analyze.hb import resolve_hb

    hb = resolve_hb(hb)
    dpor_on = resolve_dpor(dpor)
    if audit is None:
        from ..analyze.audit import audit_enabled

        audit = audit_enabled()
    from ..analyze.lint import (Diagnostic, HistoryLintError,
                                lint_enabled, lint_opseq)

    if lint if lint is not None else lint_enabled():
        # lint every key up front (O(total rows) numpy): errors raise
        # naming the offending key instead of shipping a malformed
        # encoding to the device
        bad: list = []
        for k, s in enumerate(seqs):
            for d in lint_opseq(s, model):
                bad.append(Diagnostic(d.code, d.severity,
                                      f"batch key {k}: {d.message}",
                                      index=d.index, process=d.process,
                                      f=d.f))
        if any(d.severity == "error" for d in bad):
            raise HistoryLintError(bad)
    if decompose:
        return _audit_batch(seqs, model, _search_batch_decomposed(
            seqs, model, budget=budget, dims=dims, sharding=sharding,
            cache=decompose_cache, bucket=bucket, hb=hb, dpor=dpor),
            audit)
    if bucket is None and dims is None and len(seqs) > 1:
        from .bucket import bucketing_enabled

        bucket = bucketing_enabled()
    if bucket and dims is None:
        if sharding is not None:
            # bucket-then-shard: each bucket covers the mesh at its
            # own tight dims (checker/bucket.py), instead of one fused
            # shape over the whole batch
            from .bucket import search_batch_sharded_bucketed

            return _audit_batch(seqs, model,
                                search_batch_sharded_bucketed(
                                    seqs, model, sharding,
                                    budget=budget, hb=hb, dpor=dpor),
                                audit)
        from .bucket import search_batch_bucketed

        return _audit_batch(seqs, model,
                            search_batch_bucketed(seqs, model,
                                                  budget=budget,
                                                  hb=hb, dpor=dpor),
                            audit)
    # greedy completion-order witnesses dispose of well-behaved keys
    # host-side in O(n), and the HB pre-pass disposes statically
    # decided keys next to them; only contentious keys ride the device
    # (undecided keys KEEP their must-order maps — the device mask)
    from ..analyze.hb import maybe_hb

    results_by_idx: dict = {}
    rest = []
    masks: list = []  # must_pred per rest key, aligned with `rest`
    hbs: list = []  # full prepass result per rest key (for the
    #               fallback path; _HB_UNSET when it didn't run here)
    for i, s in enumerate(seqs):
        r = None
        mp = _prepass[i] if _prepass is not None else None
        hbres = _HB_UNSET
        if greedy_witness(s, model):
            r = {"valid": True, "configs": s.n_must,
                 "max_depth": s.n_must,
                 "engine": "greedy-witness",
                 "linearization": greedy_linearization(s)}
        elif hb and _prepass is None:
            hbres = maybe_hb(s, model, True, dpor)
            if hbres is not None and hbres.decided is not None:
                r = dict(hbres.decided)
            elif hbres is not None and hbres.must_pred:
                mp = hbres.must_pred
        if r is not None:
            results_by_idx[i] = r
        else:
            rest.append(i)
            masks.append(mp)
            hbs.append(hbres)
    if not rest:
        return _audit_batch(seqs, model,
                            [results_by_idx[i]
                             for i in range(len(seqs))], audit)
    if results_by_idx:
        sub = search_batch([seqs[i] for i in rest], model, budget=budget,
                           dims=dims, sharding=sharding, bucket=False,
                           lint=False, audit=False, hb=False,
                           dpor=dpor, _prepass=masks)
        for i, r in zip(rest, sub):
            results_by_idx[i] = r
        return _audit_batch(seqs, model,
                            [results_by_idx[i]
                             for i in range(len(seqs))], audit)

    ess = [encode_search(s) for s in seqs]
    if dpor_on:
        for i, (s, e) in enumerate(zip(seqs, ess)):
            attach_reductions(e, s, model, masks[i], dedup=True)
    hard = [i for i, e in enumerate(ess)
            if e.window > MAX_WINDOW or e.n_crash > MAX_CRASH]
    if hard:
        # outliers fall back to individual host checks
        from .linear import check_opseq_linear

        out = []
        for i, s in enumerate(seqs):
            if i in hard:
                r = check_opseq_linear(s, model, lint=False, hb=hb,
                                       dpor=dpor)
                r["engine"] = "host-linear(fallback)"
                out.append(r)
            else:
                out.append(search_opseq(s, model, budget=budget,
                                        lint=False, audit=False,
                                        hb=hb, dpor=dpor,
                                        _hbres=hbs[i]))
        return _audit_batch(seqs, model, out, audit)

    # the sharded path has no escalation ladder (the key axis must keep
    # covering the mesh at a fixed shape), so it starts at the wider
    # frontier; the ladder path starts narrow and escalates in batches
    dims = dims or batch_dims(
        ess, model, frontier=64 if sharding is not None else 32)
    if dpor_on and sharding is None:
        # engine priority: rungs in the pallas regime keep the fused
        # kernel and drop the optional prune (see
        # _strip_reductions_for_pallas)
        for e in ess:
            _strip_reductions_for_pallas(e, model, dims)
    dead_pad = batch_dead_pad(ess)

    if sharding is not None:
        tele_acc = _tele.SearchTelemetry("device-batch-sharded") \
            if _tele.enabled() else None
        out, _info = _search_batch_sharded_fixed(
            seqs, ess, model, dims, sharding, budget,
            tele_acc=tele_acc)
        if tele_acc is not None and out:
            _tele.finalize_result(out[0], tele_acc)
        return _audit_batch(seqs, model, out, audit)
    esps = [pad_search(e, dims.n_det_pad, dims.n_crash_pad,
                       dead_pad=dead_pad) for e in ess]
    return _audit_batch(seqs, model,
                        _search_batch_ladder(seqs, esps, model, dims,
                                             budget), audit)


def _search_batch_sharded_fixed(seqs: list[OpSeq],
                                ess: list, model: ModelSpec,
                                dims: SearchDims, sharding,
                                budget: int, *, tele_acc=None,
                                esps=None, dead_pad=None):
    """One fixed-shape mesh-sharded batch dispatch at ``dims``.

    The shared device stage of BOTH mesh-sharded batch routes: the
    fused path (`search_batch(sharding=...)`, one call over global
    dims) and the bucketed scheduler (`checker/bucket.py`'s
    `search_batch_sharded_bucketed`, one call per bucket at that
    bucket's tight dims).  Mesh-sharded batches stay on the XLA
    kernel: partitioning a pallas_call's vmapped grid axis over a mesh
    is not a path the batching rule guarantees.

    The key axis must stay divisible by the mesh: disposal (greedy/hb)
    or a small bucket can shrink a batch below it, so the batch pads
    with inert keys (n_det = n_crash = 0, status pre-resolved VALID so
    the liveness reduction ignores them and no lane spins forever).
    Pad lanes are an artifact of mesh divisibility, NOT state-space
    work: they are stripped from the aux telemetry block BEFORE the
    lane-sum (no pad occupancy in ``search_telemetry``) and never read
    back into per-key ``configs``.

    On a single-axis, fully-addressable mesh the kernel is shard_map'd
    (`get_sharded_batch_kernel`) so each device loops only until its
    own lane block resolves; other layouts (the DCN "keys"x"shard"
    mesh, multi-process shards) take device_put + GSPMD — in a
    MULTI-PROCESS job each process owns only its addressable shards,
    and device_put from replicated host data is the supported
    construction path.

    Returns ``(results, info)``: per-key result dicts aligned with
    ``seqs`` and the dispatch info (shards, pad lanes, overflow
    redos) the bucketed scheduler folds into its stats.
    """
    tele_on = tele_acc is not None
    if dead_pad is None:
        dead_pad = batch_dead_pad(ess)
    n_dev = getattr(sharding, "num_devices", 1) or 1
    b = _round_up(len(seqs), n_dev)
    mesh, axis = _shard_map_target(sharding)
    n_shards = n_dev
    if mesh is not None and b % mesh.shape[axis] == 0:
        n_shards = mesh.shape[axis]
        fn = get_sharded_batch_kernel(
            model, dims, batch=b, mesh=mesh, axis=axis,
            masked=any(e.masked for e in ess),
            masked_crash=any(e.mask_has_crash for e in ess),
            dedup=any(e.dedup for e in ess),
            vt=dead_pad, telemetry=tele_on)
        used_shard_map = True
    else:
        fn = get_batch_kernel(model, dims, batch=len(seqs),
                              allow_pallas=False,
                              masked=any(e.masked for e in ess),
                              masked_crash=any(e.mask_has_crash
                                               for e in ess),
                              dedup=any(e.dedup for e in ess),
                              vt=dead_pad, telemetry=tele_on)
        used_shard_map = False
    if esps is None:
        # the bucketed scheduler pre-pads on its prep thread and hands
        # esps in; the fused route pads here
        esps = [pad_search(e, dims.n_det_pad, dims.n_crash_pad,
                           dead_pad=dead_pad) for e in ess]
    args = stack_batch(esps, pad_to=b)
    args = tuple(jax.device_put(np.asarray(a), sharding)
                 for a in args)
    carry0 = [np.asarray(c)
              for c in _init_batch_carry(b, dims, model)]
    carry0[1][len(seqs):] = 0
    carry0[2][len(seqs):] = VALID
    carry = tuple(jax.device_put(c, sharding) for c in carry0)

    def call(c, lvl_cap):
        t0 = time.perf_counter()
        res = fn(*args, jnp.int32(budget), jnp.int32(lvl_cap),
                 jnp.bool_(False), *c)
        if tele_acc is not None:
            jax.block_until_ready(res[:6])
            t1 = time.perf_counter()
            try:
                blk = np.asarray(res[6])
            except Exception:  # noqa: BLE001 — non-addressable
                pass           # multi-process shards: skip
            else:
                # inert mesh-divisibility pad lanes excluded BEFORE
                # the lane-sum: their rows must not bill occupancy
                tele_acc.add_totals(blk[:len(seqs)])
                _tele.emit_shard_levels(blk, len(seqs), n_shards,
                                        t0, t1)
            res = res[:6]
        return res

    # the liveness reduction runs jitted: its output is replicated,
    # so it stays readable when the carry itself is sharded over
    # processes (np.asarray on a non-fully-addressable array throws)
    active_fn = jax.jit(
        lambda s, c, g: jnp.any((s == -1) & (c > 0) & (g < budget)))

    def is_active(c):
        return bool(active_fn(c[2], c[1], c[3]))

    def gather(x):
        if getattr(x, "is_fully_addressable", True):
            return np.asarray(x)
        from jax.experimental import multihost_utils

        return np.asarray(
            multihost_utils.process_allgather(x, tiled=True))

    carry = _drive_slices(call, carry, is_active)
    status = gather(carry[2])
    count = gather(carry[1])
    configs = gather(carry[3])
    depth = gather(carry[4])
    ovf = gather(carry[5])
    status = _finalize_batch_status(status, count, ovf)
    out = []
    redo = 0
    for i in range(len(seqs)):
        if int(status[i]) == UNKNOWN and bool(ovf[i]):
            # overflowed the fixed mesh shape: redo solo with the
            # adaptive ladder
            redo += 1
            out.append(search_opseq(seqs[i], model,
                                    budget=budget, lint=False,
                                    audit=False))
        else:
            r = {"valid": _STATUS[int(status[i])],
                 "configs": int(configs[i]),
                 "max_depth": int(depth[i]),
                 "engine": "device-batch"}
            _device_batch_certificate(r)
            out.append(r)
    # which device each real key's lane was placed on, read off the
    # placed array itself (the evidence that a mesh run spread its
    # keys, rather than a count derived from the mesh's shape)
    device_keys: dict = {}
    for sh in getattr(args[0], "addressable_shards", ()):
        lo = sh.index[0].start or 0
        hi = b if sh.index[0].stop is None else sh.index[0].stop
        key = str(sh.device.id)
        device_keys[key] = device_keys.get(key, 0) + max(
            0, min(hi, len(seqs)) - lo)
    info = {"n_shards": int(n_shards), "batch_lanes": int(b),
            "pad_lanes": int(b - len(seqs)),
            "shard_map": used_shard_map, "overflow_redo": redo,
            "device_keys": device_keys}
    return out, info


def _finalize_batch_status(status, count, ovf):
    """Host-side finalization of still -1 statuses (dead frontier or
    exhausted budget), mirroring _run_kernel — the ONE rule both the
    sharded and ladder batch paths apply."""
    return np.where(
        status == -1,
        np.where(count <= 0, np.where(ovf, UNKNOWN, INVALID), UNKNOWN),
        status)


def _device_batch_certificate(r: dict) -> dict:
    """Attach the device batch engines' explicit certificate-drop
    reasons — the ONE place the batch paths state why a device verdict
    ships without a witness/frontier."""
    if r.get("valid") is True:
        r.setdefault("witness_dropped", WITNESS_DROPPED_DEVICE)
    elif r.get("valid") is False:
        r.setdefault("frontier_dropped", FRONTIER_DROPPED_DEVICE)
    return r


def _search_batch_ladder(seqs: list[OpSeq], esps: list[EncodedSearch],
                         model: ModelSpec, dims: SearchDims,
                         budget: int) -> list[dict]:
    """The batched escalation ladder — `search_batch`'s device path for
    un-meshed batches, taking PRE-PADDED EncodedSearches at ``dims``.

    This is also the entry point the bucketed scheduler
    (checker/bucket.py) feeds directly: per-bucket host prep (greedy
    witnesses, encoding, padding) happens in its pipeline thread, and
    this function only pays the device work.

    Every pending key runs at the current frontier rung; keys that
    overflow it re-run TOGETHER at 4x width (one kernel call per rung,
    not one solo search per overflowing key — solo re-runs each pay
    dispatch/compile, which is exactly what hurts on a real
    accelerator).  Keys still overflowing past the rung cap fall back
    to the solo adaptive ladder.
    """
    n = len(seqs)
    status = np.full(n, UNKNOWN, np.int32)
    count = np.zeros(n, np.int32)
    configs = np.zeros(n, np.int64)
    depth = np.zeros(n, np.int32)
    ovf = np.zeros(n, bool)
    pending = list(range(n))
    spent = np.zeros(n, np.int64)  # configs across ALL rungs
    rung = dims.frontier
    # phase-2 flags, derived from the pre-padded encodings (uniform
    # across the batch by construction: pad_search always materializes
    # the planes, and the kernel emits the checks when ANY key needs
    # them — inert tables no-op for the rest)
    b_masked = any(e.masked for e in esps)
    b_mcrash = any(e.mask_has_crash for e in esps)
    b_dedup = any(e.dedup for e in esps)
    b_vt = len(esps[0].dead_from) if esps else 8
    used_pallas = False  # any rung executed on the pallas engine
    tele_on = _tele.enabled()
    acc = _tele.SearchTelemetry("device-batch") if tele_on else None
    while pending:
        d = _dc_replace(dims, frontier=rung)
        want_pallas = _use_pallas(model, d, masked=b_masked,
                                  dedup=b_dedup)
        fnr = get_batch_kernel(model, d, batch=len(pending),
                               masked=b_masked,
                               masked_crash=b_mcrash, dedup=b_dedup,
                               vt=b_vt, telemetry=tele_on)
        st, ct, cf, dp, ov = _drive_batch_compacting(
            fnr, [esps[i] for i in pending], model, d, budget,
            bail=True, tele_acc=acc)
        used_pallas = used_pallas or want_pallas
        nxt = []
        for j, i in enumerate(pending):
            spent[i] += int(cf[j])
            if st[j] == -1 and bool(ov[j]) and spent[i] < budget:
                nxt.append(i)  # overflowed this rung: escalate
            else:
                # configs reports cumulative exploration across
                # rungs, and the per-key budget bounds the total —
                # a key never escalates once its cumulative spend
                # crosses it (worst case: budget + one rung)
                status[i], count[i] = st[j], ct[j]
                configs[i] = spent[i]
                depth[i], ovf[i] = dp[j], ov[j]
        pending = nxt
        if pending and rung >= BATCH_FRONTIER_CAP:
            break  # stragglers go solo below
        rung = min(rung * 4, BATCH_FRONTIER_CAP)
    status = _finalize_batch_status(status, count, ovf)
    out = []
    batch_engine = _engine_label(used_pallas, base="device-batch")
    solo = set(pending)
    for i in range(n):
        needs_solo = i in solo or (int(status[i]) == UNKNOWN
                                   and bool(ovf[i]))
        if needs_solo and spent[i] >= budget:
            # cumulative ladder spend already exhausted this key's
            # budget: a solo re-run would amplify work past the cap.
            # UNKNOWN stands, with the true cumulative count.
            out.append({"valid": "unknown", "configs": int(spent[i]),
                        "max_depth": int(depth[i]),
                        "engine": batch_engine})
        elif needs_solo:
            # overflowed every shared rung: redo solo with the adaptive
            # ladder, on the REMAINING budget, reporting cumulative
            # configs (ladder spend + solo spend)
            rem = budget - int(spent[i])
            r = search_opseq(seqs[i], model, budget=max(1000, rem),
                             lint=False, audit=False)
            r["configs"] = int(r.get("configs", 0)) + int(spent[i])
            out.append(r)
        else:
            out.append(_device_batch_certificate(
                {"valid": _STATUS[int(status[i])],
                 "configs": int(configs[i]),
                 "max_depth": int(depth[i]),
                 "engine": batch_engine}))
    if acc is not None and out:
        # batch-aggregate telemetry rides the FIRST result only (the
        # bucket_batch / decompose_batch convention: one shared stats
        # dict, not N serialized copies)
        _tele.finalize_result(out[0], acc)
    return out


def _search_batch_decomposed(seqs: list[OpSeq], model: ModelSpec, *,
                             budget: int, dims, sharding,
                             cache, bucket=None,
                             hb: bool | None = None,
                             dpor: bool | None = None) -> list[dict]:
    """Cache + dedup front-end for `search_batch` (decompose=True).

    Exact by construction: a canonical-hash collision means the two
    histories are the *same search problem* (same rows, same precedence
    ranks, value-bijective), so one verdict serves both.  Undecided
    results are never cached and never deduplicated onto other keys."""
    from ..decompose.cache import VerdictCache
    from ..decompose.canonical import canonical_key

    if isinstance(cache, str):
        cache = VerdictCache(cache)
    elif cache is None:
        cache = VerdictCache()  # in-memory: within-batch dedup only
    cache.reset_stats()
    keys = [canonical_key(s, model) for s in seqs]
    results: dict[int, dict] = {}
    rep: dict[str, int] = {}  # canonical key -> representative index
    todo: list[int] = []
    drop = "canonical verdict-cache hit (the cache stores verdicts, " \
           "not witnesses)"
    for i, k in enumerate(keys):
        e = cache.get(k)
        if e is not None and "v" in e:
            results[i] = {"valid": e["v"], "configs": 0,
                          "engine": "decompose-cache"}
            results[i]["witness_dropped" if e["v"] is True
                       else "frontier_dropped"] = drop
        elif k in rep:
            pass  # filled from the representative's verdict below
        else:
            rep[k] = i
            todo.append(i)
    if todo:
        sub = search_batch([seqs[i] for i in todo], model, budget=budget,
                           dims=dims, sharding=sharding, bucket=bucket,
                           lint=False, hb=hb, dpor=dpor)
        for i, r in zip(todo, sub):
            results[i] = r
            if r.get("valid") in (True, False):
                cache.put_verdict(keys[i], r["valid"])
    def _copy_cert(dst: dict, src: dict) -> dict:
        """Certificates transfer between canonically-equal keys: the
        histories are row-aligned and value-bijective (canonical.py),
        so one's witness row order / frontier rows are the other's.
        The audit pass replays the copy against ITS history, keeping
        this transfer falsifiable."""
        for field in ("linearization", "final_ops", "witness_dropped",
                      "frontier_dropped", "hb_cycle"):
            if field in src:
                v = src[field]
                dst[field] = list(v) if isinstance(v, list) else v
        return dst

    n_dup = 0
    solo: dict[str, dict] = {}
    for i, k in enumerate(keys):
        if i in results:
            continue
        r = results[rep[k]]
        if r.get("valid") in (True, False):
            n_dup += 1
            results[i] = _copy_cert({"valid": r["valid"], "configs": 0,
                                     "engine": "decompose-dedup"}, r)
            continue
        # the representative was undecided in the batch: retry solo —
        # ONCE per canonical shape (copies are isomorphic problems, so
        # a decided retry serves all of them, and sharing an undecided
        # one asserts nothing)
        r2 = solo.get(k)
        if r2 is None:
            r2 = solo[k] = search_opseq(seqs[i], model, budget=budget,
                                        lint=False)
            if r2.get("valid") in (True, False):
                cache.put_verdict(k, r2["valid"])
                # the decided retry serves the representative too: one
                # canonical shape must not report two verdicts in one
                # result list (its batch-spent configs stay billed)
                ri = results[rep[k]]
                ri["valid"] = r2["valid"]
                ri["engine"] = (ri.get("engine") or
                                "device-batch") + "+decompose-retry"
                _copy_cert(ri, r2)
            results[i] = r2
        else:
            n_dup += 1
            results[i] = _copy_cert(
                {"valid": r2.get("valid"), "configs": 0,
                 "engine": "decompose-dedup"}, r2)
    out = [results[i] for i in range(len(seqs))]
    stats = {"n_keys": len(seqs), "cache_hits": cache.hits,
             "cache_misses": cache.misses, "deduped": n_dup,
             "searched": len(todo),
             "hit_rate": round(cache.hits / max(1, len(seqs)), 4)}
    # first result only — attaching one shared mutable dict to every
    # key invites spooky cross-key mutation and serializes the stats
    # N times through per-key stores (same convention as bucket_batch)
    if out:
        out[0].setdefault("decompose_batch", stats)
    return out


def truncate_to_failure(seq: OpSeq, depth: int, window: int
                        ) -> OpSeq | None:
    """Cut the history just past the failure region, at a point where
    every kept determinate op returned before any removed op invoked.

    The device search localizes an invalid history's obstruction near
    determinate position `depth` (+ window).  The cut must be *closed*:
    if no removed op can linearize among the kept ones (kept det rets all
    precede removed invs; crashed rows before the cut are kept), then any
    valid linearization of the full history restricts to one of the
    prefix — so prefix-invalid ⟹ full-invalid, and the host oracle can
    confirm + extract a witness on the (much shorter) prefix
    (SURVEY.md §7 "witness reconstruction").

    Returns None when no quiescent cut exists before the end.
    """
    ok = np.asarray(seq.ok, dtype=bool)
    det_rows = np.nonzero(ok)[0]
    n_det = len(det_rows)
    want = min(depth + window + 1, n_det)
    if want >= n_det:
        return None
    det_inv = np.asarray(seq.inv)[det_rows]
    det_ret = np.asarray(seq.ret)[det_rows]
    run_max = np.maximum.accumulate(det_ret)
    # boundary after det i iff max ret of dets 0..i < inv of det i+1
    cut = None
    for i in range(want, n_det - 1):
        if run_max[i] < det_inv[i + 1]:
            cut = i
            break
    if cut is None:
        return None
    t = det_inv[cut + 1]  # first removed det's invocation rank
    keep = np.asarray(seq.inv) < t
    idx = np.nonzero(keep)[0]
    if len(idx) >= len(seq):
        return None
    return OpSeq(
        process=seq.process[idx], f=seq.f[idx], v1=seq.v1[idx],
        v2=seq.v2[idx], inv=seq.inv[idx], ret=seq.ret[idx],
        ok=seq.ok[idx], ops=[seq.ops[i] for i in idx],
        encoder=seq.encoder)


class Linearizable:
    """Linearizability checker backed by the device engine.

    The reference's `linearizable` checker hands the model + indexed
    history to knossos and truncates the failure analysis for reporting
    (checker.clj:114-139).  Here:

      * histories below `host_threshold` logical ops run on the exact host
        oracle (device dispatch has fixed overhead);
      * larger histories run the device search;
      * an invalid device verdict is re-verified (and a witness frontier
        extracted) by the host oracle when the history is small enough to
        afford it, closing the fingerprint-collision soundness hole.

    ``model`` may be given at construction or ride in test["model"].
    """

    name = "linearizable"

    #: algorithm aliases, mirroring checker.clj:122-126's
    #: :linear / :wgl / :competition selector.  `linear` is the memoized
    #: dominance-pruned host sweep (checker/linear.py), `wgl`/`host` the
    #: plain DFS oracle (checker/seq.py), `device`/`tpu` the device BFS,
    #: `competition` races all three.
    ALGORITHMS = {"auto": "auto", "device": "device", "tpu": "device",
                  "linear": "linear", "host": "host", "wgl": "host",
                  "competition": "competition"}

    def __init__(self, model: ModelSpec | None = None, *,
                 budget: int = 20_000_000,
                 host_threshold: int = 48,
                 witness_threshold: int = 3000,
                 algorithm: str = "auto",
                 decompose: bool = False,
                 verdict_cache=None,
                 lint: bool | None = None,
                 explain: bool | None = None,
                 audit: bool | None = None,
                 shrink: bool | None = None,
                 hb: bool | None = None,
                 dpor: bool | None = None):
        self.model = model
        # ``hb`` runs the happens-before pre-pass (analyze/hb.py) in
        # front of every host route: statically decided histories skip
        # the search entirely, undecided ones search under the
        # must-order mask.  None follows JEPSEN_TPU_HB (default on;
        # the CLI's --no-hb sets it to 0).  ``dpor`` enables the
        # dynamic layer (analyze/dpor.py: duplicate-op edges, sleep
        # sets, dead-value dedup, device mask planes).  None follows
        # JEPSEN_TPU_DPOR (default on; the CLI's --no-dpor sets it
        # to 0).
        self.hb = hb
        self.dpor = dpor
        self.budget = budget
        self.host_threshold = host_threshold
        self.witness_threshold = witness_threshold
        # ``audit`` replays every verdict's certificate through the
        # independent audit pass (analyze/audit.py; None follows
        # JEPSEN_TPU_AUDIT, set by the CLI's --audit).  ``shrink``
        # delta-debugs invalid verdicts into a minimal failing
        # subhistory for the report (analyze/shrink.py; None follows
        # JEPSEN_TPU_SHRINK, default on — reporting only, never
        # verdicts).
        self.audit = audit
        self.shrink = shrink
        # ``lint`` runs the well-formedness linter (analyze/lint.py)
        # over the history before any search: errors are fatal
        # (HistoryLintError), warnings ride the result dict as
        # ``lint_warnings``.  None follows the JEPSEN_TPU_LINT knob
        # (default on).  ``explain`` (or JEPSEN_TPU_EXPLAIN, set by the
        # CLI's --explain) reports the static search PLAN
        # (analyze/plan.py) without running any search.
        self.lint = lint
        if explain is None:
            explain = os.environ.get(
                "JEPSEN_TPU_EXPLAIN", "").lower() in ("1", "true", "on",
                                                      "yes")
        self.explain = explain
        # ``decompose=True`` runs the P-compositional decomposition
        # layer (jepsen_tpu/decompose/) in front of whichever engine
        # ``algorithm`` selects; verdict-identical, default off.
        # ``verdict_cache``: a decompose.VerdictCache, a jsonl path, or
        # True for the store-persisted default location.  The env knob
        # (set by the CLI's --lin-decompose) reaches suite-constructed
        # checkers the same way JEPSEN_TPU_LIN_ALGORITHM does.
        if not decompose:
            decompose = os.environ.get(
                "JEPSEN_TPU_LIN_DECOMPOSE", "").lower() in ("1", "true",
                                                            "on", "yes")
        self.decompose = decompose
        self.verdict_cache = verdict_cache
        src = "algorithm"
        if algorithm == "auto":
            # fleet-wide experiment knob: suites construct their own
            # checkers, so a per-suite flag can't reach them all
            env = os.environ.get("JEPSEN_TPU_LIN_ALGORITHM")
            if env:
                algorithm, src = env, "JEPSEN_TPU_LIN_ALGORITHM"
        try:
            self.algorithm = self.ALGORITHMS[algorithm]
        except KeyError:
            raise ValueError(
                f"unknown algorithm {algorithm!r} (from {src}); one of "
                f"{sorted(self.ALGORITHMS)}") from None

    def check(self, test, history, opts=None):
        model = self.model or test.get("model")
        if model is None:
            raise ValueError("linearizable checker needs a model")
        from ..analyze.lint import (check_history, check_opseq_lint,
                                    lint_enabled)

        lint_warnings: list = []
        do_lint = self.lint if self.lint is not None else lint_enabled()
        if do_lint:
            # event-level lint sees defects encoding erases (double
            # invokes, orphan completions, type drift); an OpSeq input
            # gets the columnar checks.  Errors raise HERE — before
            # encode_ops can silently mis-pair the malformed events —
            # and check_safe turns that into an "unknown" verdict
            # carrying the diagnostic, never a wrong True/False.
            if isinstance(history, OpSeq):
                lint_warnings = check_opseq_lint(history, model)
            else:
                lint_warnings = check_history(history, model)
        seq = history if isinstance(history, OpSeq) else \
            encode_ops(history, model.f_codes)
        if self.explain:
            # plan-only mode (--explain): report what the search WOULD
            # do — dims, bucket, route, decompositions — and stop
            from ..analyze.plan import explain as explain_plan
            from ..analyze.plan import render_plan

            plan = explain_plan(seq, model,
                                host_threshold=self.host_threshold)
            print(render_plan(plan))
            out = {"valid": "unknown", "engine": "explain(plan-only)",
                   "explain": plan, "configs": 0}
            if lint_warnings:
                out["lint_warnings"] = [d.to_dict()
                                        for d in lint_warnings]
            return out
        out = self._checked(test, seq, model, opts)
        if lint_warnings and isinstance(out, dict):
            out.setdefault("lint_warnings",
                           [d.to_dict() for d in lint_warnings])
        if isinstance(out, dict):
            from ..analyze.audit import maybe_audit

            maybe_audit(seq, model, out, self.audit)
        return out

    def _checked(self, test, seq, model, opts):
        if self.decompose:
            from ..decompose.cache import VerdictCache, default_cache_path
            from ..decompose.engine import check_opseq_decomposed

            cache = self.verdict_cache
            if cache is True:
                cache = default_cache_path()
            if isinstance(cache, str):
                # construct the cache ONCE per checker, not per check():
                # each construction re-parses the whole append-only
                # jsonl, which grows with every decided verdict
                if getattr(self, "_cache_obj", None) is None or \
                        self._cache_obj.path != cache:
                    self._cache_obj = VerdictCache(cache)
                cache = self._cache_obj
            sub_check = None
            if self.algorithm == "host":
                # honor the selected host engine for sub-searches too;
                # the other selections (device/competition/linear/auto)
                # keep the default host `linear` sub-engine — cells and
                # segments are small, where device dispatch only loses
                from . import seq as seqmod

                def sub_check(s, m, *, max_configs, deadline):
                    return seqmod.check_opseq(s, m,
                                              max_configs=max_configs,
                                              deadline=deadline,
                                              lint=False, hb=self.hb,
                                              dpor=self.dpor)
            # lint=False: this checker already linted (or deliberately
            # skipped) at its own boundary in check()
            out = check_opseq_decomposed(
                seq, model, cache=cache,
                sub_max_configs=self.budget,  # the user's sizing knob
                sub_check=sub_check, lint=False, witness=True,
                hb=self.hb, dpor=self.dpor,
                direct=lambda s: self._check_direct(test, s, model, opts))
            if out["valid"] is False and "report_file" not in out:
                # the direct fallback renders its own report; a verdict
                # decided by decomposition alone still gets one
                self._render_failure(test, seq, out, opts, model)
            return out
        return self._check_direct(test, seq, model, opts)

    def _check_direct(self, test, seq, model, opts):
        from . import seq as seqmod

        if (self.algorithm == "host"
                or (self.algorithm == "auto"
                    and len(seq) <= self.host_threshold)):
            # lint=False throughout _check_direct: check() linted (or
            # deliberately skipped) at the checker boundary already
            out = seqmod.check_opseq(seq, model, lint=False,
                                     hb=self.hb, dpor=self.dpor)
            out["engine"] = "host-oracle"
            if out["valid"] is False:
                self._render_failure(test, seq, out, opts, model)
            return out

        if self.algorithm == "linear":
            from .linear import DEFAULT_WITNESS_CAP, check_opseq_linear

            # user-facing path: track the valid-verdict witness (the
            # verdict-only callers — competition legs, portfolio,
            # fuzzers — leave it off and keep level-local memory)
            out = check_opseq_linear(seq, model,
                                     witness_cap=DEFAULT_WITNESS_CAP,
                                     lint=False, hb=self.hb,
                                     dpor=self.dpor)
            out["engine"] = "host-linear"
            if out["valid"] is False:
                self._render_failure(test, seq, out, opts, model)
            return out

        if self.algorithm in ("auto", "competition"):
            # the reference's default is :competition
            # (checker.clj:122-126): race the exact host DFS against the
            # device search; whichever concludes first wins.  The host
            # thread costs one core and wins exactly the histories a DFS
            # lucky-dives (deep valid ones); the device wins sweeps.
            out = check_competition(seq, model, budget=self.budget,
                                    lint=False, hb=self.hb,
                                    dpor=self.dpor)
        else:
            out = search_opseq(seq, model, budget=self.budget,
                               lint=False, hb=self.hb,
                               dpor=self.dpor)
        if out["valid"] is False:
            eng = out.get("engine", "")
            if "host-oracle" in eng or "host-linear" in eng:
                # an exact host engine already produced this verdict
                # (and its final_ops/final_paths report data);
                # re-confirming would repeat the same search
                self._render_failure(test, seq, out, opts, model)
                return out
            # exact confirmation + witness for the report, on the
            # shortest sound prefix covering the failure region
            target = seq
            trunc = truncate_to_failure(seq, out.get("max_depth", 0),
                                        out.get("window", 1))
            if trunc is not None:
                target = trunc
            if len(target) <= self.witness_threshold:
                confirm = seqmod.check_opseq(target, model, lint=False)
                if confirm["valid"] is False:
                    confirm["engine"] = out["engine"] + "+host-witness"
                    confirm["device_configs"] = out["configs"]
                    confirm["witness_prefix_ops"] = len(target)
                    self._render_failure(test, target, confirm, opts,
                                         model)
                    return confirm
                # prefix came back valid: fall through to the full
                # device verdict (obstruction lies past the cut)
        return out

    #: don't delta-debug failure reports past this many rows — each
    #: shrink probe is a bounded re-search, and a huge history's report
    #: should not cost more than its verdict did
    SHRINK_MAX_OPS = 400

    def _render_failure(self, test, seq, result, opts, model):
        """linear.html — the knossos linear.svg analog
        (checker.clj:128-135); reporting never affects the verdict.
        Invalid verdicts are first delta-debugged into a minimal
        failing subhistory (analyze/shrink.py) so the report tells a
        6-op story instead of dumping the whole history."""
        from . import linear_report

        if result.get("shrink") is None and len(seq) > 0 \
                and len(seq) <= self.SHRINK_MAX_OPS:
            from ..analyze.shrink import (shrink_enabled, shrink_invalid,
                                          shrink_summary)

            if self.shrink if self.shrink is not None \
                    else shrink_enabled():
                try:
                    s = shrink_invalid(seq, model)
                    result["shrink"] = shrink_summary(seq, s)
                except Exception:  # noqa: BLE001 — reporting only
                    pass
        path = linear_report.write_linear_html(test or {}, seq, result,
                                               opts)
        if path is not None:
            result["report_file"] = path

    def __call__(self, test, history, opts=None):
        return self.check(test, history, opts)


def linearizable(model: ModelSpec | None = None, **kw) -> Linearizable:
    return Linearizable(model, **kw)
