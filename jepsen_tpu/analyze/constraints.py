"""Model-generic constraint compiler — static order-solving beyond
registers.

PR 12's happens-before order-solver (analyze/hb.py) is register-only:
its read-from / value-block algebra needs a unique writer per value.
This module generalizes the same static-constraint idea to the OTHER
model families the engines search — the P-compositional per-key
decomposition (arXiv:1504.00204) and the static half of partial-order
reduction (arXiv:2405.11128) apply to enqueue/dequeue read-from edges
exactly as they did to register read-from edges:

  * **queue** (``unordered-queue-N`` / ``fifo-queue-N``) — an :ok
    dequeue of v *reads from* the (unique-payload) enqueue of v, so
    enqueue->dequeue is a forced edge; under FIFO, real time between
    two enqueues forces the same order on their dequeues (same-node
    enqueue pairs are real-time chains for free).  Decide-fast rules:
    dequeue-of-never-enqueued, duplicate delivery (more :ok dequeues
    than enqueue rows of a value), read-from cycles (a dequeue wholly
    before its only enqueue), FIFO inversion — each with a certificate
    the independent audit (analyze/audit.py, W007/W008) re-justifies
    without re-running this compiler.  All-:ok unique-payload
    unordered-queue histories decide *valid* constructively: a
    completion-order schedule with each enqueue pulled in front of its
    dequeue is real-time consistent whenever no read-from cycle
    exists, and the constructed witness is model-replayed before it is
    ever emitted (decide-valid is self-verified, exactly as hb.py's
    GK witness is).
  * **lock** (``mutex``) — acquire/release alternation is a counting
    invariant over forced linearization points: at any rank t, the
    acquires forced linearized (:ok, returned by t) minus the releases
    that could possibly have linearized (invoked before t) bound the
    held count from below; >= 2 is a forced double-hold, and the dual
    sweep catches a release forced with no possible acquire.  Both are
    O(n log n) and crash-sound (crashed rows count as *possible*,
    never *forced*).
  * **set** — event-level only (sets have no searchable ModelSpec):
    add->member-read edges and the SetChecker verdicts (lost /
    unexpected) with row-level evidence, the same multiset algebra the
    streamed fold executes incrementally.

The OpSeq half rides the SAME prepass slot as ``hb.py``
(``hb.maybe_hb`` dispatches by model family), so every consumer the HB
solver already reaches — ``checker/seq.py``'s DFS mask,
``checker/linear.py``'s frame mask, ``search_batch``/``bucket.py``
disposal, the decomposed and streamed sub-searches — consumes these
verdicts and must-order edges with zero new wiring.  The event-level
half (:class:`MultisetFold`) is the incremental edge form the
streaming checker's total-queue fold route executes so queue campaign
cells grade ``detection.at="streamed"``.

Soundness invariants (what keeps this verdict-identical by
construction):

  * decide-``valid`` only ever fires after the constructed witness
    replays clean against the model AND real time;
  * decide-``invalid`` only ever fires on independently re-checkable
    evidence (a forced-edge cycle, an impossible dequeue, a counting
    contradiction);
  * must-order edges are forced (hold in every valid linearization),
    so masking them can never flip a verdict;
  * anything outside the gates returns "undecided" and the engines
    run exactly as before.

Knobs: the SAME three-state flag as hb.py (``hb=False`` per call,
``JEPSEN_TPU_HB=0`` fleet-wide) — one prepass slot, one switch.
"""

from __future__ import annotations

import bisect
from collections import Counter

import numpy as np

from ..history import INF_RET, NIL, OpSeq
from ..obs.metrics import REGISTRY
from .hb import (
    EDGE_CAP_FACTOR,
    EDGE_CAP_MIN,
    HBAnalysis,
    _verify_witness,
    _window_effective,
    hb_enabled,
)

_M_PREPASS = REGISTRY.counter(
    "jtpu_constraint_prepass_total",
    "Constraint-compiler pre-pass outcomes by model family",
    ("family", "outcome"))
_M_EDGES = REGISTRY.counter(
    "jtpu_constraint_edges_total",
    "Forced constraint edges inferred beyond real time, by kind",
    ("kind",))
_M_FOLD_FLIPS = REGISTRY.counter(
    "jtpu_constraint_fold_flips_total",
    "Streamed multiset-fold verdict flips, by evidence kind",
    ("kind",))
_M_FOLD_EVENTS = REGISTRY.counter(
    "jtpu_constraint_fold_events_total",
    "Events ingested by streamed multiset folds")


# ---------------------------------------------------------------------------
# family dispatch
# ---------------------------------------------------------------------------


def family_of(model) -> str | None:
    """The constraint family a ModelSpec belongs to, or None when the
    register-family HB solver (or nothing) owns it."""
    name = getattr(model, "name", "") or ""
    if name.startswith("unordered-queue-"):
        return "queue"
    if name.startswith("fifo-queue-"):
        return "fifo-queue"
    if name == "mutex":
        return "lock"
    return None


def analyze_prepass(seq: OpSeq, model) -> HBAnalysis:
    """The unified static prepass: registers go to the HB order-solver,
    queue/lock families to the constraint compiler.  One entry so the
    batch schedulers (bucket.py) and their mirror (explain_batch)
    cannot diverge on which solver disposed a key."""
    from .hb import analyze_hb

    if family_of(model) is None:
        return analyze_hb(seq, model)
    return analyze_constraints(seq, model)


# ---------------------------------------------------------------------------
# the OpSeq pre-pass
# ---------------------------------------------------------------------------


def _decided(valid, *, certificate: dict, stats: dict) -> dict:
    stats["pruned_upper_bound"] = 0
    stats["prune_ratio"] = 0.0
    out = {"valid": valid, "configs": 0, "max_depth": 0,
           "engine": "constraint-decide"}
    out.update(certificate)
    out["constraints"] = stats
    return out


def _edge(src: int, dst: int, kind: str, via=None) -> dict:
    e = {"src": int(src), "dst": int(dst), "kind": kind}
    if via is not None:
        e["via"] = [int(via[0]), int(via[1])]
    return e


def analyze_constraints(seq: OpSeq, model) -> HBAnalysis:
    """The full pre-pass for the non-register families.  Never raises
    on in-scope inputs; anything out of scope comes back
    ``applies=False`` and undecided."""
    fam = family_of(model)
    n = len(seq)
    stats = {"solver": "constraints", "family": fam, "applies": False,
             "decided": None, "reason": None,
             "edges": {"rf": 0, "fifo": 0, "canon": 0}, "must_edges": 0}
    out = HBAnalysis(n=n, applies=False, decided=None, stats=stats)
    if fam is None:
        stats["reason"] = f"model {getattr(model, 'name', None)!r} " \
                          f"out of scope"
        return out
    if n == 0:
        stats["reason"] = "empty history"
        return out
    if fam == "lock":
        return _analyze_lock(seq, model, out)
    return _analyze_queue(seq, model, out, fifo=fam == "fifo-queue")


# ---------------------------------------------------------------------------
# queue family
# ---------------------------------------------------------------------------


class _QVal:
    """One payload value's rows."""

    __slots__ = ("enq", "enq_ok", "deq_ok", "deq_info")

    def __init__(self):
        self.enq: list[int] = []       # enqueue rows, ok + crashed
        self.enq_ok: list[int] = []
        self.deq_ok: list[int] = []
        self.deq_info: list[int] = []


def _analyze_queue(seq: OpSeq, model, out: HBAnalysis,
                   *, fifo: bool) -> HBAnalysis:
    from ..models import Q_DEQ, Q_EMPTY, Q_ENQ

    stats = out.stats
    n = len(seq)
    if tuple(model.init) != (Q_EMPTY,) * model.state_width:
        # a segment fold's carried state seeds the queue: the
        # empty-start algebra (impossible dequeue, counting) is wrong
        stats["reason"] = "non-empty initial queue state"
        return out
    f = np.asarray(seq.f)
    if not bool(np.isin(f, (Q_ENQ, Q_DEQ)).all()):
        stats["reason"] = "foreign op code"
        return out
    out.applies = True
    stats["applies"] = True

    v1 = [int(x) for x in seq.v1]
    ok = [bool(x) for x in seq.ok]
    inv = [int(x) for x in seq.inv]
    ret = [int(x) for x in seq.ret]
    fl = [int(x) for x in f]
    vals: dict[int, _QVal] = {}
    n_enq = 0
    for i in range(n):
        v = v1[i]
        if v == NIL:
            continue  # a NIL-valued row never constrains the multiset
        q = vals.get(v)
        if q is None:
            q = vals[v] = _QVal()
        if fl[i] == Q_ENQ:
            n_enq += 1
            q.enq.append(i)
            if ok[i]:
                q.enq_ok.append(i)
        elif ok[i]:
            q.deq_ok.append(i)
        else:
            q.deq_info.append(i)
    stats["values"] = len(vals)

    def rt(a: int, b: int) -> bool:
        return ret[a] < inv[b]

    # ---- decide-fast: impossible dequeue -----------------------------
    impossible = sorted(r for q in vals.values() if not q.enq
                        for r in q.deq_ok)
    if impossible:
        stats["decided"] = False
        stats["reason"] = "impossible-dequeue"
        out.decided = _decided(False, certificate={
            "final_ops": impossible,
            "queue_evidence": {"family": "queue",
                               "kind": "unexpected-dequeue",
                               "rows": impossible}}, stats=stats)
        return out

    # ---- decide-fast: duplicate delivery -----------------------------
    for q in vals.values():
        if len(q.deq_ok) > len(q.enq):
            stats["decided"] = False
            stats["reason"] = "duplicate-delivery"
            out.decided = _decided(False, certificate={
                "final_ops": sorted(q.deq_ok),
                "queue_dup": {"dequeues": sorted(q.deq_ok),
                              "enqueues": sorted(q.enq)}}, stats=stats)
            return out

    # ---- decide-fast: read-from cycle --------------------------------
    # a dequeue wholly before the ONLY enqueue that could feed it
    for q in vals.values():
        if len(q.enq) != 1:
            continue
        e = q.enq[0]
        for d in q.deq_ok:
            if rt(d, e):
                stats["decided"] = False
                stats["reason"] = "rf-cycle"
                out.decided = _decided(False, certificate={
                    "queue_cycle": [_edge(e, d, "rf"),
                                    _edge(d, e, "rt")]}, stats=stats)
                return out

    # unique (enqueue, dequeue) pairs — the edge/FIFO substrate
    pairs = [(q.enq[0], q.deq_ok[0]) for q in vals.values()
             if len(q.enq) == 1 and len(q.deq_ok) == 1
             and not q.deq_info]

    # ---- decide-fast: FIFO inversion ---------------------------------
    if fifo and len(pairs) >= 2:
        # find (i, j): enq_i wholly before enq_j AND deq_j wholly
        # before deq_i.  Sweep j by increasing inv(enq); the admitted
        # prefix (ret(enq_i) < inv(enq_j)) grows monotonically, and
        # only its max-inv(deq) member can witness the inversion.
        by_einv = sorted(pairs, key=lambda p: inv[p[0]])
        by_eret = sorted(pairs, key=lambda p: ret[p[0]])
        k = 0
        best = None  # (inv(deq_i), pair_i) over the admitted prefix
        for (ej, dj) in by_einv:
            while k < len(by_eret) and ret[by_eret[k][0]] < inv[ej]:
                p = by_eret[k]
                if best is None or inv[p[1]] > best[0]:
                    best = (inv[p[1]], p)
                k += 1
            if best is not None and ret[dj] < best[0]:
                ei, di = best[1]
                if ei != ej:
                    stats["decided"] = False
                    stats["reason"] = "fifo-inversion"
                    out.decided = _decided(False, certificate={
                        "queue_cycle": [
                            _edge(di, dj, "fifo", via=(ei, ej)),
                            _edge(dj, di, "rt")]}, stats=stats)
                    return out

    # ---- decide-fast: constructive valid (unordered only) ------------
    all_ok = all(ok)
    unique = all(len(q.enq) <= 1 and len(q.deq_ok) <= 1
                 for q in vals.values())
    if not fifo and all_ok and unique and not any(v == NIL for v in v1) \
            and model.state_width >= n_enq:
        # completion order, with each enqueue pulled in front of its
        # dequeue: rt-consistent because no rf 2-cycle survived above
        # (ret(deq) >= inv(enq) for every pair), then self-verified by
        # model replay before the decision ever leaves this module
        key = {}
        for q in vals.values():
            if q.enq and q.deq_ok:
                e, d = q.enq[0], q.deq_ok[0]
                key[e] = min(ret[e], ret[d])
        order = sorted(range(n),
                       key=lambda i: (key.get(i, ret[i]),
                                      0 if fl[i] == Q_ENQ else 1, i))
        if _verify_witness(seq, model, order):
            stats["decided"] = True
            stats["reason"] = "completion-schedule"
            out.decided = _decided(True, certificate={
                "linearization": [int(r) for r in order],
                "max_depth": n}, stats=stats)
            return out

    # ---- undecided: emit the prune -----------------------------------
    cap = max(EDGE_CAP_MIN, EDGE_CAP_FACTOR * n)
    edges: list[tuple[int, int, str]] = []
    for q in vals.values():
        if len(q.enq) != 1:
            continue  # no unique writer: no forced read-from
        e = q.enq[0]
        for d in (*q.deq_ok, *q.deq_info):
            if not rt(e, d):
                edges.append((e, d, "rf"))
                if len(edges) >= cap:
                    break
        if len(edges) >= cap:
            break
    if fifo and len(edges) < cap and len(pairs) >= 2:
        # one FIFO predecessor per dequeue: the min-ret enqueue wholly
        # before it forces its dequeue first (edges are individually
        # forced, so a star is as sound as a chain)
        by_einv = sorted(pairs, key=lambda p: inv[p[0]])
        best = None  # (ret(enq), deq) with min ret(enq) so far
        for (e, d) in by_einv:
            if best is not None and best[0] < inv[e] \
                    and not rt(best[1], d):
                edges.append((best[1], d, "fifo"))
                if len(edges) >= cap:
                    break
            if best is None or ret[e] < best[0]:
                best = (ret[e], d)
    if not fifo and len(edges) < cap and n_enq < model.state_width \
            and not any(q.deq_info for q in vals.values()):
        edges += _queue_canon_edges(inv, ret, ok, fl, v1, stats,
                                    cap - len(edges))
    for (_s, _d, k) in edges:
        stats["edges"][k] += 1
    stats["must_edges"] = len(edges)
    must: dict[int, list[int]] = {}
    for (src, dst, _k) in edges:
        must.setdefault(int(dst), []).append(int(src))
    out.must_pred = {d: tuple(sorted(set(s))) for d, s in must.items()}
    _prune_stats(seq, edges, stats)
    return out


def _queue_canon_edges(inv, ret, ok, fl, v1, stats: dict,
                       cap: int) -> list[tuple[int, int, str]]:
    """Canonical-order edges of an unordered queue whose capacity never
    binds (fewer enqueues than lanes) and whose crashed dequeues carry
    no value.  Two kinds, counted in ``stats`` (``early_enqueues``,
    ``drain_chain``):

      * a crashed enqueue goes before every :ok row invoked after it.
        An element added earlier, or added at all, makes no op of a
        multiset illegal, and the enqueue is then still after every row
        that returned before its invocation — so any linearization
        becomes one that satisfies these edges.  A crashed enqueue
        then never stays open across levels, where each doubled the
        frontier for as long as it might still come;
      * a final drain, expanded into concurrent dequeues, goes in row
        order: the largest group of :ok dequeues invoked last, pairwise
        concurrent, after every other :ok row returned and before any
        crashed row could be invoked.  In a linearization the crashed
        rows among the group move before it (the enqueues by the first
        kind already are; a dequeue without a value is a no-op), and
        then the group's dequeues, each taking a value present, can
        take any order — so they need only one, and the C(k, j)
        subsets a search opens over k of them become one chain.

    Edges that real time already implies are not emitted."""
    from ..models import Q_DEQ, Q_ENQ

    n = len(inv)
    oks = sorted((i for i in range(n) if ok[i]), key=lambda i: inv[i])
    if not oks:
        return []
    invs = [inv[i] for i in oks]
    sfx = [0] * len(oks)        # least return from each :ok row on
    m = INF_RET
    for k in range(len(oks) - 1, -1, -1):
        m = min(m, ret[oks[k]])
        sfx[k] = m
    out: list[tuple[int, int, str]] = []
    crashed = [i for i in range(n) if not ok[i]]
    early = 0
    for c in crashed:
        if fl[c] != Q_ENQ or v1[c] == NIL:
            continue
        k = bisect.bisect_right(invs, inv[c])
        if k < len(oks):
            early += 1
        bound = sfx[k] if k < len(oks) else -1
        while k < len(oks) and invs[k] < bound and len(out) < cap:
            out.append((c, oks[k], "canon"))
            k += 1
    # the drain: the least s whose :ok suffix oks[s:] qualifies
    last_crash_inv = max((inv[c] for c in crashed), default=-1)
    pre = [-1] * (len(oks) + 1)  # greatest return before each :ok row
    for k, r in enumerate(oks):
        pre[k + 1] = max(pre[k], ret[r])
    start = None
    for s in range(len(oks) - 1, -1, -1):
        r = oks[s]
        if fl[r] != Q_DEQ or v1[r] == NIL or invs[-1] >= sfx[s] \
                or last_crash_inv >= sfx[s]:
            break
        if pre[s] < invs[s] and s < len(oks) - 1:
            start = s
    chain = sorted(oks[start:]) if start is not None else []
    for a, b in zip(chain, chain[1:]):
        if len(out) < cap:
            out.append((a, b, "canon"))
    stats["early_enqueues"] = early
    stats["drain_chain"] = len(chain)
    return out


def _prune_stats(seq: OpSeq, edges, stats: dict) -> None:
    w_raw, w_eff = _window_effective(seq, edges)
    ok = np.asarray(seq.ok, dtype=bool)
    nd = int(ok.sum())
    n = len(seq)
    raw = (nd + 1) << (max(0, w_raw - 1) + (n - nd))
    pruned = min((nd + 1) << (max(0, w_eff - 1) + (n - nd)), raw)
    stats["window_effective"] = w_eff
    stats["pruned_upper_bound"] = pruned
    stats["prune_ratio"] = round(pruned / raw, 6) if raw else None


# ---------------------------------------------------------------------------
# lock family
# ---------------------------------------------------------------------------


def _analyze_lock(seq: OpSeq, model, out: HBAnalysis) -> HBAnalysis:
    from ..models import M_ACQUIRE, M_RELEASE

    stats = out.stats
    if tuple(model.init) != (0,):
        stats["reason"] = "non-free initial lock state"
        return out
    f = np.asarray(seq.f)
    if not bool(np.isin(f, (M_ACQUIRE, M_RELEASE)).all()):
        stats["reason"] = "foreign op code"
        return out
    out.applies = True
    stats["applies"] = True
    ok = [bool(x) for x in seq.ok]
    inv = [int(x) for x in seq.inv]
    ret = [int(x) for x in seq.ret]
    fl = [int(x) for x in f]
    n = len(seq)
    acq_rows = [i for i in range(n) if fl[i] == M_ACQUIRE]
    rel_rows = [i for i in range(n) if fl[i] == M_RELEASE]
    stats["acquires"] = len(acq_rows)
    stats["releases"] = len(rel_rows)

    # forced double-hold: at the k-th :ok acquire completion, fewer
    # than k-1 releases could possibly have linearized
    acq_ok = sorted((i for i in acq_rows if ok[i]),
                    key=lambda i: ret[i])
    rel_inv = sorted(inv[i] for i in rel_rows)
    for k, i in enumerate(acq_ok, start=1):
        possible_rel = bisect.bisect_left(rel_inv, ret[i])
        if k - possible_rel >= 2:
            stats["decided"] = False
            stats["reason"] = "lock-overhold"
            out.decided = _decided(False, certificate={
                "final_ops": sorted(acq_ok[max(0, k - 2):k])},
                stats=stats)
            return out

    # forced release-of-free: at the k-th :ok release completion,
    # fewer than k acquires could possibly have linearized
    rel_ok = sorted((i for i in rel_rows if ok[i]),
                    key=lambda i: ret[i])
    acq_inv = sorted(inv[i] for i in acq_rows)
    for k, i in enumerate(rel_ok, start=1):
        possible_acq = bisect.bisect_left(acq_inv, ret[i])
        if k - possible_acq >= 1:
            stats["decided"] = False
            stats["reason"] = "release-unheld"
            out.decided = _decided(False, certificate={
                "final_ops": [i]}, stats=stats)
            return out

    # alternation has no unique-writer structure: no forced edges to
    # emit, and decide-valid stays with the engines
    _prune_stats(seq, [], stats)
    return out


# ---------------------------------------------------------------------------
# the prepass slot (hb.maybe_hb dispatches here)
# ---------------------------------------------------------------------------


def maybe_constraints(seq: OpSeq, model) -> HBAnalysis:
    """Run the constraint pre-pass under a span + the
    ``jtpu_constraint_*`` metrics — the non-register twin of
    ``hb.maybe_hb``'s body (the flag was already resolved there)."""
    from .. import obs

    fam = family_of(model) or "none"
    with obs.span("constraints.prepass", cat="analyze", rows=len(seq),
                  family=fam) as sp:
        a = analyze_constraints(seq, model)
        sp.set(**{k: a.stats[k] for k in ("early_enqueues", "drain_chain")
                  if k in a.stats})
    if not a.applies:
        _M_PREPASS.inc(family=fam, outcome="skipped")
        return a
    if a.decided is not None:
        _M_PREPASS.inc(family=fam, outcome="decided_valid"
                       if a.decided["valid"] else "decided_invalid")
    else:
        _M_PREPASS.inc(family=fam, outcome="undecided")
        for k, v in a.stats["edges"].items():
            if v:
                _M_EDGES.inc(v, kind=k)
    return a


def plan_block(seq: OpSeq, model) -> dict:
    """The static ``constraints`` block for explain(): family,
    decidability, inferred edge counts, and the streamed-fold
    eligibility (which incremental fold route the family has).  Pure
    description — no live metrics are touched."""
    fam = family_of(model)
    if fam is None:
        return {"applies": False, "family": None, "enabled": hb_enabled(),
                "reason": "register-family model (see the hb block)",
                "stream_fold": {"eligible": False, "route": None}}
    a = analyze_constraints(seq, model)
    st = dict(a.stats)
    st["enabled"] = hb_enabled()
    st["stream_fold"] = {
        "eligible": fam in ("queue", "fifo-queue"),
        "route": "total-queue" if fam in ("queue", "fifo-queue")
        else None}
    if "pruned_upper_bound" not in st:
        st.setdefault("pruned_upper_bound", None)
        st.setdefault("prune_ratio", 1.0)
    return st


# ---------------------------------------------------------------------------
# event-level multiset analysis (the checkers' and the fold's substrate)
# ---------------------------------------------------------------------------


def analyze_queue_events(history) -> dict:
    """Static multiset analysis of an event-level queue history — the
    same verdict ``checker.basic.total_queue`` computes, carried as
    row-level evidence (event indices) the W007 audit re-justifies.
    Returns::

        {"valid": bool, "evidence": {...} | None,
         "edges": n_rf, "lost": {...}, "unexpected": {...}}

    Drains expand exactly as the checker expands them; an
    unexpandable (crashed) drain yields ``{"valid": "unknown"}``, the
    checker's own behavior under ``check_safe``.
    """
    from ..history import is_invoke, is_ok

    attempts: Counter = Counter()
    enq_ok: Counter = Counter()
    enq_ok_row: dict = {}
    deq: Counter = Counter()
    first_deq_row: dict = {}
    edges = 0
    for i, op in enumerate(history):
        if not isinstance(op.process, int):
            continue
        if op.f == "enqueue":
            if is_invoke(op):
                attempts[op.value] += 1
            elif is_ok(op):
                enq_ok[op.value] += 1
                enq_ok_row.setdefault(op.value, i)
        elif op.f == "dequeue" and is_ok(op):
            deq[op.value] += 1
            first_deq_row.setdefault(op.value, i)
            if op.value in enq_ok_row:
                edges += 1  # enqueue -> dequeue read-from
        elif op.f == "drain":
            if is_ok(op) and isinstance(op.value, (list, tuple)):
                for element in op.value:
                    deq[element] += 1
                    first_deq_row.setdefault(element, i)
                    if element in enq_ok_row:
                        edges += 1
            elif not is_invoke(op) and op.type != "fail":
                return {"valid": "unknown", "evidence": None,
                        "edges": edges,
                        "info": "crashed drain: removed elements "
                                "unidentifiable"}
    lost = enq_ok - deq
    unexpected = Counter({v: c for v, c in deq.items()
                          if v not in attempts})
    evidence = None
    if unexpected:
        rows = sorted(first_deq_row[v] for v in unexpected)
        evidence = {"family": "queue", "kind": "unexpected-dequeue",
                    "rows": rows, "values": sorted(map(str, unexpected))}
    elif lost:
        rows = sorted(enq_ok_row[v] for v in lost if v in enq_ok_row)
        evidence = {"family": "queue", "kind": "lost-acked-enqueue",
                    "rows": rows, "values": sorted(map(str, lost))}
    return {"valid": not lost and not unexpected, "evidence": evidence,
            "edges": edges, "lost": dict(lost),
            "unexpected": dict(unexpected)}


def analyze_set_events(history) -> dict:
    """Static set analysis: add->member-read edges plus the SetChecker
    verdict (lost / unexpected against the final read) with row-level
    evidence."""
    from ..history import is_invoke, is_ok

    attempts: set = set()
    add_ok_row: dict = {}
    final_read = None
    final_row = None
    edges = 0
    for i, op in enumerate(history):
        if not isinstance(op.process, int):
            continue
        if op.f == "add":
            if is_invoke(op):
                attempts.add(op.value)
            elif is_ok(op):
                add_ok_row.setdefault(op.value, i)
        elif op.f == "read" and is_ok(op):
            final_read, final_row = set(op.value or ()), i
    if final_read is None:
        return {"valid": "unknown", "evidence": None, "edges": 0}
    edges = sum(1 for v in final_read if v in add_ok_row)
    lost = set(add_ok_row) - final_read
    unexpected = final_read - attempts
    evidence = None
    if unexpected:
        evidence = {"family": "set", "kind": "unexpected-member",
                    "rows": [final_row],
                    "values": sorted(map(str, unexpected))}
    elif lost:
        evidence = {"family": "set", "kind": "lost-acked-add",
                    "rows": sorted(add_ok_row[v] for v in lost),
                    "values": sorted(map(str, lost))}
    return {"valid": not lost and not unexpected, "evidence": evidence,
            "edges": edges, "lost": sorted(map(str, lost)),
            "unexpected": sorted(map(str, unexpected))}


class MultisetFold:
    """The incremental edge form of the multiset analysis — what the
    streaming checker's total-queue fold route executes per event.

    ``step(op, event_idx)`` folds one history event and returns flip
    evidence (a dict shaped like :func:`analyze_queue_events`'s
    ``evidence``) the FIRST time the running state proves the history
    invalid, else None.  Two flip rules, both confirmed at finalize by
    the post-hoc checker (the final verdict is always the checker's):

      * **unexpected** — an :ok dequeue (or drained element) of a
        value no enqueue ever attempted: flagged at the dequeue's
        event.
      * **lost** — AT an :ok drain's own completion with no client op
        pending, acked enqueues missing from every dequeue/drain so
        far are lost: flagged at the drain event (the moment the final
        drain returns short, not minutes later at teardown).  Never
        evaluated at other completions — an enqueue acked after the
        drain must not be flagged the instant its own :ok lands.

    ``family="set"``: adds/reads with the read as the drain analog.
    """

    def __init__(self, family: str = "total-queue"):
        self.family = "set" if family == "set" else "total-queue"
        self.attempts: Counter = Counter()
        self.enq_ok: Counter = Counter()
        self.enq_ok_row: dict = {}
        self.deq: Counter = Counter()
        self.pending: dict = {}     # process -> f
        self.drained = False        # an :ok drain/read has landed
        self.lossy = False          # crashed drain: lost undecidable
        self.last_read: set | None = None
        self.last_read_row: int | None = None

    # -- event fold ----------------------------------------------------

    def step(self, op, i: int) -> dict | None:
        from ..history import INVOKE

        _M_FOLD_EVENTS.inc()
        if not isinstance(op.process, int):
            return None
        if op.type == INVOKE:
            self.pending[op.process] = op.f
            if op.f in ("enqueue", "add"):
                self.attempts[op.value] += 1
            return None
        self.pending.pop(op.process, None)
        if self.family == "set":
            flip = self._step_set(op, i)
        else:
            flip = self._step_queue(op, i)
        if flip is not None:
            _M_FOLD_FLIPS.inc(kind=flip["kind"])
        return flip

    def _step_queue(self, op, i: int) -> dict | None:
        from ..history import is_ok

        if op.f == "enqueue" and is_ok(op):
            self.enq_ok[op.value] += 1
            self.enq_ok_row.setdefault(op.value, i)
        elif op.f == "dequeue" and is_ok(op):
            self.deq[op.value] += 1
            if op.value not in self.attempts:
                return {"family": "queue", "kind": "unexpected-dequeue",
                        "rows": [i], "values": [str(op.value)]}
        elif op.f == "drain":
            if is_ok(op) and isinstance(op.value, (list, tuple)):
                self.drained = True
                for element in op.value:
                    self.deq[element] += 1
                    if element not in self.attempts:
                        return {"family": "queue",
                                "kind": "unexpected-dequeue",
                                "rows": [i],
                                "values": [str(element)]}
                # the lost rule runs ONLY here, at a drain's own
                # completion with nothing pending — never at later
                # quiescent completions, where an enqueue acked AFTER
                # the drain would be flagged the instant its :ok lands
                if not self.lossy and not self.pending:
                    lost = self.enq_ok - self.deq
                    if lost:
                        rows = sorted(self.enq_ok_row[v] for v in lost
                                      if v in self.enq_ok_row)
                        return {"family": "queue",
                                "kind": "lost-acked-enqueue",
                                "rows": rows,
                                "values": sorted(map(str, lost))}
            elif op.type == "info":
                self.lossy = True  # removed elements unidentifiable
        return None

    def _step_set(self, op, i: int) -> dict | None:
        from ..history import is_ok

        if op.f == "add" and is_ok(op):
            self.enq_ok[op.value] += 1
            self.enq_ok_row.setdefault(op.value, i)
        elif op.f == "read" and is_ok(op):
            self.drained = True
            self.last_read = set(op.value or ())
            self.last_read_row = i
            unexpected = self.last_read - set(self.attempts)
            if unexpected:
                return {"family": "set", "kind": "unexpected-member",
                        "rows": [i],
                        "values": sorted(map(str, unexpected))}
            # as with drains: lost evaluates only AT the read itself
            # (an add acked after the final read is not lost)
            if not self.pending:
                lost = set(self.enq_ok_row) - self.last_read
                if lost:
                    return {"family": "set", "kind": "lost-acked-add",
                            "rows": sorted(self.enq_ok_row[v]
                                           for v in lost),
                            "values": sorted(map(str, lost))}
        return None
