"""Sequential linearizability oracle — host-side Wing-Gong/Lowe DFS.

This is the CPU reference implementation of the search the TPU engine
(checker/linearizable.py) vectorizes.  It plays three roles:

  1. the differential-test oracle for the TPU engine (random histories must
     agree — the analog of the reference racing knossos `linear` vs `wgl`
     in `competition`, checker.clj:122-126);
  2. the small-history fast path (device dispatch has fixed overhead);
  3. witness reconstruction: when the TPU pass finds a history invalid,
     this DFS re-derives a concrete longest-linearizable prefix for the
     report (SURVEY.md §7 "witness reconstruction").

Algorithm (knossos.wgl / Lowe "Testing for linearizability", see
PAPERS.md): a *configuration* is (set of linearized ops, model state).
From a configuration, any op j may be linearized next iff

    j not linearized, and
    inv[j] < ret[k]  for every other unlinearized op k
    (no unlinearized op returned before j was invoked), and
    model.step(state, j) is legal.

The history is valid iff some configuration containing every ``ok`` op is
reachable.  ``info`` (crashed/indeterminate) ops have ret = +inf: they
never block anything and may linearize at any point after invocation, or
never — exactly knossos's crashed-op semantics (core.clj:387-397 defines
how crashed processes arise).

The search is DFS with a visited memo on (linearized-set, state); sets are
Python bigint bitmasks.  A configuration's enabled ops are found by walking
the zero bits of its mask lowest first, up to the first op invoked at or
after the least pending return: O(window) bit operations a configuration,
whatever the depth, since already-linearized ops are never visited.  Worst
case exponential — ``max_configs`` bounds work and yields {"valid":
"unknown"} past it, the moral equivalent of the reference's -Xmx32g
ceiling (jepsen/project.clj:25).
"""

from __future__ import annotations

from typing import Optional

from ..history import INF_RET, OpSeq
from ..models import ModelSpec


def _walk_parents(parent_of: dict, key) -> list[int]:
    """Rebuild a linearization (op rows, in order) by walking parents."""
    lin: list[int] = []
    k = key
    while k is not None:
        p = parent_of.get(k)
        if p is None:
            break
        op, pk = p
        lin.append(op)
        k = pk
    lin.reverse()
    return lin


def check_opseq(seq: OpSeq, model: ModelSpec, *,
                max_configs: int = 5_000_000,
                deadline: float | None = None,
                cancel=None,
                order_seed: int | None = None,
                decompose: bool = False,
                decompose_cache=None,
                lint: bool | None = None,
                audit: bool | None = None,
                hb: bool | None = None,
                dpor: bool | None = None) -> dict:
    """Run the DFS over a columnar OpSeq.  Returns a knossos-style map:

    valid        True | False | "unknown"
    configs      number of configurations explored
    linearization  (valid only) list of row indices in linearization order
    max_depth    deepest prefix length reached
    final_ops    (invalid only) row indices of candidate ops at the
                 deepest frontier — the ops that could not be linearized

    Every valid verdict from this engine carries its witness (the DFS
    parent chain is free), and every invalid one its blocking frontier;
    ``audit`` replays that certificate through the independent audit
    pass (analyze/audit.py; None follows JEPSEN_TPU_AUDIT).

    ``deadline`` (``time.perf_counter()`` clock) yields "unknown" once
    exceeded (checked every 4096 configs) — the wall-clock twin of
    ``max_configs`` for time-bounded throughput comparisons.  ``cancel``
    (a ``threading.Event``) yields "unknown" once set — how the
    competition mode retires the loser (see
    ``linearizable.check_competition``).  ``order_seed`` randomizes the
    DFS candidate-push order: the verdict is unchanged, but different
    seeds dive different subtrees first — the diversity knob for the
    portfolio comparator (checker/parallel.py).  ``decompose`` routes
    through the P-compositional decomposition layer (jepsen_tpu/
    decompose/) with this DFS as the sub-engine — verdict-identical,
    default off; ``decompose_cache`` is its VerdictCache or jsonl path.
    ``lint`` runs the O(n) well-formedness linter (analyze/lint.py)
    over the OpSeq before searching — on by default (None follows the
    JEPSEN_TPU_LINT knob); errors raise
    :class:`~jepsen_tpu.analyze.HistoryLintError` instead of feeding a
    malformed history to the search.  Verdict-identical on well-formed
    histories (tests/test_analyze.py's differential fuzz).
    ``hb`` runs the happens-before pre-pass (analyze/hb.py; None
    follows JEPSEN_TPU_HB, default on): statically decided histories
    return immediately with an audited certificate and zero explored
    configs, and undecided ones search under the must-order mask —
    verdict-identical either way.
    ``dpor`` enables the dynamic partial-order reduction layer
    (analyze/dpor.py; None follows JEPSEN_TPU_DPOR, default on):
    duplicate-op canonical edges join the must-order mask, explored
    siblings that commute at the concrete state become *sleep sets*
    pruning covered interleavings, and register states holding a value
    no remaining op compares against collapse onto one dead token
    (decompose/canonical.py's quotient) so symmetric interleavings
    dedup in the visited memo — verdict-identical by construction.
    """
    from ..analyze.audit import maybe_audit
    from ..analyze.dpor import (SleepSets, resolve_dpor, sleep_visit,
                                _M_DEDUP, _M_MASK)
    from ..analyze.hb import attach, maybe_hb
    from ..analyze.lint import maybe_lint

    maybe_lint(seq, model, lint)

    dpor_stats: dict | None = None

    def finish(out: dict) -> dict:
        if dpor_stats is not None:
            out.setdefault("dpor", dpor_stats)
        return maybe_audit(seq, model, attach(out, hbres), audit)

    hbres = None
    if not decompose:
        hbres = maybe_hb(seq, model, hb, dpor)
        if hbres is not None and hbres.decided is not None:
            return finish(dict(hbres.decided))

    if decompose:
        from ..decompose.engine import check_opseq_decomposed

        def _direct(s):
            return check_opseq(s, model, max_configs=max_configs,
                               deadline=deadline, cancel=cancel,
                               order_seed=order_seed, lint=False,
                               hb=hb, dpor=dpor)

        def _sub(s, m, *, max_configs=max_configs, deadline=deadline):
            return check_opseq(s, m, max_configs=max_configs,
                               deadline=deadline, cancel=cancel,
                               order_seed=order_seed, lint=False,
                               hb=hb, dpor=dpor)

        # the entry seq was linted above (when enabled); cells/segments
        # are engine-derived projections, so re-linting them would only
        # re-prove invariants subseq preserves by construction.
        # witness=True: this DFS tracks parent chains anyway, so the
        # decomposed route stitches them for free
        return check_opseq_decomposed(seq, model, cache=decompose_cache,
                                      direct=_direct, sub_check=_sub,
                                      sub_max_configs=max_configs,
                                      deadline=deadline, lint=False,
                                      witness=True, audit=audit,
                                      hb=hb)
    import random as _random
    import time
    n = len(seq)
    ok_mask = 0
    for i in range(n):
        if bool(seq.ok[i]):
            ok_mask |= 1 << i
    if n == 0:
        return finish({"valid": True, "configs": 0, "linearization": [],
                       "max_depth": 0})

    inv = [int(x) for x in seq.inv]
    ret = [int(x) for x in seq.ret]
    f = [int(x) for x in seq.f]
    v1 = [int(x) for x in seq.v1]
    v2 = [int(x) for x in seq.v2]
    pystep = model.pystep
    full = (1 << n) - 1

    # must-order mask (HB pre-pass): op j may linearize only once every
    # must-predecessor is in the linearized set — forced edges hold in
    # every valid linearization, canonical edges lose none
    preds = [0] * n
    if hbres is not None and hbres.must_pred:
        for dst, srcs in hbres.must_pred.items():
            pm = 0
            for s_ in srcs:
                pm |= 1 << s_
            preds[dst] = pm

    # dynamic layer (analyze/dpor.py): sleep sets over observed
    # commutativity + the dead-value state quotient
    dpor_on = resolve_dpor(dpor)
    sleep_sets = None
    cmp_masks = None
    dead_tok = 0
    if dpor_on and n:
        from ..decompose.canonical import comparison_row_masks
        from ..history import NIL as _NIL

        sleep_sets = SleepSets(seq, model)
        cm = comparison_row_masks(seq, model)
        if cm is not None:
            cmp_masks, _dv = cm
            dead_tok = _dv.token
        dpor_stats = {"enabled": True, "sleep_prunes": 0,
                      "dedup_rewrites": 0, "dedup_hits": 0,
                      "mask_skips": 0}

    # visited maps (mask, state) -> the intersection of the sleep
    # masks it was expanded under (dpor off: always 0, degenerating
    # to the plain visited set — see dpor.sleep_visit)
    visited: dict = {}
    configs = 0
    max_depth = -1
    best_frontier: list[int] = []
    best_keys: list[tuple] = []

    def covered(key, sleep: int) -> bool:
        """Read-only pre-push peek (the pop does the recording
        visit)."""
        z1 = visited.get(key)
        return z1 is not None and z1 & ~sleep == 0

    # DFS stack entries: (mask, state, sleep); parent_of records
    # (op, parent_key) so the linearization is rebuilt by walking
    # parents on success.
    init = model.init
    stack: list[tuple[int, tuple, int]] = [(0, init, 0)]
    parent_of: dict[tuple[int, tuple], Optional[tuple]] = {(0, init): None}

    while stack:
        mask, state, sleep = stack.pop()
        key = (mask, state)
        first_visit = key not in visited
        missing = sleep_visit(visited, key, sleep)
        if missing is None:
            continue
        if first_visit:
            # revisits expand ONLY `missing` (previously-sleeping)
            # transitions — bounded clean-up, not new configurations;
            # counting them would make a dpor run look more expensive
            # than the exploration it saved
            configs += 1
        if configs > max_configs:
            return finish({"valid": "unknown", "configs": configs,
                           "max_depth": max_depth,
                           "info": f"exceeded max_configs={max_configs}"})
        if configs % 4096 == 0:
            if deadline is not None and time.perf_counter() > deadline:
                return finish({"valid": "unknown", "configs": configs,
                               "max_depth": max_depth,
                               "info": "exceeded deadline"})
            if cancel is not None and cancel.is_set():
                return finish({"valid": "unknown", "configs": configs,
                               "max_depth": max_depth,
                               "info": "cancelled"})

        if (mask & ok_mask) == ok_mask:
            lin = _walk_parents(parent_of, key)
            return finish({"valid": True, "configs": configs,
                           "linearization": lin,
                           "max_depth": len(lin)})

        # Enabled candidates: visit the unlinearized ops (the zero bits of
        # mask, lowest first) in invocation order, maintaining the min
        # return among unlinearized seen so far.  Once inv[j] >= that min,
        # no later op can be enabled (invocations are sorted), and the
        # window min equals the global unlinearized min because any op
        # past the stop point has ret > inv >= stop.  Linearized ops are
        # never visited: O(window) a configuration, whatever the depth.
        cand: list[int] = []
        rets: list[int] = []
        minret = INF_RET + 1
        free = full & ~mask
        while free:
            low = free & -free
            j = low.bit_length() - 1
            if inv[j] >= minret:
                break
            cand.append(j)
            rets.append(ret[j])
            if ret[j] < minret:
                minret = ret[j]
            free ^= low

        depth = mask.bit_count()
        if depth > max_depth:
            max_depth = depth
            best_frontier = list(cand)
            best_keys = [key]
        elif depth == max_depth and len(best_keys) < 10:
            best_keys.append(key)  # checker.clj:136-139 keeps 10 configs

        # min-excluding-self via (min, second-min)
        if rets:
            m1 = min(rets)
            m1_count = rets.count(m1)
            m2 = INF_RET + 1
            first = True
            for r in rets:
                if r == m1 and first:
                    first = False
                elif r < m2:
                    m2 = r
        order = range(len(cand))
        if order_seed is not None:
            order = list(order)
            _random.Random(order_seed ^ hash(key)).shuffle(order)
        pushes: list[tuple[int, tuple]] = []
        explorable = 0  # candidates past excl+preds (prior visits
        # explored these, minus their sleeps — the justified sleep
        # base for missing-mode children)
        for idx in order:
            j2 = cand[idx]
            excl = m2 if rets[idx] == m1 and m1_count == 1 else m1
            if inv[j2] >= excl:
                continue
            if preds[j2] & ~mask:
                if dpor_stats is not None:
                    dpor_stats["mask_skips"] += 1
                    _M_MASK.inc(site="dfs")
                continue  # a must-predecessor is not yet linearized
            explorable |= 1 << j2
            if missing and not (missing >> j2) & 1:
                continue  # revisit: only previously-sleeping
                # transitions need (re-)exploration
            if (sleep >> j2) & 1:
                # sleeping: this continuation was fully covered through
                # a commuting sibling explored first (analyze/dpor.py)
                sleep_sets.record_prune()
                dpor_stats["sleep_prunes"] += 1
                continue
            new_state = pystep(state, f[j2], v1[j2], v2[j2])
            if new_state is None:
                continue
            nm = mask | (1 << j2)
            if cmp_masks is not None:
                v = new_state[0]
                if v != dead_tok and v != _NIL:
                    cmpm = cmp_masks.get(v)
                    if cmpm is None or (cmpm & ~nm) == 0:
                        # every row comparing v is linearized: the
                        # value is observation-dead — collapse onto
                        # the canonical token so symmetric siblings
                        # merge in the visited memo
                        new_state = (dead_tok,)
                        dpor_stats["dedup_rewrites"] += 1
                        _M_DEDUP.inc(site="dfs", event="rewrite")
            pushes.append((j2, (nm, new_state)))
        # assign child sleep sets: a child pushed at index t is popped
        # AFTER pushes[t+1:] (stack order), so those siblings' subtrees
        # are fully explored first and — where they commute with the
        # taken op at this state — join the child's sleep set
        child_sleeps = [0] * len(pushes)
        if sleep_sets is not None and pushes:
            # on a missing-mode revisit the non-missing candidates were
            # explored by prior visits, so they are justified sleepers
            # for the re-explored children
            prior = (explorable & ~missing) if missing else 0
            suffix = 0
            for t in range(len(pushes) - 1, -1, -1):
                j2 = pushes[t][0]
                base = (sleep | prior | suffix) & ~(1 << j2)
                if base:
                    child_sleeps[t] = sleep_sets.child_sleep(
                        state, j2, base)
                suffix |= 1 << j2
        for (j2, nk), csl in zip(pushes, child_sleeps):
            if not covered(nk, csl):
                if nk not in parent_of:
                    parent_of[nk] = (j2, key)
                stack.append((nk[0], nk[1], csl))
            elif dpor_stats is not None and nk[1] == (dead_tok,) \
                    and cmp_masks is not None:
                dpor_stats["dedup_hits"] += 1
                _M_DEDUP.inc(site="dfs", event="hit")

    # reconstruct up to 10 deepest partial linearizations — the analog of
    # knossos's :final-paths, truncated exactly as checker.clj:136-139
    # ("writing these can take *hours*") truncates for the report
    final_paths = [{"linearized": _walk_parents(parent_of, bkey),
                    "state": bkey[1]}
                   for bkey in best_keys[:10]]
    return finish({"valid": False, "configs": configs,
                   "max_depth": max_depth,
                   "final_ops": best_frontier,
                   "final_paths": final_paths})
