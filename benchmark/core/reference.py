"""The plain reference: Wing & Gong's search with Lowe's memo, over the
generator's plain events, for the configuration's model.

It imports nothing of `jepsen_tpu` and takes nothing the program made.
A configuration is (set of linearized ops, model state).  From one, op j
may go next iff it is not linearized, it was invoked before every
unlinearized ok op returned, and the model allows it.  The history is
linearizable iff some configuration holds every ok op.  A crashed (info)
op never returns: it may take effect at any point after its invocation,
or never.

The model is its file, `models/<model>.py` (`spec.model`):
``start(init, control)`` is its first state and ``step(state, op,
control)`` its transition, None where it does not allow the op.
``control=True`` is one control of the correctness check: the same
search over the model's weaker guarantee (for the CAS register, a read
may return any value the register has held, as etcd gives without
quorum reads).  It is not linearizability, and the check has to tell
the two apart.
"""

from __future__ import annotations

from .gen import INF, pair


def rows_of(events):
    """Ops that may have taken effect, in invocation order: the rows a
    certificate's indices refer to."""
    return [op for op in pair(events) if op["type"] != "fail"]


def split_keys(events):
    """Per-key event lists of a keyed history; nemesis events dropped."""
    out = {}
    for e in events:
        if e[0] != "nemesis":
            out.setdefault(e[3], []).append((e[0], e[1], e[2], e[4]))
    return out


def check(model, rows, init, *, control=False):
    """Returns (valid, configurations visited, depth): depth is the most
    rows (ok and info) any configuration linearized, the length of
    knossos's deepest partial linearizations.  The set of linearized
    rows is kept as (first unlinearized ok row, bits of the ok rows
    after it, bits of the info rows), so each step costs the width of
    the window and not the length of the history."""
    oks = [op for op in rows if op["type"] == "ok"]
    infos = [op for op in rows if op["type"] != "ok"]
    n = len(oks)
    start = (0, 0, 0, model.start(init, control))
    seen = {start}
    stack = [start]
    depth = 0
    while stack:
        low, rel, done_info, state = stack.pop()
        depth = max(depth, low + rel.bit_count() + done_info.bit_count())
        if low == n:
            return True, len(seen), depth
        # ok rows are in invocation order: the scan stops at the first
        # one invoked after the earliest return among unlinearized rows
        first_ret = INF
        j = low
        while j < n and oks[j]["inv"] < first_ret:
            if not rel >> (j - low) & 1:
                first_ret = min(first_ret, oks[j]["ret"])
                nxt = model.step(state, oks[j], control)
                if nxt is not None:
                    r = rel | 1 << (j - low)
                    t = (~r & (r + 1)).bit_length() - 1
                    cfg = (low + t, r >> t, done_info, nxt)
                    if cfg not in seen:
                        seen.add(cfg)
                        stack.append(cfg)
            j += 1
        for i, op in enumerate(infos):
            if op["inv"] < first_ret and not done_info >> i & 1:
                nxt = model.step(state, op, control)
                if nxt is not None:
                    cfg = (low, rel, done_info | 1 << i, nxt)
                    if cfg not in seen:
                        seen.add(cfg)
                        stack.append(cfg)
    return False, len(seen), depth


def replay(model, rows, order, init, *, whole=True):
    """True iff ``order`` (row indices) is a linearization: distinct
    rows, each invoked before every other row not yet placed returned,
    and allowed by the model; with ``whole``, every ok row placed.
    With ``whole=False`` it checks a partial linearization, as knossos
    reports the deepest ones of an invalid history."""
    if len(set(order)) != len(order) or any(
            not 0 <= i < len(rows) for i in order):
        return False
    oks = {i for i, op in enumerate(rows) if op["type"] == "ok"}
    if whole and oks - set(order):
        return False
    # returns of the ok rows not yet placed, ascending
    open_rets = sorted((rows[i]["ret"], i) for i in oks)
    state = model.start(init, False)
    for i in order:
        first = next((r for r, j in open_rets[:2] if j != i), INF)
        if rows[i]["inv"] > first:
            return False
        if i in oks:
            open_rets.remove((rows[i]["ret"], i))
        state = model.step(state, rows[i], False)
        if state is None:
            return False
    return True
