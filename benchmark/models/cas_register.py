"""The CAS register (knossos's cas-register): read, write and
compare-and-set over small integer values.  A configuration whose
checker's ``model`` is the program's ``cas_register`` is checked with
this file's parts.

Copied from the program, and changed only where noted:

* `register_history` is `jepsen_tpu/synth.register_history`.  Changes:
  a crashed worker goes on under a fresh process id (p + concurrency),
  as a Jepsen worker thread does, instead of being retired; ``crashes``
  crashes exactly that many invocations, so every history does the
  same amount of indeterminate work; ``init`` sets the register's
  starting value (ZooKeeper's suite starts at 0).  ``exact`` stops
  invoking at an exact count of ops that may have taken effect, as
  `bench._exact_encoded` scanned for.
* `stale_read` replaces `synth.corrupt_read`: the read returns a value
  that really was written, but that no linearization can return.

The reference's state is (register value, values it has held); the
second part is kept only by the control, the register that lets a read
return any value it has held, the guarantee etcd gives without quorum
reads.
"""

from __future__ import annotations

import math

from core.gen import pair

#: the traffic keys this model reads: where in the history the stale
#: read of an invalid history sits
TRAFFIC_KEYS = {"stale_at"}


def register_history(rng, *, n_ops, n_procs, overlap=4, crash_p=0.0,
                     max_crashes=16, crashes=None, n_values=5, cas=True,
                     init=None, exact=False):
    """Concurrent CAS-register history, valid by construction: each op
    takes effect atomically at its completion event.  Returns events
    ``(process, type, f, value)`` with worker-local process ids.
    ``exact`` makes ``n_ops`` the count of ops that may have taken
    effect (ok or info; fails don't count) instead of invocations."""
    state = init
    h = []
    pending = {}            # worker -> (f, value)
    pid = list(range(n_procs))
    crash_set = (set(rng.sample(range(n_ops), crashes))
                 if crashes is not None else None)
    crash_of = {}           # worker -> whether its pending op crashes
    n_crashed = 0
    done = 0
    failed = 0

    def more():
        return done - failed < n_ops if exact else done < n_ops

    while more() or pending:
        free = [w for w in range(n_procs) if w not in pending]
        if more() and free and (len(pending) < overlap or not pending):
            w = rng.choice(free)
            f = rng.choice(["read", "write", "cas"] if cas
                           else ["read", "write"])
            if f == "read":
                v = None
            elif f == "write":
                v = rng.randrange(n_values)
            else:
                v = (rng.randrange(n_values), rng.randrange(n_values))
            h.append((pid[w], "invoke", f, v))
            pending[w] = (f, v)
            if crash_set is not None:
                crash_of[w] = done in crash_set
            done += 1
            continue
        w = rng.choice(list(pending))
        f, v = pending.pop(w)
        if crash_set is not None:
            crashed = crash_of.pop(w)
        else:
            crashed = (crash_p and rng.random() < crash_p
                       and n_crashed < max_crashes)
        if crashed:
            n_crashed += 1
            if rng.random() < 0.5:
                if f == "write":
                    state = v
                elif f == "cas" and state == v[0]:
                    state = v[1]
            h.append((pid[w], "info", f, v if f != "read" else None))
            pid[w] += n_procs
            continue
        if f == "read":
            h.append((pid[w], "ok", f, state))
        elif f == "write":
            state = v
            h.append((pid[w], "ok", f, v))
        elif state == v[0]:
            state = v[1]
            h.append((pid[w], "ok", f, v))
        else:
            h.append((pid[w], "fail", f, v))
            failed += 1
    return h


def history(rng, p, n_ops, exact):
    """One history of the deployment ``p`` (configuration and traffic),
    valid by construction."""
    return register_history(
        rng, n_ops=n_ops, exact=exact, n_procs=p["procs"],
        overlap=p["overlap"], crash_p=p.get("crash_p", 0),
        max_crashes=p.get("max_crashes", 16), crashes=p.get("crashes"),
        n_values=p["values"], init=p["init"])


def stale_read(events, at):
    """Rewrite the ok read nearest fraction ``at`` of the way through to
    a stale value: one that an ok write or cas put in the register
    before the read was invoked, but that a later op (``barrier``,
    completed before the read was invoked) overwrote, with no op that
    could set the value again able to take effect between the barrier
    and the read.  No linearization can return it, so the history is
    invalid by construction, and no static pass can refute it from the
    value alone.  Returns None where no read qualifies."""
    ops = pair(events)
    target = int(at * (len(events) - 1))
    reads = sorted((op for op in ops
                    if op["f"] == "read" and op["type"] == "ok"
                    and op["value"] is not None),
                   key=lambda op: (abs(op["inv"] - target), op["inv"]))

    def sets(op):
        # the value an op leaves in the register, if it took effect
        if op["type"] == "fail" or op["f"] == "read":
            return None
        return op["value"] if op["f"] == "write" else op["value"][1]

    for r in reads:
        before = [op for op in ops if op["type"] == "ok"
                  and op["ret"] < r["inv"] and sets(op) is not None]
        for v in sorted({sets(op) for op in before} - {r["value"]}):
            # the last op that could still set v before the read ends,
            # and the last invoked barrier that left another value
            last_set = max(op["ret"] for op in ops if sets(op) == v
                           and op["inv"] < r["ret"])
            barrier = max((b["inv"] for b in before if sets(b) != v),
                          default=-1)
            if barrier > last_set:
                out = list(events)
                i = r["ret"]
                out[i] = out[i][:-1] + (v,)
                return out
    return None


def corrupt(events, p):
    """``events`` made invalid by a stale read, or None where no read
    qualifies."""
    return stale_read(events, p["stale_at"])


def start(init, control):
    return (init, frozenset([init]) if control else frozenset())


def step(state, op, control):
    """The register after ``op`` (a row of `reference.rows_of`), or None
    where the register does not allow it.  A read of None is a read
    whose value is unknown and always allowed, as in the program's
    model."""
    val, held = state
    f, v = op["f"], op["value"]
    if f == "read":
        ok = v is None or v == val or (control and v in held)
        return state if ok else None
    if f == "write":
        new = v
    elif val == v[0]:
        new = v[1]
    else:
        return None
    return (new, held | {new}) if control else (new, held)


def state_bits(config):
    """The register's value: one of ``values``, or unset."""
    return math.ceil(math.log2(config["values"] + 1))
