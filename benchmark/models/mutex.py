"""The lock (knossos's mutex): acquire and release of one lock, with no
owner in the model.  A configuration whose checker's ``model`` is the
program's ``mutex`` is checked with this file's parts.

Copied from the program, and changed only where noted:

* `lock_history` is `jepsen_tpu/synth.sim_mutex_history`, run as
  `models/cas_register.py` runs the register's generator.  Each worker
  alternates acquire and release (`suites/hazelcast.lock_gen`, one
  `gen/each` per process), whatever the last op returned.  Changes: a
  crashed worker goes on under a fresh process id (p + concurrency) and
  starts again at acquire, as a Jepsen worker thread does under
  `gen/each`, instead of being retired; ``crashes`` crashes exactly
  that many invocations, at slots drawn over the ops the history counts,
  as a partition schedule that runs the whole test would; at most
  ``overlap`` ops are in flight, as in
  the register's generator; ``exact`` stops invoking at an exact count
  of ops that may have taken effect (ok or info); the drain at the end
  went, since a blocked acquire times out (tryLock's 5 s) as a fail.
* `completion_order_replays` is `checker/linearizable.greedy_witness`
  over the generator's events: the ok ops in completion order, crashed
  ops skipped.  A history it replays is redrawn, so the program's
  search, not its shortcut, decides every history.

Each op takes effect at its completion: an acquire is ok iff the lock
is free then (else tryLock timed out: fail), a release iff the worker
holds it (else "not lock owner": fail).  A crashed op (info) took
effect with probability 1/2.  A worker that crashed holding the lock
leaves it to its CP session, which expires with probability
`SESSION_EXPIRY_P` at each event, as `sim_mutex_history`'s lease.  The
history stays valid: a crashed acquire that took effect may be left
out, and a crashed release placed at the expiry.
"""

from __future__ import annotations

from core.gen import pair

#: the model reads no traffic keys of its own
TRAFFIC_KEYS: set = set()

#: per event, the chance that a dead holder's lock is free again:
#: `synth.sim_mutex_history`'s ``lease_p``, a guess (the configuration's
#: ``assumed.session_expiry`` says why no source gives it).  While a dead
#: session holds the lock every acquire fails, and failed ops are not
#: encoded, so a longer expiry lengthens the test more than it changes
#: the checked history
SESSION_EXPIRY_P = 0.05


def lock_history(rng, *, n_ops, n_procs, overlap, crashes, exact=False):
    """Concurrent lock history, valid by construction.  Returns events
    ``(process, type, f, None)`` with worker-local process ids."""
    holder = None           # process id holding the lock
    dead = False            # the holder crashed: its session holds it
    h = []
    pending = {}            # worker -> f
    pid = list(range(n_procs))
    nxt = ["acquire"] * n_procs
    # crash slots on the clock that ``n_ops`` counts (ok + info ops when
    # ``exact``, invocations otherwise), so the crashes spread over the
    # whole history and not over its first n_ops invocations
    slots = sorted(rng.sample(range(n_ops), crashes), reverse=True)
    crash_of = {}           # worker -> whether its pending op crashes
    done = 0
    failed = 0

    def clock():
        return done - failed if exact else done

    while clock() < n_ops or pending:
        if dead and rng.random() < SESSION_EXPIRY_P:
            holder, dead = None, False
        free = [w for w in range(n_procs) if w not in pending]
        if clock() < n_ops and free and (len(pending) < overlap
                                         or not pending):
            w = rng.choice(free)
            f = nxt[w]
            nxt[w] = "release" if f == "acquire" else "acquire"
            h.append((pid[w], "invoke", f, None))
            pending[w] = f
            # the clock rises by one an invocation, so it meets each slot
            crash_of[w] = bool(slots) and slots[-1] <= clock()
            if crash_of[w]:
                slots.pop()
            done += 1
            continue
        w = rng.choice(list(pending))
        f = pending.pop(w)
        me = pid[w]
        if crash_of.pop(w):
            if rng.random() < 0.5:
                if f == "acquire" and holder is None:
                    holder = me
                elif f == "release" and holder == me:
                    holder = None
            if holder == me:
                dead = True
            h.append((me, "info", f, None))
            pid[w] += n_procs
            nxt[w] = "acquire"
            continue
        if f == "acquire":
            ok = holder is None
        else:
            ok = holder == me
        if ok:
            holder = me if f == "acquire" else None
        else:
            failed += 1
        h.append((me, "ok" if ok else "fail", f, None))
    return h


def completion_order_replays(events):
    """True iff the ok ops in completion order, crashed ops skipped, are
    a linearization: the program's greedy witness."""
    state = start(None, False)
    for op in sorted((op for op in pair(events) if op["type"] == "ok"),
                     key=lambda op: op["ret"]):
        state = step(state, op, False)
        if state is None:
            return False
    return True


def history(rng, p, n_ops, exact):
    """One history of the deployment ``p`` (configuration and traffic),
    valid by construction, redrawn until its completion order is no
    linearization: a crashed release took effect, or was placed by its
    session's expiry, and another worker's ok acquire followed."""
    for _ in range(256):
        h = lock_history(rng, n_ops=n_ops, exact=exact, n_procs=p["procs"],
                         overlap=p["overlap"], crashes=p["crashes"])
        if not completion_order_replays(h):
            return h
    raise ValueError("every history of 256 replays in completion order")


def corrupt(events, p):
    """None: no invalid lock history is a cell's work yet.  A planted
    double grant (a failed acquire made ok) is refuted by the program's
    static lock prepass, which reports no deepest partial
    linearizations, so `shallow_refutations` would refuse every run."""
    return None


def start(init, control):
    """The free lock."""
    return 0


def step(state, op, control):
    """The lock after ``op``, or None where knossos's mutex does not
    allow it: acquire iff free, release iff held.  The lock has no
    weaker guarantee to control with: its cell's control drops the
    crashed ops."""
    if op["f"] == "acquire":
        return 1 if state == 0 else None
    return 0 if state == 1 else None


def state_bits(config):
    """Held or free."""
    return 1
