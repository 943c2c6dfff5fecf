"""The unordered queue (knossos's unordered-queue): a multiset of
enqueued values, from which a dequeue takes the value it returned.  A
configuration whose checker's ``model`` is the program's
``unordered_queue`` is checked with this file's parts.

`queue_history` follows the RabbitMQ suite (`suites/rabbitmq.py`,
`rabbit_test`): 5 worker threads draw enqueue or dequeue from
`gen/queue` (enqueues of fresh integers), a dequeue of an empty queue
fails, and the test ends with a drain from every worker; it is run as
`models/mutex.py` runs the lock's generator, with these choices:

* each op takes effect at its completion, and the broker hands out its
  oldest message (a dequeue returns the queue's head);
* a crashed worker goes on under a fresh process id (p + concurrency);
  ``crashes`` crashes exactly that many invocations, at slots drawn over
  the ops the history counts, and a crashed op took effect with
  probability 1/2 (a crashed publish was or was not confirmed, a crashed
  get did or did not take its message);
* ``exact`` stops invoking where the ops that may have taken effect (ok
  or info) and the drain's elements make exactly ``n_ops``;
* at most ``overlap`` ops are in flight.

The drain comes already expanded as `checker.basic.QueueLinearizable.
_expand_drains` expands it (`expand_drains` here, on plain events): the
drain that returned k elements becomes k concurrent dequeues on fresh
processes, invoked where the drain was invoked and completed where it
completed; a drain that returned nothing becomes nothing.

`completion_order_replays` is `checker/linearizable.greedy_witness` over
the events: the ok ops in completion order, crashed ops skipped.  A
history it replays is redrawn, so the program's search, not its
shortcut, decides every history.

The reference's state is an unbounded multiset (a `Counter`, kept as a
frozen set of its items so that the search can memoize it).  A crashed
dequeue returned no value, so it is a no-op, as in the program's
`models/__init__._uq_pystep_factory`: for a multiset that is sound,
since an element that is never seen again makes no later op illegal.

`program_chains_the_drain` is the one part that imports the program: a
program whose queue prepass does not reduce these histories is refused
in set-up, at once, since its search of one of them would not end in a
run's time.  It reads nothing that the comparison reads.
"""

from __future__ import annotations

import functools
from collections import Counter

from core.gen import pair

#: the traffic keys this model reads: the share of invocations that
#: enqueue (`gen/queue` draws enqueue and dequeue alike), and the
#: [least, most] elements the final drain returns
TRAFFIC_KEYS = {"enqueue_share", "drain"}


def queue_history(rng, *, n_ops, n_procs, overlap, crashes, enqueue_share,
                  drain, exact=False):
    """Concurrent queue history, valid by construction, drain included
    and not expanded: each worker's drain is ``(p, "invoke", "drain",
    None)`` then ``(p, "ok", "drain", [elements])``, the first worker's
    taking every message the broker holds.  Returns events ``(process,
    type, f, value)`` with worker-local process ids, or None where the
    history does not meet ``exact`` or ``drain`` (the caller redraws)."""
    queue = []              # the broker's messages, oldest first
    h = []
    pending = {}            # worker -> (f, value)
    pid = list(range(n_procs))
    nxt = 0                 # gen/queue's next enqueued value
    # crash slots on the clock that ``n_ops`` counts, over the ops
    # before the drain, so the crashes spread over the whole test
    slots = sorted(rng.sample(range(n_ops - drain[1]), crashes),
                   reverse=True)
    crash_of = {}           # worker -> whether its pending op crashes
    done = 0
    failed = 0

    def clock():
        return done - failed if exact else done

    def more():
        # the drain will return what the broker holds: stop invoking
        # once the ops so far and that drain make n_ops
        return clock() + len(queue) < n_ops

    while more() or pending:
        free = [w for w in range(n_procs) if w not in pending]
        if more() and free and (len(pending) < overlap or not pending):
            w = rng.choice(free)
            if rng.random() < enqueue_share:
                f, v = "enqueue", nxt
                nxt += 1
            else:
                f, v = "dequeue", None
            h.append((pid[w], "invoke", f, v))
            pending[w] = (f, v)
            # the clock rises by one an invocation, so it meets each slot
            crash_of[w] = bool(slots) and slots[-1] <= clock()
            if crash_of[w]:
                slots.pop()
            done += 1
            continue
        w = rng.choice(list(pending))
        f, v = pending.pop(w)
        me = pid[w]
        if crash_of.pop(w):
            if rng.random() < 0.5:
                if f == "enqueue":
                    queue.append(v)
                elif queue:
                    queue.pop(0)
            h.append((me, "info", f, v))
            pid[w] += n_procs
            continue
        if f == "enqueue":
            queue.append(v)
            h.append((me, "ok", f, v))
        elif queue:
            h.append((me, "ok", f, queue.pop(0)))
        else:
            failed += 1
            h.append((me, "fail", f, None))
    if slots or not drain[0] <= len(queue) <= drain[1] or (
            exact and clock() + len(queue) != n_ops):
        return None
    # the final phase: every worker drains at once; the first to reach
    # the broker takes every message, the others find it empty
    order = list(range(n_procs))
    rng.shuffle(order)
    h += [(pid[w], "invoke", "drain", None) for w in order]
    h += [(pid[w], "ok", "drain", queue if i == 0 else [])
          for i, w in enumerate(order)]
    return h


def expand_drains(events):
    """`QueueLinearizable._expand_drains` over plain events, for ok
    drains with a list value (the only drains `queue_history` makes):
    each element a dequeue on its own fresh process, invoked at the
    drain's invocation and completed at its completion."""
    out = []
    fresh = 1 + max((e[0] for e in events if isinstance(e[0], int)),
                    default=0)
    pending = {}            # drain process -> its invocation's place
    for e in events:
        p, typ, f, v = e
        if f != "drain":
            out.append(e)
        elif typ == "invoke":
            pending[p] = len(out)
        else:
            at = pending.pop(p)
            procs = range(fresh, fresh + len(v))
            fresh += len(v)
            out[at:at] = [(q, "invoke", "dequeue", None) for q in procs]
            for k in pending:
                if pending[k] >= at:
                    pending[k] += len(v)
            out += [(q, "ok", "dequeue", x) for q, x in zip(procs, v)]
    return out


def enqueues(events):
    return sum(1 for e in events if e[1] == "invoke" and e[2] == "enqueue")


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


def sized(events, capacity):
    """True iff `QueueLinearizable` sizes ``events``' model at
    ``capacity``: the enqueue count plus 1, rounded up to a power of two
    (so 512-1,023 enqueues for ``unordered_queue(1024)``)."""
    return capacity // 2 <= enqueues(events) <= capacity - 1


#: the smallest history with both of the queue prepass's canonical
#: edges: a crashed enqueue invoked before an :ok op, and a final drain
#: of two concurrent dequeues
PROBE = [(0, "invoke", "enqueue", 0), (1, "invoke", "enqueue", 1),
         (0, "ok", "enqueue", 0), (1, "info", "enqueue", 1),
         (2, "invoke", "enqueue", 2), (2, "ok", "enqueue", 2),
         (5, "invoke", "dequeue", None), (6, "invoke", "dequeue", None),
         (5, "ok", "dequeue", 0), (6, "ok", "dequeue", 2)]


@functools.cache
def program_chains_the_drain():
    """True iff the program's queue prepass (`analyze/constraints.
    analyze_constraints`) puts `PROBE`'s drain in one order, as it does
    for an unordered queue whose capacity cannot bind.  Without that
    reduction, and the early crashed enqueues that come with it, each
    crashed enqueue doubles the frontier for as long as it may still
    take effect, and a drain of k opens C(k, j) configurations at its
    j-th dequeue: on a v5e such a program left the first 1,800-op
    history undecided after 390 s (PERF.md, section 6)."""
    from core import sut  # puts the checkout's program on sys.path
    from jepsen_tpu.analyze.constraints import analyze_constraints
    from jepsen_tpu.history import encode_ops
    from jepsen_tpu.models import unordered_queue

    model = unordered_queue(8)
    seq = encode_ops(sut.history(PROBE), model.f_codes)
    return analyze_constraints(seq, model).stats.get("drain_chain") == 2


def history(rng, p, n_ops, exact):
    """One history of the deployment ``p`` (configuration and traffic),
    valid by construction, its drain expanded, redrawn until
    `QueueLinearizable` sizes its model at the configuration's capacity
    (``init``), its drain returns ``p["drain"]`` elements, and its
    completion order is no linearization: a crashed enqueue took
    effect, and its value was dequeued or drained.  Refuses a program
    that does not chain the drain (`program_chains_the_drain`)."""
    if not program_chains_the_drain():
        raise RuntimeError("the program's queue prepass does not chain a "
                           "final drain: its search of these histories "
                           "would not end in a run's time")
    for _ in range(4096):
        h = queue_history(rng, n_ops=n_ops, exact=exact,
                          n_procs=p["procs"], overlap=p["overlap"],
                          crashes=p["crashes"],
                          enqueue_share=p["enqueue_share"],
                          drain=p["drain"])
        if h is None or not sized(h, p["init"]):
            continue
        h = expand_drains(h)
        if not completion_order_replays(h):
            return h
    raise ValueError("no history of 4096 met the drain, the enqueue count "
                     "and the completion order")


def corrupt(events, p):
    """None: no invalid queue history is a cell's work yet.  A planted
    duplicate delivery, or a dequeue of a value never enqueued, is
    refuted statically by the program's queue prepass
    (`analyze/constraints._analyze_queue`), which reports ``max_depth``
    0 and no deepest partial linearizations, so `shallow_refutations`
    would refuse every run, as with the lock's double grant."""
    return None


def start(init, control):
    """The empty queue; ``init`` is the program's capacity, which an
    unbounded multiset does not need."""
    return frozenset()


def step(state, op, control):
    """The multiset after ``op``, or None where knossos's unordered-queue
    does not allow it: an enqueue adds its value, a dequeue removes the
    value it returned, legal iff present; a dequeue with no value (a
    crashed one) changes nothing.  The queue has no weaker guarantee to
    control with: its cell's control drops the crashed ops."""
    v = op["value"]
    if v is None:
        return state
    c = Counter(dict(state))
    if op["f"] == "enqueue":
        c[v] += 1
    elif c[v]:
        c[v] -= 1
        if not c[v]:
            del c[v]
    else:
        return None
    return frozenset(c.items())


def state_bits(config):
    """One bit per value a history can enqueue, the least any
    implementation of the multiset needs: the program's capacity
    (``init``), at least the enqueue count plus one."""
    return config["init"]
