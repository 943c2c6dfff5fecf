"""Synthetic history generation — benchmark + self-test workloads.

Simulates N logically single-threaded processes (the reference's worker
model, core.clj:329-407) against an in-memory register/mutex, emitting a
history that is valid by construction: each op takes effect atomically at
its completion event, which is a legal linearization point.  Knobs:

  * ``overlap``  — target number of simultaneously pending ops; drives the
    real-time-order ambiguity the checker must search through (the
    generator analog of `delay-til` racing, generator.clj:134-157).
  * ``crash_p``  — probability a pending op crashes (:info) instead of
    completing; crashed effects are applied with probability .5, matching
    the "maybe happened" semantics the checker must cope with
    (core.clj:387-397).
  * ``corrupt_at`` — fraction; rewrites one ok read near that point of the
    history to a bogus value, which (almost always) makes the history
    non-linearizable so the checker must sweep the full state space.

:func:`baseline_history` and :func:`baseline_batch` rebuild the seeded
histories of BASELINE.md's configurations (``chip_smoke.py`` and
``tools/fusioncount.py`` run them).
"""

from __future__ import annotations

import random
from dataclasses import replace

from .history import Op, fail_op, info_op, invoke_op, ok_op


def register_history(rng: random.Random, *, n_ops: int, n_procs: int,
                     overlap: int = 4, crash_p: float = 0.0,
                     max_crashes: int = 16, n_values: int = 5,
                     cas: bool = True,
                     unique_writes: bool = False,
                     quiesce_every: int | None = None) -> list[Op]:
    """Concurrent CAS-register history, valid by construction.

    ``unique_writes`` draws every write value from a fresh counter
    (starting at 1, so it never collides with a register's initial 0)
    instead of ``[0, n_values)`` — the unique-writes register class the
    per-value block decomposition (decompose/partition.py) is exact
    on.

    ``quiesce_every`` drains every pending op after each that many
    invocations before invoking more — a *bursty* workload with
    guaranteed quiescent points every ~that many ops, the shape the
    quiescence cutter (and the streaming checker's online cuts) feeds
    on: segments of roughly that size at the full ``overlap`` width."""
    state = None
    h: list[Op] = []
    pending: dict[int, tuple] = {}
    n_crashed = 0
    done = 0
    next_v = 1  # unique_writes counter
    crashed_procs: set[int] = set()
    while done < n_ops or pending:
        free = [p for p in range(n_procs)
                if p not in pending and p not in crashed_procs]
        want_invoke = (done < n_ops and free
                       and (len(pending) < overlap or not pending)
                       and not (quiesce_every and done
                                and done % quiesce_every == 0
                                and pending))
        if want_invoke:
            p = rng.choice(free)
            fs = ["read", "write"] + (["cas"] if cas else [])
            f = rng.choice(fs)
            if f == "read":
                v = None
            elif f == "write":
                if unique_writes:
                    v = next_v
                    next_v += 1
                else:
                    v = rng.randrange(n_values)
            else:
                v = (rng.randrange(n_values), rng.randrange(n_values))
            h.append(invoke_op(p, f, v))
            pending[p] = (f, v)
            done += 1
            continue
        if not pending:
            break
        p = rng.choice(list(pending))
        f, v = pending.pop(p)
        if crash_p and rng.random() < crash_p and n_crashed < max_crashes:
            n_crashed += 1
            crashed_procs.add(p)  # a crashed process id is retired
            if rng.random() < 0.5:
                if f == "write":
                    state = v
                elif f == "cas" and state == v[0]:
                    state = v[1]
            h.append(info_op(p, f, v if f != "read" else None))
            continue
        if f == "read":
            h.append(ok_op(p, f, state))
        elif f == "write":
            state = v
            h.append(ok_op(p, f, v))
        else:
            if state == v[0]:
                state = v[1]
                h.append(ok_op(p, f, v))
            else:
                h.append(fail_op(p, f, v))
    return h


def swap_read_values(rng: random.Random, h: list[Op], *,
                     min_gap: int | None = None) -> list[Op]:
    """Swap the values of two ok reads of DIFFERENT values at least
    ``min_gap`` events apart (default: a quarter of the history).

    On a unique-writes history this forces block-order conflicts — a
    value current in two separated stretches would need two writes —
    which is the invalidity mode the per-value block decomposition's
    cross-block acyclicity test exists to catch.  (`corrupt_read`'s
    never-written value is rejected before any order reasoning.)"""
    idx = [i for i, op in enumerate(h)
           if op.type == "ok" and op.f == "read" and op.value is not None]
    if len(idx) < 2:
        return h
    gap = len(h) // 4 if min_gap is None else min_gap
    for _ in range(200):
        i, j = sorted(rng.sample(idx, 2))
        if j - i >= gap and h[i].value != h[j].value:
            h = list(h)
            h[i], h[j] = (replace(h[i], value=h[j].value),
                          replace(h[j], value=h[i].value))
            return h
    return h


def corrupt_read(rng: random.Random, h: list[Op], *,
                 at: float = 1.0) -> list[Op]:
    """Rewrite the ok read nearest fraction ``at`` of the way through to a
    value nothing wrote; the result is (almost certainly) invalid."""
    h = list(h)
    idx = [i for i, op in enumerate(h)
           if op.type == "ok" and op.f == "read" and op.value is not None]
    if not idx:
        return h
    target = int(at * (len(h) - 1))
    i = min(idx, key=lambda j: abs(j - target))
    h[i] = replace(h[i], value=(h[i].value or 0) + 1_000_003)
    return h


# ---------------------------------------------------------------------------
# Differential-test simulators (shared by tests/test_linearizable.py and
# tools/fuzz.py — one canonical copy, so a simulator fix lands once)
# ---------------------------------------------------------------------------


def sim_register_history(rng: random.Random, n_procs: int = 4,
                         n_ops: int = 40, *, crash_p: float = 0.0,
                         cas: bool = True,
                         max_crashes: int = 8) -> list[Op]:
    """Simulate processes against a real register; ops linearize at
    completion, so the emitted history is valid."""
    state = None  # register starts unset
    h: list[Op] = []
    pending: dict = {}  # process -> (f, value)
    n_crashed = 0
    done = 0
    while done < n_ops or pending:
        p = rng.randrange(n_procs)
        if p in pending:
            f, v = pending.pop(p)
            if crash_p and rng.random() < crash_p and \
                    n_crashed < max_crashes:
                n_crashed += 1
                # crashed: op takes effect iff coin flip says so
                if rng.random() < 0.5:
                    if f == "write":
                        state = v
                    elif f == "cas" and state == v[0]:
                        state = v[1]
                h.append(info_op(p, f, v if f != "read" else None))
                continue
            if f == "read":
                h.append(ok_op(p, f, state))
            elif f == "write":
                state = v
                h.append(ok_op(p, f, v))
            else:  # cas
                if state == v[0]:
                    state = v[1]
                    h.append(ok_op(p, f, v))
                else:
                    h.append(fail_op(p, f, v))
        elif done < n_ops:
            fs = ["read", "write"] + (["cas"] if cas else [])
            f = rng.choice(fs)
            if f == "read":
                v = None
            elif f == "write":
                v = rng.randrange(5)
            else:
                v = (rng.randrange(5), rng.randrange(5))
            h.append(invoke_op(p, f, v))
            pending[p] = (f, v)
            done += 1
    return h


def sim_mutex_history(rng: random.Random, n_ops: int = 40,
                      n_procs: int = 4, *,
                      crash_p: float = 0.0,
                      max_crashes: int = 48,
                      lease_p: float = 0.05) -> list[Op]:
    """Alternating acquire/release per process against a real lock.

    Always terminates: after the op budget is spent, completable pending
    ops are drained (the holder releases out-of-budget if needed) and
    anything still stuck — e.g. acquires blocked behind a crashed holder
    — becomes a crashed :info op, exactly what the harness records for
    ops whose fate is unknown (core.clj:387-397).

    A holder that crashes still holding the lock would deadlock every
    other process; like a real lock service, the lock's lease then
    expires (probability ``lease_p`` per scheduling step).  The emitted
    history stays valid: a crashed acquire is a :info op the checker may
    linearize or skip, and the skip branch always explains later
    acquires.  ``max_crashes`` caps :info ops so the engine's crash mask
    stays within its width."""
    holder = None
    holder_crashed = False
    h: list[Op] = []
    pending: dict = {}  # process -> f
    wants: dict = {}
    crashed: set = set()
    done = 0
    while done < n_ops:
        if len(crashed) >= n_procs:
            break  # everyone crashed; the history just ends short
        if holder_crashed and rng.random() < lease_p:
            holder = None  # lease expiry frees a dead holder's lock
            holder_crashed = False
        p = rng.randrange(n_procs)
        if p in crashed:
            continue
        if p in pending:
            f = pending[p]
            if crash_p and len(crashed) < max_crashes \
                    and rng.random() < crash_p:
                # coin flip: did the op take effect before the crash?
                if rng.random() < 0.5:
                    if f == "acquire" and holder is None:
                        holder = p
                    elif f == "release" and holder == p:
                        holder = None
                del pending[p]
                crashed.add(p)
                # a dead process still holding the lock (crashed acquire
                # that took effect, or crashed release that did NOT) must
                # be lease-expirable, or the simulation deadlocks; a
                # crash by a NON-holder must not touch the flag
                if holder == p:
                    holder_crashed = True
                h.append(info_op(p, f, None))
                continue
            if f == "acquire" and holder is None:
                holder = p
                holder_crashed = False
                del pending[p]
                h.append(ok_op(p, f, None))
            elif f == "release":
                del pending[p]
                if holder == p:
                    holder = None
                    holder_crashed = False
                    h.append(ok_op(p, f, None))
                else:
                    h.append(fail_op(p, f, None))
            continue
        f = "release" if wants.get(p) else "acquire"
        wants[p] = not wants.get(p)
        h.append(invoke_op(p, f, None))
        pending[p] = f
        done += 1

    # drain: free the lock if its holder is still schedulable, complete
    # what completes, and crash the rest
    if holder is not None and holder not in crashed \
            and holder not in pending:
        h.append(invoke_op(holder, "release", None))
        h.append(ok_op(holder, "release", None))
        holder = None
    for p, f in sorted(pending.items()):
        if f == "acquire" and holder is None:
            holder = p
            h.append(ok_op(p, f, None))
        elif f == "release":
            if holder == p:
                holder = None
                h.append(ok_op(p, f, None))
            else:
                h.append(fail_op(p, f, None))
        else:
            h.append(info_op(p, f, None))
    return h


def flip_read(rng: random.Random, h: list[Op]) -> list[Op]:
    """Flip one ok read's value; usually makes the history invalid."""
    h = list(h)
    idx = [i for i, op in enumerate(h)
           if op.type == "ok" and op.f == "read" and op.value is not None]
    if not idx:
        return h
    i = rng.choice(idx)
    h[i] = replace(h[i], value=(h[i].value or 0) + 7)
    return h


def mutate(rng: random.Random, h: list[Op]) -> list[Op]:
    """One random mutation: flip a read value, swap two completions, or
    duplicate a completion."""
    h = list(h)
    kind = rng.randrange(3)
    if kind == 0:
        return flip_read(rng, h)
    idx = [i for i, op in enumerate(h) if op.type == "ok"]
    if kind == 1 and len(idx) >= 2:
        i, j = rng.sample(idx, 2)
        h[i], h[j] = h[j], h[i]
    elif idx:
        h.insert(rng.choice(idx), h[rng.choice(idx)])
    return h


def sim_queue_history(rng: random.Random, n_ops: int = 40,
                      n_procs: int = 4, *,
                      crash_p: float = 0.0,
                      fifo: bool = False) -> list[Op]:
    """Enqueue/dequeue against a real in-memory multiset, valid by
    construction (ops take effect at completion; dequeues return an
    arbitrary present element — or the oldest when ``fifo``, making the
    history fifo-queue-valid).  Enqueued values are unique integers so
    corruptions are unambiguous.  Crashed enqueues apply their effect
    with probability .5 — but a crashed enqueue's value may then be
    dequeued later, which is still valid (the checker must consider the
    crashed op as possibly-linearized, core.clj:387-397)."""
    contents: list[int] = []
    h: list[Op] = []
    pending: dict = {}  # process -> (f, value-or-None)
    crashed: set = set()
    next_v = 0
    done = 0
    while done < n_ops or pending:
        live = [p for p in range(n_procs) if p not in crashed]
        if not live:
            break
        p = rng.choice(live)
        if p in pending:
            f, v = pending.pop(p)
            if crash_p and rng.random() < crash_p:
                if f == "enqueue" and rng.random() < 0.5:
                    contents.append(v)
                crashed.add(p)
                h.append(info_op(p, f, v))
                continue
            if f == "enqueue":
                contents.append(v)
                h.append(ok_op(p, f, v))
            else:  # dequeue completes only if something is present
                if contents:
                    got = contents.pop(
                        0 if fifo else rng.randrange(len(contents)))
                    h.append(ok_op(p, f, got))
                else:
                    h.append(fail_op(p, f, None))
        elif done < n_ops:
            if rng.random() < 0.55 or not contents:
                f, v = "enqueue", next_v
                next_v += 1
            else:
                f, v = "dequeue", None
            h.append(invoke_op(p, f, v))
            pending[p] = (f, v)
            done += 1
    return h


def swap_dequeues(rng: random.Random, h: list[Op]) -> list[Op]:
    """Swap two ok dequeues' values — reorders the service order, which a
    FIFO model must reject unless the two were concurrent."""
    idx = [i for i, op in enumerate(h)
           if op.type == "ok" and op.f == "dequeue"]
    if len(idx) < 2:
        return h
    i, j = rng.sample(idx, 2)
    h = list(h)
    h[i], h[j] = (replace(h[i], value=h[j].value),
                  replace(h[j], value=h[i].value))
    return h


def corrupt_dequeue(rng: random.Random, h: list[Op]) -> list[Op]:
    """Rewrite one ok dequeue's value to one never enqueued — a
    from-thin-air element no linearization can explain."""
    idx = [i for i, op in enumerate(h)
           if op.type == "ok" and op.f == "dequeue"]
    if not idx:
        return h
    i = rng.choice(idx)
    h = list(h)
    h[i] = replace(h[i], value=999_983)
    return h


# ---------------------------------------------------------------------------
# BASELINE.md's seeded histories
# ---------------------------------------------------------------------------

#: name -> (encoded op count, processes) of BASELINE.md's single
#: histories; the seed is the name, so every process rebuilds the
#: identical history
BASELINE_HISTORIES = {
    "1k": (1_000, 32),
    "mutex2k": (2_000, 16),
    "10k": (10_000, 32),
    # every write a distinct value, overlap never drained: the class
    # the per-value block decomposition is exact on
    "10kuniq": (10_000, 32),
    # 64 processes at overlap 32: wide pruned levels
    "10k64": (10_000, 64),
}

#: BASELINE config 3's per-key history: (invoked ops, processes)
BASELINE_BATCH_KEY = (128, 8)


def _exact_encoded(gen, encode, target: int, *, lo_guess: int):
    """Scan the generator's nominal invoke count until the ENCODED row
    count equals ``target`` (encode_ops drops :fail ops, so the invoke
    count overshoots it).  ``gen(n)`` -> event history; ``encode(h)``
    -> OpSeq.  The scan order is fixed, so the result is deterministic;
    when no candidate hits ``target`` exactly the nearest miss is
    returned."""
    n = lo_guess
    best = None  # (abs gap, seq)
    seen: set[int] = set()
    for _ in range(200):
        seq = encode(gen(n))
        got = len(seq)
        if got == target:
            return seq
        if best is None or abs(got - target) < best[0]:
            best = (abs(got - target), seq)
        seen.add(n)
        # proportional step toward the target, at least +-1
        step = int(round(n * (target - got) / max(1, got)))
        n += step if step else (1 if got < target else -1)
        n = max(target // 2, n)
        if n in seen:
            # walk to the nearest unvisited candidate; give up once the
            # local neighborhood is exhausted
            for d in range(1, 50):
                if n + d not in seen:
                    n += d
                    break
                if n - d > target // 2 and n - d not in seen:
                    n -= d
                    break
            else:
                break
    return best[1]


def baseline_history(name: str):
    """``(seq, model)``: BASELINE.md's seeded history ``name`` (a key
    of :data:`BASELINE_HISTORIES`), encoded to its nominal op count
    (``mutex2k`` and ``10k64`` land within a few ops of it).  The
    ``10k64`` scan takes tens of seconds on a CPU core."""
    from .history import encode_ops
    from .models import cas_register, mutex, register

    n_ops, n_procs = BASELINE_HISTORIES[name]
    if name == "10kuniq":
        # no crashes and no cas, so the encoded count equals the invoke
        # count; swapping two distant reads' values makes the history
        # (almost surely) invalid through the forced block order
        model = register(0)

        def gen(n):
            rng = random.Random(f"bench-{name}")
            h = register_history(rng, n_ops=n, n_procs=n_procs,
                                 overlap=8, crash_p=0.0, cas=False,
                                 unique_writes=True)
            return swap_read_values(rng, h)

        lo_guess = n_ops
    elif name == "mutex2k":
        # BASELINE config 4: a lock with crashed (:info) ops.  The
        # appended chain of (#info + 2) ok acquires makes the history
        # invalid however the :info releases are placed, so the check
        # must sweep rather than stop at a greedy witness
        model = mutex()

        def gen(n):
            rng = random.Random(f"bench-{name}")
            h = sim_mutex_history(rng, n_ops=n, n_procs=n_procs,
                                  crash_p=0.01, max_crashes=12)
            n_info = sum(1 for op in h if op.type == "info")
            for i in range(n_info + 2):
                p = n_procs + i
                h = h + [invoke_op(p, "acquire", None),
                         ok_op(p, "acquire", None)]
            return h

        lo_guess = n_ops
    else:
        model = cas_register()
        overlap = 32 if name == "10k64" else 8

        def gen(n):
            rng = random.Random(f"bench-{name}")
            h = register_history(rng, n_ops=n, n_procs=n_procs,
                                 overlap=overlap, crash_p=0.002,
                                 max_crashes=8, n_values=4)
            return corrupt_read(rng, h, at=0.98)

        lo_guess = int(n_ops * 1.35)
    seq = _exact_encoded(gen, lambda h: encode_ops(h, model.f_codes),
                         n_ops, lo_guess=lo_guess)
    return seq, model


def baseline_batch_key(k: int):
    """``(seq, model)``: BASELINE config 3's key ``k``, a 128-op
    8-process CAS-register history; every fourth key (``k % 4 == 0``)
    has a corrupted read."""
    from .history import encode_ops
    from .models import cas_register

    n_ops, n_procs = BASELINE_BATCH_KEY
    model = cas_register()
    rng = random.Random(f"bench-batch-{k}")
    h = register_history(rng, n_ops=n_ops, n_procs=n_procs, overlap=4,
                         crash_p=0.01, max_crashes=2, n_values=4)
    if k % 4 == 0:
        h = corrupt_read(rng, h, at=0.85)
    return encode_ops(h, model.f_codes), model


def baseline_batch(n_keys: int = 256):
    """``(seqs, model)``: the first ``n_keys`` keys of BASELINE config
    3 (:func:`baseline_batch_key`)."""
    from .models import cas_register

    return [baseline_batch_key(k)[0] for k in range(n_keys)], \
        cas_register()
