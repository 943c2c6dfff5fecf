"""Consistency models — knossos.model equivalents, numeric from the start.

The reference delegates model semantics to knossos.model (used from
jepsen/src/jepsen/checker.clj:15-21 and suites passim): ``register``,
``cas-register``, ``mutex``, ``noop``, each a pure ``step(model, op) ->
model' | inconsistent`` function over immutable state.

Here each model is a :class:`ModelSpec` whose state is a fixed-width tuple
of int32 lanes, with TWO step implementations kept adjacent and
differential-tested (tests/test_models.py):

  * ``pystep`` — plain Python, used by the sequential oracle checker and by
    witness reconstruction;
  * ``jstep``  — a jit-able JAX kernel ``(state[w], f, v1, v2) ->
    (state'[w], legal)``, compiled into the TPU frontier search.

Fixed-width int state is a deliberate design constraint: the TPU engine
packs millions of model states into dense device arrays; anything that
cannot be encoded in a few int32 lanes (unbounded sets/queues) gets a
bounded-capacity encoding or stays host-side (SURVEY.md §7 "hashing model
states on TPU").

Values are pre-encoded to int32 by history.ValueEncoder; ``NIL`` means
"unknown value" (e.g. a read whose invocation hasn't been filled in), which
per knossos.model semantics is always legal and does not change state.
"""

from __future__ import annotations

import bisect
from dataclasses import dataclass, field
from typing import Callable, Optional, Tuple

import jax.numpy as jnp

from ..history import NIL

State = Tuple[int, ...]


@dataclass(frozen=True)
class ModelSpec:
    """A consistency model over fixed-width integer state.

    f_codes maps op :f names to the integer codes both step functions
    dispatch on.  ``init`` is the initial state tuple.
    """

    name: str
    f_codes: dict
    state_width: int
    init: State
    pystep: Callable[[State, int, int, int], Optional[State]]
    # jstep(state: int32[w], f: int32, v1: int32, v2: int32)
    #   -> (state': int32[w], legal: bool)
    jstep: Callable
    doc: str = ""

    def step(self, state: State, f: str, value) -> Optional[State]:
        """Convenience: step by f-name with raw int/tuple value (tests)."""
        code = self.f_codes[f]
        if isinstance(value, (tuple, list)):
            v1, v2 = value
        else:
            v1, v2 = (NIL if value is None else value), NIL
        return self.pystep(state, code, v1, v2)


# ---------------------------------------------------------------------------
# register — a single read/write register (knossos.model/register)
# ---------------------------------------------------------------------------

R_READ, R_WRITE, R_CAS = 0, 1, 2


def _register_pystep(state, f, v1, v2):
    (val,) = state
    if f == R_READ:
        return state if (v1 == NIL or v1 == val) else None
    if f == R_WRITE:
        return (v1,)
    raise ValueError(f"register: bad f code {f}")


def _register_jstep(state, f, v1, v2):
    val = state[0]
    # legality as boolean algebra, not a select of booleans: the same
    # step runs inside the Pallas level kernel, and Mosaic cannot
    # lower a select whose operands are i1 vectors
    legal = (f != R_READ) | (v1 == NIL) | (v1 == val)
    new_val = jnp.where(f == R_WRITE, v1, val)
    return jnp.stack([new_val]), legal


def register(initial: int = 0) -> ModelSpec:
    """A read/write register holding one int (knossos.model/register)."""
    return ModelSpec(
        name="register",
        f_codes={"read": R_READ, "write": R_WRITE},
        state_width=1,
        init=(initial,),
        pystep=_register_pystep,
        jstep=_register_jstep,
        doc="single int register; read legal iff value unknown or equal",
    )


# ---------------------------------------------------------------------------
# cas-register — read/write/compare-and-set (knossos.model/cas-register)
# The workhorse of the reference's suites: etcdemo (jepsen.etcdemo:171-185),
# zookeeper (zookeeper.clj:127-129), etcd, consul, cockroach register, ...
# ---------------------------------------------------------------------------


def _cas_register_pystep(state, f, v1, v2):
    (val,) = state
    if f == R_READ:
        return state if (v1 == NIL or v1 == val) else None
    if f == R_WRITE:
        return (v1,)
    if f == R_CAS:
        return (v2,) if val == v1 else None
    raise ValueError(f"cas-register: bad f code {f}")


def _cas_register_jstep(state, f, v1, v2):
    val = state[0]
    read_legal = (v1 == NIL) | (v1 == val)
    cas_legal = v1 == val
    # boolean algebra, not a select of booleans (see _register_jstep)
    legal = (((f == R_READ) & read_legal) | ((f == R_CAS) & cas_legal)
             | ((f != R_READ) & (f != R_CAS)))
    new_val = jnp.where(f == R_WRITE, v1,
                        jnp.where((f == R_CAS) & cas_legal, v2, val))
    return jnp.stack([new_val]), legal


def cas_register(initial: int = NIL) -> ModelSpec:
    """Read/write/cas register.  ``cas`` takes value [expected, new].

    Default initial state is NIL (an unset register), matching
    knossos.model/cas-register with a nil initial value — a read of NIL is
    then only legal as an unknown-value read.
    """
    return ModelSpec(
        name="cas-register",
        f_codes={"read": R_READ, "write": R_WRITE, "cas": R_CAS},
        state_width=1,
        init=(initial,),
        pystep=_cas_register_pystep,
        jstep=_cas_register_jstep,
        doc="int register with compare-and-set",
    )


# ---------------------------------------------------------------------------
# mutex — a single lock (knossos.model/mutex); checked linearizable by the
# hazelcast suite's lock workload (hazelcast.clj:379-386).
# ---------------------------------------------------------------------------

M_ACQUIRE, M_RELEASE = 0, 1


def _mutex_pystep(state, f, v1, v2):
    (locked,) = state
    if f == M_ACQUIRE:
        return (1,) if not locked else None
    if f == M_RELEASE:
        return (0,) if locked else None
    raise ValueError(f"mutex: bad f code {f}")


def _mutex_jstep(state, f, v1, v2):
    locked = state[0]
    # boolean algebra, not a select of booleans (see _register_jstep)
    legal = (((f == M_ACQUIRE) & (locked == 0))
             | ((f != M_ACQUIRE) & (locked == 1)))
    new_locked = jnp.where(f == M_ACQUIRE, 1, 0)
    return jnp.stack([jnp.where(legal, new_locked, locked)]), legal


def mutex() -> ModelSpec:
    return ModelSpec(
        name="mutex",
        f_codes={"acquire": M_ACQUIRE, "release": M_RELEASE},
        state_width=1,
        init=(0,),
        pystep=_mutex_pystep,
        jstep=_mutex_jstep,
        doc="single lock; acquire legal iff free, release legal iff held",
    )


# ---------------------------------------------------------------------------
# noop — everything is legal (knossos.model/noop; jepsen.tests/noop-test)
# ---------------------------------------------------------------------------


def _noop_pystep(state, f, v1, v2):
    return state


def _noop_jstep(state, f, v1, v2):
    return state, jnp.bool_(True)


class _AnyFCodes(dict):
    """f_codes table accepting every f name (all map to code 0), so the
    noop model really does admit arbitrary histories through encode_ops."""

    def __contains__(self, key):  # noqa: D105
        return True

    def __getitem__(self, key):
        return super().get(key, 0)

    def __missing__(self, key):
        return 0


def noop() -> ModelSpec:
    return ModelSpec(
        name="noop", f_codes=_AnyFCodes(), state_width=1, init=(0,),
        pystep=_noop_pystep, jstep=_noop_jstep,
        doc="accepts every operation",
    )


# ---------------------------------------------------------------------------
# multi-register — k independent registers in one object
# (knossos.model/multi-register); reads/writes take [key value].
# ---------------------------------------------------------------------------


def multi_register(width: int, initial: int = 0) -> ModelSpec:
    """`width` registers; f value lanes are (key, value)."""

    def pystep(state, f, v1, v2):
        key = v1
        if key == NIL or not (0 <= key < width):
            return None
        if f == R_READ:
            return state if (v2 == NIL or v2 == state[key]) else None
        if f == R_WRITE:
            s = list(state)
            s[key] = v2
            return tuple(s)
        raise ValueError(f"multi-register: bad f code {f}")

    def jstep(state, f, v1, v2):
        key = jnp.clip(v1, 0, width - 1)
        in_range = (v1 >= 0) & (v1 < width)
        cur = state[key]
        read_legal = in_range & ((v2 == NIL) | (v2 == cur))
        legal = jnp.where(f == R_READ, read_legal, in_range)
        # illegal steps must leave state unchanged (the engine relies on it)
        new_state = jnp.where((f == R_WRITE) & in_range,
                              state.at[key].set(v2), state)
        return new_state, legal

    return ModelSpec(
        name="multi-register",
        f_codes={"read": R_READ, "write": R_WRITE},
        state_width=width,
        init=(initial,) * width,
        pystep=pystep,
        jstep=jstep,
        doc=f"{width} independent registers addressed by (key, value) ops",
    )


# ---------------------------------------------------------------------------
# unordered-queue — a bounded multiset (knossos.model/unordered-queue);
# enqueue always adds, dequeue of v is legal iff v is present.  The
# reference checks queue workloads by model-reducing histories
# (checker.clj:141-147, disque.clj:305, rabbitmq_test.clj:55); this model
# additionally makes them *searchable* on device: the multiset state is a
# CAPACITY-lane sorted int32 array (SURVEY.md §7's "sorted-array encodings
# with capacity caps"), so equal multisets are bit-identical and the
# engine's exact dedup applies unchanged.
# ---------------------------------------------------------------------------

Q_ENQ, Q_DEQ = 0, 1

#: empty lane marker — sorts after every real value (encoded values are
#: small non-negative ints; 2**31-1 is reserved)
Q_EMPTY = 2**31 - 1


def _uq_pystep_factory(capacity: int):
    def pystep(state, f, v1, v2):
        if v1 == NIL:
            # an op with an unknown value (crashed invoke) constrains
            # nothing and changes nothing, matching the register models'
            # NIL convention
            return state
        # the lanes are sorted (empty lanes last): bisect, not a scan
        # or a sort of the whole capacity
        if f == Q_ENQ:
            if state[capacity - 1] != Q_EMPTY:
                return None  # over capacity: size the model to the history
            i = bisect.bisect_right(state, v1)
            return state[:i] + (v1,) + state[i:capacity - 1]
        if f == Q_DEQ:
            i = bisect.bisect_left(state, v1)
            if i == capacity or state[i] != v1:
                return None
            return state[:i] + state[i + 1:] + (Q_EMPTY,)
        raise ValueError(f"unordered-queue: bad f code {f}")

    return pystep


def _uq_jstep_factory(capacity: int):
    def jstep(state, f, v1, v2):
        idx = jnp.arange(capacity)
        nil = v1 == NIL

        # enqueue: sorted insert at position cnt = |{i: state[i] <= v}|
        room = state[capacity - 1] == Q_EMPTY
        cnt = (state <= v1).sum()
        prev = jnp.roll(state, 1)  # prev[0] unused (idx 0 is < or == cnt)
        enq = jnp.where(idx < cnt, state,
                        jnp.where(idx == cnt, v1, prev))

        # dequeue: remove the first lane equal to v (duplicates keep one)
        eq = state == v1
        present = eq.any()
        m = jnp.argmax(eq)
        nxt = jnp.concatenate(
            [state[1:], jnp.full((1,), Q_EMPTY, state.dtype)])
        deq = jnp.where(idx < m, state, nxt)

        is_enq = f == Q_ENQ
        legal = jnp.where(nil, True, jnp.where(is_enq, room, present))
        new_state = jnp.where(
            nil | ~legal, state,
            jnp.where(is_enq, enq, deq))
        return new_state, legal

    return jstep


def unordered_queue(capacity: int = 16) -> ModelSpec:
    """Bounded unordered queue (multiset).  ``capacity`` must be at least
    the largest queue length any linearization of the history can reach
    (#enqueues is always a safe bound); an enqueue past capacity is
    treated as illegal, which would wrongly fail an over-capacity legal
    history — size generously."""
    return ModelSpec(
        name=f"unordered-queue-{capacity}",
        f_codes={"enqueue": Q_ENQ, "dequeue": Q_DEQ},
        state_width=capacity,
        init=(Q_EMPTY,) * capacity,
        pystep=_uq_pystep_factory(capacity),
        jstep=_uq_jstep_factory(capacity),
        doc="bounded multiset; dequeue legal iff the value is present",
    )


# ---------------------------------------------------------------------------
# fifo-queue — knossos.model/fifo-queue: dequeue must return the OLDEST
# element.  State is a left-aligned bounded ring (front at lane 0, empty
# lanes = Q_EMPTY): enqueue appends at the fill count, dequeue matches
# lane 0 and shifts left.  Left-alignment keeps the encoding canonical,
# so the engine's exact dedup applies unchanged.
# ---------------------------------------------------------------------------


def _fq_pystep_factory(capacity: int):
    def pystep(state, f, v1, v2):
        if v1 == NIL:
            return state
        if f == Q_ENQ:
            if state[capacity - 1] != Q_EMPTY:
                return None  # over capacity: size the model generously
            cnt = sum(1 for x in state if x != Q_EMPTY)
            return state[:cnt] + (v1,) + state[cnt + 1:]
        if f == Q_DEQ:
            if state[0] == Q_EMPTY or state[0] != v1:
                return None
            return state[1:] + (Q_EMPTY,)
        raise ValueError(f"fifo-queue: bad f code {f}")

    return pystep


def _fq_jstep_factory(capacity: int):
    def jstep(state, f, v1, v2):
        idx = jnp.arange(capacity)
        nil = v1 == NIL

        room = state[capacity - 1] == Q_EMPTY
        cnt = (state != Q_EMPTY).sum()
        enq = jnp.where(idx == cnt, v1, state)

        head_ok = (state[0] != Q_EMPTY) & (state[0] == v1)
        deq = jnp.concatenate(
            [state[1:], jnp.full((1,), Q_EMPTY, state.dtype)])

        is_enq = f == Q_ENQ
        legal = jnp.where(nil, True, jnp.where(is_enq, room, head_ok))
        new_state = jnp.where(
            nil | ~legal, state,
            jnp.where(is_enq, enq, deq))
        return new_state, legal

    return jstep


def fifo_queue(capacity: int = 16) -> ModelSpec:
    """Bounded FIFO queue; see `unordered_queue` for the capacity
    contract (an enqueue past capacity is treated as illegal)."""
    return ModelSpec(
        name=f"fifo-queue-{capacity}",
        f_codes={"enqueue": Q_ENQ, "dequeue": Q_DEQ},
        state_width=capacity,
        init=(Q_EMPTY,) * capacity,
        pystep=_fq_pystep_factory(capacity),
        jstep=_fq_jstep_factory(capacity),
        doc="bounded FIFO; dequeue legal iff it returns the oldest",
    )
