"""Independent-keys lifting — shard one workload across many keys.

Reference: jepsen/src/jepsen/independent.clj.  Expensive checks (above all
linearizability) only scale to short histories; the reference lifts a
single-register workload to a keyed map of registers: generators wrap
values in ``[k v]`` tuples (tuple at independent.clj:21, generators at
31-220) and the checker splits the history into per-key subhistories
checked in bounded parallel (independent.clj:247-298).

Here the same lift gains a device fast path: when the lifted checker is
the TPU linearizability engine, all per-key subhistories are encoded and
checked in ONE batched device call (`search_batch`, vmap over the key
axis) — the reference's `bounded-pmap` becomes a batch dimension, which is
exactly the parallelism BASELINE.md config #3 measures.
"""

from __future__ import annotations

from typing import Any, Callable, Iterable

from . import obs
from .checker.core import Checker, check_safe, merge_valid
from .history import Op
from .util import bounded_pmap


class KV:
    """A kv tuple distinguishable from plain values (independent.clj:21-29).

    Plain tuples can be legitimate op values (e.g. cas pairs), so keyed
    values get their own type, like the reference's MapEntry.
    """

    __slots__ = ("key", "value")

    def __init__(self, key, value):
        self.key = key
        self.value = value

    def __iter__(self):
        yield self.key
        yield self.value

    def __eq__(self, other):
        return (isinstance(other, KV) and other.key == self.key
                and other.value == self.value)

    def __hash__(self):
        return hash((KV, self.key, self.value))

    def __repr__(self):
        return f"[{self.key!r} {self.value!r}]"


def tuple_(k, v) -> KV:
    return KV(k, v)


def is_tuple(v) -> bool:
    return isinstance(v, KV)


def history_keys(history: Iterable[Op]) -> list:
    """Distinct keys appearing in tuple values (independent.clj:222-232)."""
    seen: dict = {}
    for op in history:
        if is_tuple(op.value):
            seen.setdefault(op.value.key, None)
    return list(seen)


def subhistory(k, history: Iterable[Op]) -> list[Op]:
    """All ops without a differing key, tuples unwrapped
    (independent.clj:234-245).  Un-keyed ops (nemesis, info logging) are
    kept so every subhistory sees them."""
    from dataclasses import replace

    out = []
    for op in history:
        if not is_tuple(op.value):
            out.append(op)
        elif op.value.key == k:
            out.append(replace(op, value=op.value.value))
    return out


def _unwrap(op: Op) -> Op:
    """`op` with its KV value replaced by the KV's value."""
    if type(op) is Op:
        return Op(op.process, op.type, op.f, op.value.value, op.time,
                  op.index, op.error)
    from dataclasses import replace

    return replace(op, value=op.value.value)


def subhistories(history: Iterable[Op]) -> dict:
    """Every key's subhistory in one pass: ``{k: subhistory(k, history)
    for k in history_keys(history)}``, keys in first-appearance order."""
    return _split(history)[0]


def _split(history: Iterable[Op]) -> tuple[dict, int]:
    """`subhistories`, and the number of un-keyed ops.

    A key seen for the first time starts from the un-keyed ops before it;
    an un-keyed op is appended, the same object, to every key seen so
    far.  O(events + unkeyed x keys), where the per-key rescan is
    O(keys x events)."""
    unkeyed: list[Op] = []
    subs: dict = {}
    for op in history:
        v = op.value
        if isinstance(v, KV):
            sub = subs.get(v.key)
            if sub is None:
                sub = subs[v.key] = unkeyed.copy()
            sub.append(_unwrap(op))
        else:
            unkeyed.append(op)
            for sub in subs.values():
                sub.append(op)
    return subs, len(unkeyed)


class SequentialGenerator:
    """Work through keys one at a time (independent.clj:31-64): build
    fgen(k1), emit its ops (values wrapped as [k1 v]) until exhausted,
    then move to k2, ..."""

    def __init__(self, keys: Iterable, fgen: Callable):
        import threading

        from .generator import Generator  # noqa: F401 (protocol home)

        self._keys = iter(keys)
        self._fgen = fgen
        self._lock = threading.Lock()
        self._cur = None
        self._done = False
        self._advance()

    def _advance(self):
        k = next(self._keys, _SENTINEL := object())
        if k is _SENTINEL:
            self._cur = None
            self._done = True
        else:
            self._cur = (k, self._fgen(k))

    def op(self, test, process):
        from .generator import gen_op

        while True:
            with self._lock:
                if self._done:
                    return None
                k, g = self._cur
            op = gen_op(g, test, process)
            if op is not None:
                op = dict(op)
                op["value"] = KV(k, op.get("value"))
                return op
            with self._lock:
                if not self._done and self._cur is not None \
                        and self._cur[0] == k:
                    self._advance()


def sequential_generator(keys, fgen) -> SequentialGenerator:
    return SequentialGenerator(keys, fgen)


class ConcurrentGenerator:
    """n threads per key, groups working concurrently on distinct keys
    (independent.clj:66-220).  Worker threads are split into contiguous
    groups of n; each group runs fgen(k) for its current key with
    *threads* rebound to the group (so barriers inside sub-generators
    synchronize per-key), and pulls the next key when exhausted.  The
    nemesis never enters sub-generators."""

    def __init__(self, n: int, keys: Iterable, fgen: Callable):
        import threading

        assert n > 0 and isinstance(n, int)
        self.n = n
        self._keys = iter(keys)
        self._fgen = fgen
        self._lock = threading.Lock()
        self._active: list | None = None  # per-group [k, gen] or None
        self._group_threads: list | None = None

    def _init_state(self, test):
        from .generator import current_threads

        threads = [t for t in current_threads() if isinstance(t, int)]
        tc = len(threads)
        assert sorted(threads) == list(range(tc)), \
            "concurrent-generator expects integer threads 0..n-1"
        assert test["concurrency"] == tc, (
            f"expected test concurrency ({test['concurrency']}) to equal "
            f"the number of integer threads ({tc})")
        group_count = tc // self.n
        assert self.n <= tc, (
            f"with {tc} worker threads, cannot run a key with {self.n} "
            f"threads concurrently; raise concurrency to at least {self.n}")
        assert tc == self.n * group_count, (
            f"{tc} threads cannot be split into groups of {self.n}; "
            f"make concurrency a multiple of {self.n}")
        self._active = []
        for _ in range(group_count):
            k = next(self._keys, None)
            self._active.append(None if k is None else [k, self._fgen(k)])
        self._group_threads = [threads[i * self.n:(i + 1) * self.n]
                               for i in range(group_count)]

    def op(self, test, process):
        from .generator import gen_op, process_to_thread, with_threads

        with self._lock:
            if self._active is None:
                self._init_state(test)
        thread = process_to_thread(test, process)
        assert isinstance(thread, int), (
            f"only numeric worker threads may draw from "
            f"concurrent-generator, got {thread!r}")
        group = thread // self.n
        while True:
            with self._lock:
                pair = self._active[group]
            if pair is None:
                return None
            k, g = pair
            with with_threads(self._group_threads[group]):
                op = gen_op(g, test, process)
            if op is not None:
                op = dict(op)
                op["value"] = KV(k, op.get("value"))
                return op
            with self._lock:
                if self._active[group] is pair:
                    nk = next(self._keys, None)
                    self._active[group] = \
                        None if nk is None else [nk, self._fgen(nk)]


def concurrent_generator(n: int, keys, fgen) -> ConcurrentGenerator:
    return ConcurrentGenerator(n, keys, fgen)


class IndependentChecker(Checker):
    """Lift a checker over values to a checker over [k v] histories
    (independent.clj:247-298): valid iff valid for every key's
    subhistory."""

    def __init__(self, checker: Checker, *, batch_device: bool = True):
        self.checker = checker
        self.batch_device = batch_device

    def _device_batch(self, test, subhistories: dict):
        """One vmap'd device call for all keys (TPU fast path)."""
        from .checker.linearizable import Linearizable, search_batch
        from .history import encode_ops

        chk: Linearizable = self.checker
        model = chk.model or test.get("model")
        keys = list(subhistories)
        with obs.span("independent.encode", keys=len(keys)):
            seqs = [encode_ops(subhistories[k], model.f_codes)
                    for k in keys]

        def one(i, why, **args):
            with obs.span("independent.key", key=keys[i],
                          rows=len(seqs[i]), why=why, **args):
                return check_safe(chk, test, subhistories[keys[i]])

        def recheck(i, r):
            """Key ``i``'s check, the batch having found it invalid
            (``r``).  Where the sub-checker's own check would search the
            device and confirm an invalid verdict on the host, the host
            confirms ``r`` instead, at the batch's depth and window; a
            key it does not confirm gets the sub-checker's own check."""
            if not chk.confirms_batch_verdict(r):
                return one(i, "recheck")
            with obs.span("independent.key", key=keys[i],
                          rows=len(seqs[i]), why="confirm") as sp:
                try:
                    out, why = chk.confirm_batch_verdict(
                        test, subhistories[keys[i]], seqs[i], r)
                except Exception as e:  # noqa: BLE001 — the own check
                    # below meets it again, and check_safe reports it
                    out, why = None, type(e).__name__
                sp.set(confirmed=out is not None)
            return out if out is not None else \
                one(i, "recheck", fallback=why)

        # tiny histories aren't worth a device roundtrip; knossos-style
        # host checks for them, batch the rest
        small = [i for i, s in enumerate(seqs)
                 if len(s) <= chk.host_threshold]
        results: dict = {}
        for i in small:
            results[keys[i]] = one(i, "small")
        big = [i for i in range(len(keys)) if i not in set(small)]
        if big:
            batch = search_batch([seqs[i] for i in big], model,
                                 budget=chk.budget)
            for i, r in zip(big, batch):
                if r["valid"] is False:
                    results[keys[i]] = recheck(i, r)
                else:
                    results[keys[i]] = r
        return results

    def check(self, test, history, opts=None):
        # the spans of this call, the keys' checkers' included, share
        # one check id; no span encloses the call itself
        with obs.check_scope():
            return self._check(test, history, opts)

    def _check(self, test, history, opts):
        from .checker.linearizable import Linearizable

        with obs.span("independent.split", events=len(history)) as sp:
            subs, unkeyed = _split(history)
            ks = list(subs)
            sp.set(keys=len(ks), unkeyed=unkeyed)
        if self.batch_device and isinstance(self.checker, Linearizable):
            results = self._device_batch(test, subs)
        else:
            vals = bounded_pmap(
                lambda k: check_safe(self.checker, test, subs[k],
                                     (opts or {}) | {"history_key": k}),
                ks)
            results = dict(zip(ks, vals))
        # "unknown" is not a failure (it's truthy in the reference,
        # independent.clj:283-289); only false/missing verdicts are
        failures = [k for k, r in results.items()
                    if r.get("valid") in (False, None)]
        return {
            "valid": merge_valid(r.get("valid") for r in results.values()),
            "results": results,
            "failures": failures,
        }


def checker(sub: Checker, **kw) -> Checker:
    return IndependentChecker(sub, **kw)
