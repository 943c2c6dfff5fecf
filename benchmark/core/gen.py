"""The one traffic generator: histories from a configuration, a traffic
mix and a seed.

Everything here is plain Python over plain tuples and imports nothing of
`jepsen_tpu`, so no PR that changes the program can move the yardstick.
An event is ``(process, type, f, value)``; a keyed event (etcdemo's
independent keys) is ``(process, type, f, key, value)``.

What is the model's own comes from its file, `models/<model>.py`
(`spec.model`): ``history(rng, p, n_ops, exact)``, a history valid by
construction, and ``corrupt(events, p)``, the same history made invalid
(None where it cannot be).  Here are the layouts that arrange those
histories into a test, the nemesis, and the pairing of events into ops.
"""

from __future__ import annotations

import random

INF = float("inf")

#: the fields of a traffic file the layouts read, besides the model's own
#: (its ``TRAFFIC_KEYS``); anything else is refused so that a typo is not
#: silently a default.  ``stale_share`` is the share of keys (of
#: histories, in a single-history layout) that the model's ``corrupt``
#: makes invalid, for any model
LAYOUT_KEYS = {"pool", "overlap", "crash_p", "max_crashes", "crashes",
               "stale_share", "work", "why"}


def pair(events):
    """Logical ops of one history: dicts with the invoke event's index,
    the completion's index (INF for a crash) and type, in invocation
    order.  Fails are kept (type "fail"); callers drop them."""
    open_ = {}
    ops = []
    for i, e in enumerate(events):
        p, typ, f, v = e[0], e[1], e[2], e[-1]
        if p == "nemesis":
            continue
        if typ == "invoke":
            op = {"f": f, "value": v, "inv": i, "ret": INF, "type": "info"}
            open_[p] = op
            ops.append(op)
        else:
            op = open_.pop(p)
            op["type"] = typ
            if typ == "ok":
                op["ret"] = i
                if op["value"] is None:
                    # what a read (an op invoked with no value) returned
                    op["value"] = v
    return ops


def encoded_count(events):
    """Ops that may have taken effect: ok and info, not fail."""
    return sum(1 for op in pair(events) if op["type"] != "fail")


def nemesis_events(p):
    """partition-random-halves at ``period_s`` on / ``period_s`` off over
    the test: each start and stop is an invoke and an info completion."""
    nem = p["nemesis"]
    cycles = max(1, p["test_seconds"] // (2 * nem["period_s"]))
    out = []
    for _ in range(cycles):
        for f in ("start", "stop"):
            out += [("nemesis", "info", f, None)] * 2
    return out


def interleave(rng, streams, nemesis):
    """Merge per-group event streams in a seeded random order, keeping
    each stream's own order; nemesis events are spread evenly."""
    heads = [0] * len(streams)
    total = sum(len(s) for s in streams)
    every = max(1, total // (len(nemesis) + 1))
    out = []
    live = [g for g, s in enumerate(streams) if s]
    k = 0
    while live:
        g = rng.choice(live)
        out.append(streams[g][heads[g]])
        heads[g] += 1
        if heads[g] == len(streams[g]):
            live.remove(g)
        if k < len(nemesis) and len(out) % every == 0:
            out.append(nemesis[k])
            k += 1
    return out + nemesis[k:]


def _history(rng, p, model, n_ops, exact, stale):
    """One history of the model from the seed's ``rng``; with ``stale``,
    corrupted, redrawn until the corruption has a place."""
    for _ in range(64):
        h = model.history(rng, p, n_ops, exact)
        if not stale:
            return h
        h = model.corrupt(h, p)
        if h is not None:
            return h
    raise ValueError("no corruption fits 64 histories in a row")


def independent_test(rng, p, model):
    """One etcdemo test: ``keys_per_test`` keys; ``groups`` thread groups
    of ``procs`` threads each work through the keys in turn
    (independent/concurrent-generator), so group g holds keys g,
    g + groups, ...; a ``stale_share`` of the keys, drawn from the
    seed, is corrupted.  Returns (events, truth) where truth
    maps key -> True if the key's history is valid by construction."""
    n_keys = p["keys_per_test"]
    groups = p["concurrency"] // p["procs"]
    stale = set(rng.sample(range(n_keys),
                           round(p["stale_share"] * n_keys)))
    procs = p["procs"]
    # crashes so far of each thread of each group: a crashed thread goes
    # on under process id + concurrency, across the group's keys
    gen_of = [[0] * procs for _ in range(groups)]
    streams = [[] for _ in range(groups)]
    for k in range(n_keys):
        g = k % groups
        h = _history(rng, p, model, p["ops_per_key"], False, k in stale)
        top = list(gen_of[g])
        for q, typ, f, v in h:
            w, local = q % procs, q // procs
            gen_ = gen_of[g][w] + local
            top[w] = max(top[w], gen_ + (typ == "info"))
            streams[g].append((g * procs + w + p["concurrency"] * gen_,
                               typ, f, k, v))
        gen_of[g] = top
    events = interleave(rng, streams, nemesis_events(p))
    return events, {k: k not in stale for k in range(n_keys)}


def single_history(rng, p, model):
    """One whole history of exactly ``encoded_ops`` ops that may have
    taken effect, corrupted where ``stale_share`` is set.  With ``work``
    = [lo, hi], redrawn until the plain reference's search visits from
    lo to hi configurations: the configurations a history holds set most
    of what a search of it costs, and they vary twofold from one drawn
    history to the next, so each seed would time other work."""
    from .reference import check, rows_of

    lo, hi = p.get("work", (0, INF))
    for _ in range(256):
        h = _history(rng, p, model, p["encoded_ops"], True,
                     p.get("stale_share", 0))
        if lo <= check(model, rows_of(h), p["init"])[1] <= hi:
            break
    else:
        raise ValueError(f"no history of {lo}-{hi} configurations in 256")
    nem = nemesis_events(p)
    events = h[:len(h) // 2] + nem[:len(nem) // 2] + h[len(h) // 2:] \
        + nem[len(nem) // 2:]
    return events, {None: not p.get("stale_share", 0)}


LAYOUTS = {"independent": independent_test, "single": single_history}


def make_pool(seed, config, traffic, model):
    """The run's ``pool`` histories (tests, for independent keys) of the
    configuration's model, all drawn from the seed and made in set-up."""
    unknown = set(traffic) - LAYOUT_KEYS - model.TRAFFIC_KEYS
    if unknown:
        raise ValueError(f"unknown traffic keys {sorted(unknown)}")
    rng = random.Random(f"pool:{int(seed)}")
    p = {**config, **traffic}
    return [LAYOUTS[p["layout"]](rng, p, model) for _ in range(p["pool"])]
