"""Differential fuzzer for the linearizability engines, with shrinking.

The reference's trust story for its checker is knossos `competition` —
racing two independent algorithms and taking the first answer
(jepsen/src/jepsen/checker.clj:122-126).  This goes further: generate
random histories (valid-by-construction, corrupted, and crash-heavy),
require the device BFS engine and the exact host DFS oracle
(checker/seq.py) to agree, and on ANY disagreement shrink the history to
a minimal counterexample before reporting — the artifact a human needs
to debug a checker divergence is the 6-op core, not the 400-op haystack.

Usage:
    python tools/fuzz.py --rounds 200 [--seed 0] [--n-ops 60]
                         [--model cas-register|register|mutex|
                                  unordered-queue|fifo-queue]
    python tools/fuzz.py --corpus [store/corpus]

``--corpus`` is the campaign->fuzz regression net (live/corpus.py):
every banked live-campaign history replays through ALL engine routes —
direct device BFS, decomposed, bucketed, streaming — with
verdict-parity assertions, a banked-expectation check, and the
certificate audit; queue (multiset) entries replay through
``total_queue``; engine entries additionally replay through the
dedup+DPOR route (analyze/dpor.py) forced on AND off as an extra
bit-identical-parity + audit leg.  Exit 1 on any parity break,
expectation mismatch, or W-code.

Exit code 0 = no divergence; 1 = divergence found (minimal repro printed
as JSON ops, replayable via --replay FILE).
"""

from __future__ import annotations

import argparse
import json
import os
import random
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from jepsen_tpu.checker import linearizable as lin, seq as oracle  # noqa: E402
from jepsen_tpu.history import Op, encode_ops, info_op, invoke_op, ok_op  # noqa: E402
from jepsen_tpu.models import (  # noqa: E402
    cas_register, fifo_queue, mutex, register, unordered_queue,
)

MODELS = {
    "cas-register": cas_register,
    "register": lambda: register(0),
    "mutex": mutex,
    # capacity bounds the multiset; #enqueues never exceeds n-ops, and
    # the fuzzer caps queue histories at 32 ops (see gen_history)
    "unordered-queue": lambda: unordered_queue(33),
    "fifo-queue": lambda: fifo_queue(33),
}

#: queue configs carry a 33-lane state; keep their histories small
QUEUE_MAX_OPS = 32


def gen_history(rng: random.Random, model_name: str, n_ops: int,
                n_procs: int, crash_p: float) -> list[Op]:
    """Canonical simulators live in jepsen_tpu/synth.py (shared with the
    differential tests)."""
    from jepsen_tpu.synth import (
        sim_mutex_history, sim_queue_history, sim_register_history,
    )

    if model_name == "mutex":
        return sim_mutex_history(rng, n_ops, n_procs, crash_p=crash_p)
    if model_name in ("unordered-queue", "fifo-queue"):
        return sim_queue_history(rng, min(n_ops, QUEUE_MAX_OPS), n_procs,
                                 crash_p=crash_p,
                                 fifo=model_name == "fifo-queue")
    return sim_register_history(rng, n_procs, n_ops, crash_p=crash_p,
                                cas=(model_name == "cas-register"),
                                max_crashes=16)


def corrupt(rng: random.Random, h: list[Op]) -> list[Op]:
    from jepsen_tpu.synth import corrupt_dequeue, mutate

    if any(op.f == "dequeue" for op in h) and rng.random() < 0.5:
        # queue-specific corruptions: a from-thin-air dequeue, or a
        # service-order swap (mutate's flip_read arm is a no-op here)
        from jepsen_tpu.synth import swap_dequeues

        if rng.random() < 0.5:
            return swap_dequeues(rng, h)
        return corrupt_dequeue(rng, h)
    return mutate(rng, h)


#: per-engine work caps — mutated histories can explode combinatorially;
#: rounds where either engine gives up are skipped, not flagged
ORACLE_CAP = 40_000
DEVICE_BUDGET = 120_000


def results(h: list[Op], model):
    """Three-way full results: (WGL oracle, device BFS, linear host
    sweep) — or None on an encode error (the caller reads
    ``verdicts`` for that case).  The linear sweep runs with a witness
    cap so its valid verdicts carry auditable certificates."""
    from jepsen_tpu.checker.linear import check_opseq_linear

    s = encode_ops(h, model.f_codes)
    a = oracle.check_opseq(s, model, max_configs=ORACLE_CAP)
    b = lin.search_opseq(s, model, budget=DEVICE_BUDGET)
    c = check_opseq_linear(s, model, max_configs=ORACLE_CAP,
                           witness_cap=500_000)
    return s, (a, b, c)


def verdicts(h: list[Op], model) -> tuple:
    """Three-way: (WGL oracle, device BFS, linear host sweep)."""
    try:
        _s, (a, b, c) = results(h, model)
    except Exception as e:
        err = ("encode-error", str(e))
        return err, err, err
    return a["valid"], b["valid"], c["valid"]


def audit_results(s, model, rs) -> list:
    """Certificate audit over one round's three engine results:
    returns the W-code diagnostics found (empty = all certificates
    replay clean).  Fails loudly in --audit mode: a certificate its
    own engine cannot replay is an engine bug even when all three
    verdicts agree."""
    from jepsen_tpu.analyze.audit import audit

    bad = []
    for engine, r in zip(("oracle", "device", "linear"), rs):
        a = audit(s, model, r)
        if not a["ok"]:
            bad.extend((engine, d) for d in a["diagnostics"])
    return bad


def _diverge(vs) -> bool:
    vs = [v for v in vs if v != "unknown"]
    return len(set(vs)) > 1  # capped-out engines are not divergences


def diverges(h: list[Op], model) -> bool:
    return _diverge(verdicts(h, model))


def shrink(h: list[Op], model, *, max_passes: int = 8) -> list[Op]:
    """Greedy delta-debugging: repeatedly drop op *pairs* (invoke + its
    completion) and lone ops while the divergence persists."""
    from dataclasses import replace as _r  # noqa: F401

    cur = list(h)
    for _ in range(max_passes):
        changed = False
        # try dropping each process's whole op stream first (coarse)
        procs = sorted({op.process for op in cur})
        for p in procs:
            cand = [op for op in cur if op.process != p]
            if len(cand) < len(cur) and cand and diverges(cand, model):
                cur = cand
                changed = True
        # then drop invoke+completion pairs (fine)
        i = 0
        while i < len(cur):
            op = cur[i]
            if op.type == "invoke":
                js = [j for j in range(i + 1, len(cur))
                      if cur[j].process == op.process]
                drop = {i} | ({js[0]} if js else set())
            else:
                drop = {i}
            cand = [op for j, op in enumerate(cur) if j not in drop]
            if cand and diverges(cand, model):
                cur = cand
                changed = True
            else:
                i += 1
        if not changed:
            break
    return cur


def corpus_replay(pool_dir: str, *, audit: bool = True,
                  max_entries: int | None = None,
                  budget: int = DEVICE_BUDGET) -> int:
    """Replay the banked campaign corpus through every engine route.

    Engine entries (register/mutex models) run direct (device BFS),
    decomposed, bucketed, and streaming — plus the HB pre-pass
    (analyze/hb.py): every banked history replays through the static
    order-solver, and when it decides fast its verdict joins the
    parity set and its certificate (GK witness or HB-cycle) goes
    through the independent audit like any engine's.  All decided
    verdicts must be bit-identical to each other AND to the banked
    expectation (when one was recorded), and every certificate must
    audit clean.  Queue entries replay deterministically through
    ``total_queue`` against their banked verdict.  Returns 0 clean /
    1 on any failure."""
    from jepsen_tpu.analyze.audit import audit as audit_fn
    from jepsen_tpu.analyze.hb import hb_dispose
    from jepsen_tpu.decompose.engine import check_opseq_decomposed
    from jepsen_tpu.live import corpus as corpus_mod
    from jepsen_tpu.stream import StreamChecker

    entries = corpus_mod.load_pool(pool_dir)
    if max_entries is not None:
        entries = entries[:max_entries]
    if not entries:
        print(f"corpus: no entries under {pool_dir}")
        return 0
    t0 = time.time()
    failures = unknowns = hb_decided = 0
    for i, e in enumerate(entries):
        label = (f"{e.get('family')}×{e.get('nemesis')}"
                 f"{' seeded' if e.get('seeded') else ''} "
                 f"[{e['id'][:12]}]")
        ops = [Op.from_dict(d) for d in e["ops"]]
        banked = e.get("valid")
        try:
            if e.get("routes") == "queue":
                r = corpus_mod.replay_queue(ops)
                verdicts = {"total-queue": r["valid"]}
                # the static constraint compiler's event-level multiset
                # analysis joins the parity set: same verdict, with
                # W007-auditable evidence rows on invalid
                from jepsen_tpu.analyze.constraints import \
                    analyze_queue_events

                ca = analyze_queue_events(ops)
                verdicts["constraints"] = ca["valid"]
                if ca["valid"] is False and ca.get("evidence"):
                    from jepsen_tpu.analyze.audit import audit_events

                    a = audit_events(ops, {
                        "valid": False, "queue_evidence": ca["evidence"]})
                    if not a["ok"]:
                        print(f"CORPUS AUDIT FAILURE {label}: "
                              f"{[str(d) for d in a['diagnostics']]}",
                              file=sys.stderr)
                        failures += 1
                        continue
                results = []
            else:
                model = corpus_mod.entry_model(e)
                s = encode_ops(ops, model.f_codes)
                direct = lin.search_opseq(s, model, budget=budget)
                decomposed = check_opseq_decomposed(s, model,
                                                    witness=True)
                bucketed = lin.search_batch([s], model, bucket=True,
                                            budget=budget)[0]
                sc = StreamChecker(model)
                for op in ops:
                    sc.ingest(op)
                streamed = sc.finalize()
                verdicts = {"direct": direct["valid"],
                            "decomposed": decomposed["valid"],
                            "bucketed": bucketed["valid"],
                            "streaming": streamed["valid"]}
                results = [("direct", s, model, direct),
                           ("decomposed", s, model, decomposed),
                           ("bucketed", s, model, bucketed),
                           ("streaming", s, model, streamed)]
                hbr = hb_dispose(s, model)
                if hbr is not None:
                    # the static solver decided this banked history
                    # outright: its verdict must match every engine's,
                    # and its certificate must audit like theirs
                    hb_decided += 1
                    verdicts["hb"] = hbr["valid"]
                    results.append(("hb", s, model, hbr))
                # dpor parity leg: the dynamic layer (duplicate-op
                # edges, sleep sets, dead-value dedup, device mask
                # planes) must be verdict-transparent on every banked
                # history — replay the host DFS route with dpor forced
                # ON and OFF and require bit-identical verdicts; the
                # dpor-on certificate goes through the audit like any
                # engine's (regression teeth in tests/test_corpus.py)
                d_on = oracle.check_opseq(s, model,
                                          max_configs=ORACLE_CAP,
                                          dpor=True)
                d_off = oracle.check_opseq(s, model,
                                           max_configs=ORACLE_CAP,
                                           dpor=False)
                verdicts["dpor"] = d_on["valid"]
                verdicts["dpor-off"] = d_off["valid"]
                results.append(("dpor", s, model, d_on))
        except Exception as exc:  # noqa: BLE001 — report, keep going
            print(f"CORPUS FAILURE {label}: replay crashed: "
                  f"{type(exc).__name__}: {exc}", file=sys.stderr)
            failures += 1
            continue
        decided = {k: v for k, v in verdicts.items()
                   if v not in ("unknown",)}
        unknowns += len(verdicts) - len(decided)
        if len(set(decided.values())) > 1:
            print(f"CORPUS DIVERGENCE {label}: {verdicts}",
                  file=sys.stderr)
            failures += 1
            continue
        if banked is not None and decided \
                and set(decided.values()) != {banked}:
            print(f"CORPUS REGRESSION {label}: banked verdict "
                  f"{banked}, engines now say {verdicts}",
                  file=sys.stderr)
            failures += 1
            continue
        mi = e.get("minimal")
        if mi:
            # bank-time ddmin contract: the stored minimal repro must
            # still reproduce the invalid verdict on its route — a
            # minimal core that stopped failing is a checker (or
            # shrinker) regression
            mops = [Op.from_dict(d) for d in mi["ops"]]
            try:
                if e.get("routes") == "queue":
                    mv = corpus_mod.replay_queue(mops)["valid"]
                else:
                    m2 = corpus_mod.entry_model(e)
                    ms = encode_ops(mops, m2.f_codes)
                    mv = oracle.check_opseq(
                        ms, m2, max_configs=ORACLE_CAP)["valid"]
            except Exception as exc:  # noqa: BLE001
                print(f"CORPUS MINIMAL FAILURE {label}: replay "
                      f"crashed: {type(exc).__name__}: {exc}",
                      file=sys.stderr)
                failures += 1
                continue
            if mv is not False:
                print(f"CORPUS MINIMAL FAILURE {label}: the banked "
                      f"{mi['n_ops']}-op minimal repro no longer "
                      f"reproduces invalid (got {mv!r})",
                      file=sys.stderr)
                failures += 1
                continue
        if audit:
            bad = []
            for engine, s_, m_, r_ in results:
                a = audit_fn(s_, m_, r_)
                if not a["ok"]:
                    bad.extend((engine, d) for d in a["diagnostics"])
            if bad:
                print(f"CORPUS AUDIT FAILURE {label}:",
                      file=sys.stderr)
                for engine, d in bad:
                    print(f"  [{engine}] {d}", file=sys.stderr)
                failures += 1
    status = "CLEAN" if failures == 0 else f"{failures} FAILURE(S)"
    print(f"corpus: {len(entries)} entr"
          f"{'y' if len(entries) == 1 else 'ies'} replayed through "
          f"all routes, {status}"
          + (f" ({hb_decided} decided fast by the HB pre-pass, "
             f"parity+audit checked)" if hb_decided else "")
          + (f" ({unknowns} route verdict(s) unknown under the "
             f"budget)" if unknowns else "")
          + f" ({time.time() - t0:.0f}s)")
    return 1 if failures else 0


def replay(path: str, model_name: str) -> int:
    model = MODELS[model_name]()
    ops = [Op.from_dict(d) for d in json.load(open(path))]
    a, b, c = verdicts(ops, model)
    div = len({v for v in (a, b, c) if v != "unknown"}) > 1
    print(f"oracle={a} device={b} linear={c} "
          f"({'DIVERGES' if div else 'agree'})")
    return 1 if div else 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=100)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--n-ops", type=int, default=60)
    ap.add_argument("--n-procs", type=int, default=4)
    ap.add_argument("--model", default="cas-register",
                    choices=sorted(MODELS))
    ap.add_argument("--replay", metavar="FILE")
    ap.add_argument("--corpus", nargs="?", const="store/corpus",
                    default=None, metavar="DIR",
                    help="Replay the banked live-campaign corpus "
                         "(live/corpus.py) through all engine routes "
                         "with verdict-parity + audit assertions; "
                         "DIR defaults to store/corpus")
    ap.add_argument("--max-entries", type=int, default=None,
                    help="Bound the --corpus replay to the first N "
                         "pool entries")
    ap.add_argument("--out", default="fuzz-repro.json")
    ap.add_argument("--audit", action="store_true",
                    help="Also replay every engine's certificate "
                         "through jepsen_tpu.analyze.audit; any W-code "
                         "fails the run loudly (exit 1)")
    args = ap.parse_args()

    if args.corpus is not None:
        return corpus_replay(args.corpus,
                             max_entries=args.max_entries)

    if args.replay:
        return replay(args.replay, args.model)

    model = MODELS[args.model]()
    t0 = time.time()
    for i in range(args.rounds):
        rng = random.Random(args.seed + i)
        crash_p = rng.choice([0.0, 0.0, 0.1, 0.25])
        h = gen_history(rng, args.model, args.n_ops, args.n_procs,
                        crash_p)
        if rng.random() < 0.7:
            h = corrupt(rng, h)
        div = None
        if args.audit:
            # one engine pass serves both the audit and the divergence
            # test — the three searches dominate a round's cost
            try:
                s, rs = results(h, model)
            except Exception:
                div = False  # encode errors are the lint fuzzer's beat
            else:
                bad = audit_results(s, model, rs)
                if bad:
                    print(f"AUDIT FAILURE at round {i} "
                          f"(seed {args.seed + i}):", file=sys.stderr)
                    for engine, d in bad:
                        print(f"  [{engine}] {d}", file=sys.stderr)
                    json.dump([op.to_dict() for op in h],
                              open(args.out, "w"), indent=1)
                    print(f"history -> {args.out}")
                    return 1
                div = _diverge([r["valid"] for r in rs])
        if diverges(h, model) if div is None else div:
            a, b, c = verdicts(h, model)
            print(f"DIVERGENCE at round {i} (seed {args.seed + i}): "
                  f"oracle={a} device={b} linear={c}; shrinking...",
                  file=sys.stderr)
            small = shrink(h, model)
            a2, b2, c2 = verdicts(small, model)
            json.dump([op.to_dict() for op in small], open(args.out, "w"),
                      indent=1)
            print(f"minimal repro: {len(small)} ops (from {len(h)}) -> "
                  f"{args.out}; oracle={a2} device={b2} linear={c2}")
            for op in small:
                print(" ", op.to_dict())
            return 1
        if (i + 1) % 25 == 0:
            print(f"fuzz: {i + 1}/{args.rounds} rounds clean "
                  f"({time.time() - t0:.0f}s)", file=sys.stderr)
    print(f"fuzz: {args.rounds} rounds, no divergence "
          f"({time.time() - t0:.0f}s)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
