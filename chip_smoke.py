#!/usr/bin/env python3
"""chip_smoke.py — drive the checker's main path once on a TPU.

    python chip_smoke.py              # one chip: every phase below
    python chip_smoke.py --chips 4    # the mesh-sharded path only

One process holds the chip for the whole run.  Every phase prints one
line (engine or route, decided or not, wall seconds including
compiles, and whether it matched the host reference); the last line
of stdout is the JSON contract line
``{"ok": true, "device": {"platform", "kind", "count"}}``.  Any phase
that fails, or whose result does not match, makes the script exit
non-zero without that line, and so does a run that finds no TPU: it
never carries on on the CPU.

Phases (one chip):

* device   — jax.devices()[0] is a TPU.
* suite    — the atomdemo suite in process, linearizable checker on
  algorithm="device"; results.json must be valid with device engines.
* 1k, mutex2k — bench.py's seeded single histories through
  ``Linearizable(model, algorithm="device", hb=False)`` (audited
  certificates; the static prepass would decide mutex2k with no
  search at all, so it is off wherever the device must run), with
  the default ``auto`` engine selector, pinned to the XLA kernel, and
  with the Pallas kernel wherever it is eligible; every verdict must
  equal host ``linear``.  Where a selector picked the Pallas kernel,
  the engine label must say so.
* batch    — ``search_batch`` over bench.py's 256 keys (BASELINE
  config 3), each verdict compared with the host.
* stream   — a few runs of op lines through the in-process stream
  service with every fold routed to the device; each final verdict
  equals the post-hoc one and carries ``fallback: false``.
* 10k      — BASELINE's 10,000-op 32-process CAS-register history.

Phases (``--chips 4``): ``search_batch`` over the same 256 keys on a
4-device ``NamedSharding`` mesh (each device must hold keys) and the
sharded frontier on one history, compared with one-device
``search_batch`` in the same process and with the host.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))


class PhaseError(AssertionError):
    pass


def _check(cond, msg):
    if not cond:
        raise PhaseError(msg)


def _line(phase, **kw):
    print(f"phase {phase}: " + " ".join(f"{k}={v}" for k, v in kw.items()),
          flush=True)


def _host(seq, model):
    from jepsen_tpu.checker.linear import check_opseq_linear

    return check_opseq_linear(seq, model, lint=False)["valid"]


#: persistent-compilation-cache events seen by this process
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": 0,
                 "/jax/compilation_cache/cache_misses": 0}


def _count_cache_event(event, **_kw):
    if event in _CACHE_EVENTS:
        _CACHE_EVENTS[event] += 1


def _cache_entries(path) -> int:
    try:
        return len(os.listdir(path))
    except OSError:
        return 0


def _pallas_kernels() -> int:
    from jepsen_tpu.checker import linearizable as lin

    return sum(1 for k in lin._KERNEL_CACHE if k[-1] == "pallas")


def phase_suite(out_dir):
    """atomdemo through its CLI entry point, in this process."""
    from jepsen_tpu import store
    from jepsen_tpu.suites import atomdemo

    os.environ["JEPSEN_TPU_LIN_ALGORITHM"] = "device"
    base = os.path.join(out_dir, "store")
    old = store.BASE
    store.BASE = base
    t0 = time.perf_counter()
    try:
        try:
            atomdemo.main(["test", "--dummy", "-n", "n1", "-n", "n2",
                           "-n", "n3", "--time-limit", "5",
                           "--ack-latency", "0.05", "--concurrency",
                           "4n", "--rate", "200"])
        except SystemExit as e:
            _check(e.code in (0, None), f"atomdemo exited {e.code}")
    finally:
        store.BASE = old
        os.environ.pop("JEPSEN_TPU_LIN_ALGORITHM", None)
    dt = time.perf_counter() - t0
    runs = sorted(
        os.path.join(r, f) for r, _d, fs in os.walk(base) for f in fs
        if f == "results.json")
    _check(runs, "atomdemo wrote no results.json")
    with open(runs[-1]) as f:
        res = json.load(f)
    engines: dict = {}

    def walk(x):
        if isinstance(x, dict):
            if isinstance(x.get("engine"), str):
                engines[x["engine"]] = engines.get(x["engine"], 0) + 1
            for v in x.values():
                walk(v)
        elif isinstance(x, list):
            for v in x:
                walk(v)

    walk(res)
    dev = sum(n for e, n in engines.items() if e.startswith("device-"))
    _line("suite", engine=json.dumps(engines, sort_keys=True).replace(
        " ", ""), decided=res.get("valid"), seconds=f"{dt:.3f}",
        match=res.get("valid") is True and dev > 0)
    _check(res.get("valid") is True, f"atomdemo results valid="
           f"{res.get('valid')!r}")
    _check(dev > 0, f"no key ran on a device engine: {engines}")


def phase_single(name):
    """One bench history through Linearizable(algorithm="device"),
    auto-selected engine and pinned XLA, against host linear."""
    import bench
    from jepsen_tpu.checker import linearizable as lin

    seq, model = bench.make_seq(name)
    t0 = time.perf_counter()
    want = _host(seq, model)
    t_host = time.perf_counter() - t0
    _check(want in (True, False), f"{name}: host undecided")
    # auto: the default selector; xla: every slice on the XLA kernel;
    # pallas: search_opseq started on the Pallas kernel's widest
    # eligible rung (F=64) with the dpor reductions it declines off,
    # so it runs until the frontier outgrows it
    for mode in ("auto", "xla", "pallas"):
        prev = lin._ENGINE_MODE
        lin._ENGINE_MODE = mode
        n_pallas0 = _pallas_kernels()
        t0 = time.perf_counter()
        try:
            if mode == "pallas":
                dims = lin.choose_dims(lin.encode_search(seq), model,
                                       frontier=64)
                out = lin.search_opseq(seq, model, dims=dims, hb=False,
                                       dpor=False, audit=True)
            else:
                out = lin.Linearizable(model, algorithm="device",
                                       hb=False,
                                       audit=True).check({}, seq)
        finally:
            lin._ENGINE_MODE = prev
        dt = time.perf_counter() - t0
        eng = out.get("engine", "")
        chose_pallas = _pallas_kernels() > n_pallas0
        match = out["valid"] == want
        _line(name, selector=mode, engine=eng,
              pallas_selected=chose_pallas, decided=out["valid"],
              seconds=f"{dt:.3f}", host=want,
              host_seconds=f"{t_host:.3f}", match=match)
        _check(eng.startswith("device-"), f"{name}: engine {eng!r} is "
               "not a device engine")
        _check(match, f"{name}/{mode}: device {out['valid']} != host "
               f"{want}")
        _check(out.get("audit", {}).get("ok") is True,
               f"{name}/{mode}: certificate audit {out.get('audit')}")
        _check(("pallas" in eng) == chose_pallas,
               f"{name}/{mode}: selector picked pallas={chose_pallas} "
               f"but the engine label is {eng!r}")
        if mode == "xla":
            _check(not chose_pallas, f"{name}: pinned XLA used pallas")
        if mode == "pallas":
            _check(chose_pallas, f"{name}: the Pallas kernel never ran")


def _batch(sharding=None):
    import bench
    from jepsen_tpu.checker import linearizable as lin

    seqs, model = bench.make_batch()
    t0 = time.perf_counter()
    res = lin.search_batch(seqs, model, sharding=sharding, audit=True)
    dt = time.perf_counter() - t0
    got = [r["valid"] for r in res]
    engines = sorted({r.get("engine", "") for r in res})
    return seqs, model, got, engines, dt, res


def phase_batch():
    seqs, model, got, engines, dt, _res = _batch()
    want = [_host(s, model) for s in seqs]
    match = got == want
    _line("batch", keys=len(seqs), engine=",".join(engines),
          decided=sum(v in (True, False) for v in got),
          invalid=sum(v is False for v in got), seconds=f"{dt:.3f}",
          match=match)
    _check(match, "batch: per-key verdicts differ from the host at "
           f"{[i for i, (a, b) in enumerate(zip(got, want)) if a != b]}")
    _check(any(e.startswith("device-") for e in engines),
           f"batch: no key ran on the device: {engines}")
    _check(not any("fallback" in e for e in engines),
           f"batch: a key took the host fallback: {engines}")


def phase_stream():
    """A few runs of op lines through the in-process stream service,
    every eligible segment fold routed to the device batch."""
    import random

    from jepsen_tpu.history import encode_ops
    from jepsen_tpu.models import register
    from jepsen_tpu.stream.service import StreamService, serve_lines
    from jepsen_tpu.synth import flip_read, register_history

    model = register(0)
    lines, want = [], {}
    for i in range(4):
        rng = random.Random(f"chip-smoke-stream-{i}")
        h = register_history(rng, n_ops=400, n_procs=6, overlap=4,
                             quiesce_every=12, n_values=6, cas=False)
        if i % 2:
            h = flip_read(rng, h)
        run = f"r{i}"
        want[run] = _host(encode_ops(h, model.f_codes), model)
        lines.append(json.dumps({"run": run, "model": "register",
                                 "init": 0}))
        for op in h:
            lines.append(json.dumps({"run": run, "op": {
                "process": op.process, "type": op.type, "f": op.f,
                "value": op.value}}))
        lines.append(json.dumps({"run": run, "end": True}))
    finals: dict = {}

    def emit(msg):
        if "final" in msg:
            finals[msg["run"]] = msg["final"]

    t0 = time.perf_counter()
    serve_lines(StreamService(host_fold_max=0), lines, emit)
    dt = time.perf_counter() - t0
    for run, w in sorted(want.items()):
        f = finals.get(run) or {}
        st = f.get("stream", {})
        match = f.get("valid") == w and st.get("fallback") is False
        _line("stream", run=run, engine=f.get("engine"),
              device_folds=st.get("routes", {}).get("device"),
              decided=f.get("valid"), fallback=st.get("fallback"),
              seconds=f"{dt:.3f}", match=match)
        _check(match, f"stream {run}: final {f.get('valid')} (fallback "
               f"{st.get('fallback')}) vs post-hoc {w}")
    _check(any((finals[r].get("stream", {}).get("routes", {})
                .get("device") or 0) > 0 for r in finals),
           "stream: no segment fold ran on the device")


def phase_10k():
    import bench
    from jepsen_tpu.checker import linearizable as lin

    seq, model = bench.make_seq("10k")
    t0 = time.perf_counter()
    want = _host(seq, model)
    t_host = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = lin.Linearizable(model, algorithm="device", hb=False,
                           audit=True).check({}, seq)
    dt = time.perf_counter() - t0
    eng = out.get("engine", "")
    match = out["valid"] == want
    _line("10k", engine=eng, decided=out["valid"],
          configs=out.get("device_configs", out.get("configs")),
          depth=out.get("max_depth"),
          seconds=f"{dt:.3f}", host=want, host_seconds=f"{t_host:.3f}",
          match=match)
    _check(eng.startswith("device-"), f"10k: engine {eng!r}")
    _check(match, f"10k: device {out['valid']} != host {want}")


def phase_mesh(devices):
    """--chips 4: the mesh-sharded batch and the sharded frontier."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import bench
    from jepsen_tpu.checker import linearizable as lin

    mesh = Mesh(np.array(devices), ("shard",))
    sharding = NamedSharding(mesh, P("shard"))
    seqs, model, one, _e1, dt1, _r1 = _batch()
    want = [_host(s, model) for s in seqs]
    _line("batch-1dev", keys=len(seqs), seconds=f"{dt1:.3f}",
          match=one == want)
    _check(one == want, "one-device batch differs from the host")
    _s, _m, got, engines, dt, res = _batch(sharding)
    dev_keys = (res[0].get("shard_batch") or {}).get("device_keys") or {}
    match = got == want == one
    _line("batch-mesh", keys=len(seqs), engine=",".join(engines),
          device_keys=json.dumps(dev_keys, sort_keys=True).replace(
              " ", ""), seconds=f"{dt:.3f}", match=match)
    _check(match, "mesh batch verdicts differ from one-device/host")
    _check(not any("fallback" in e for e in engines),
           f"mesh batch: a key took the host fallback: {engines}")
    _check(sorted(dev_keys) == sorted(str(d.id) for d in devices)
           and all(v > 0 for v in dev_keys.values()),
           f"mesh batch did not place keys on every device: {dev_keys}")
    seq, model = bench.make_seq("1k")
    want1 = _host(seq, model)
    t0 = time.perf_counter()
    out = lin.search_opseq_sharded(seq, model, mesh, audit=True)
    dt = time.perf_counter() - t0
    single = lin.search_opseq(seq, model, audit=True)
    match = out["valid"] == want1 == single["valid"]
    _line("sharded-frontier", engine=out.get("engine"),
          decided=out["valid"], one_device=single["valid"], host=want1,
          seconds=f"{dt:.3f}", match=match)
    _check(match, f"sharded frontier {out['valid']} vs one-device "
           f"{single['valid']} vs host {want1}")
    _check(str(out.get("engine", "")).startswith("device-"),
           f"sharded frontier engine {out.get('engine')!r}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh-sharded path (and what "
                         "it is compared with) across four chips.")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(HERE, "jepsen_tpu")):
        print("chip_smoke: no jepsen_tpu package next to this script; "
              "run it from a checkout of the repo", file=sys.stderr)
        return 2
    sys.path.insert(0, HERE)
    from jepsen_tpu.util import enable_compilation_cache

    cache = enable_compilation_cache()
    import jax

    jax.monitoring.register_event_listener(_count_cache_event)
    entries0 = _cache_entries(cache)
    # the fallback location, watched to show nothing lands there when
    # JAX_COMPILATION_CACHE_DIR points elsewhere
    repo_cache = os.path.join(HERE, ".jax_cache")
    repo0 = _cache_entries(repo_cache)
    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        print(f"chip_smoke: found no TPU (JAX platform "
              f"{d0.platform!r}); this smoke runs on the chip only",
              file=sys.stderr)
        return 1
    _line("device", platform=d0.platform,
          kind=json.dumps(d0.device_kind).replace(" ", "_"),
          count=len(devices), compile_cache=cache,
          cache_entries=entries0)
    out_dir = os.path.join(HERE, "chiprun_out", "smoke")
    os.makedirs(out_dir, exist_ok=True)
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            _check(len(devices) >= 4, f"--chips 4 needs four devices, "
                   f"JAX reports {len(devices)}")
            devices = devices[:4]
            phase_mesh(devices)
        else:
            phase_suite(out_dir)
            phase_single("1k")
            phase_single("mutex2k")
            phase_batch()
            phase_stream()
            phase_10k()
    except Exception as e:  # noqa: BLE001 — any fault fails the smoke
        import traceback

        traceback.print_exc()
        print(f"chip_smoke: FAILED after {time.perf_counter() - t0:.1f}s:"
              f" {e}", file=sys.stderr)
        return 1
    print(f"chip_smoke: all phases passed in "
          f"{time.perf_counter() - t0:.1f}s", file=sys.stderr)
    _line("compile-cache", dir=cache, entries_before=entries0,
          entries_after=_cache_entries(cache),
          hits=_CACHE_EVENTS["/jax/compilation_cache/cache_hits"],
          misses=_CACHE_EVENTS["/jax/compilation_cache/cache_misses"],
          repo_cache_entries=f"{repo0}->{_cache_entries(repo_cache)}")
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
