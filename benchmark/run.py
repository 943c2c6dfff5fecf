"""One run of one benchmark cell.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up makes the cell's pool of histories from the seed, builds the
checker the configuration names, and checks every history of the pool
once, which compiles or loads every program the window will run (the
frontier widths a search climbs through depend on the history, so one
warm-up history of its own left programs to compile inside the
window).  The window is a closed loop with one client, cycling through
the pool:
one checker call at a time, as a Jepsen control node analyses one test
after another; it closes at the first completion at or after
``--seconds``.  Once it has closed, every verdict of the window is
compared with the plain reference (`core/reference.py`, over the model
the configuration names, `models/<model>.py`), each linearization the
program gave is replayed, and the numbers compared are printed beside
their limits.  The last line of standard output is
the result.  With ``--trace 1`` the program's spans are on, the first
`TRACED_S` seconds of the window run under the JAX profiler, and the
result carries the cell's per-layer metrics, read over those seconds,
instead of its end-to-end ones.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from core import gen, reference, spec, xtrace  # noqa: E402

OUT = os.path.join(spec.BENCH, "out")
#: a traced run's profiler records the window's calls up to the first
#: that ends this many seconds in: a trace of a whole window of the zk
#: cell took 4 minutes to read back (my chip run, PR 22)
TRACED_S = 10.0
#: the JAX event that marks a program lowered (then compiled, or loaded
#: from the persistent cache): none should happen inside the window
LOWERED = "/jax/core/compile/jaxpr_to_mlir_module_duration"
#: the most keys, in percent of those judged, whose valid verdict may
#: carry no linearization (the batch kernel's keys): sound runs of the
#: etcdemo cells read 4.4-8.9 at their sizes, a "valid" answered without
#: a search 100, the all-valid cell's control 94 (PERF.md, section 2)
UNCERTIFIED_LIMIT = 30.0


class NoChip(SystemExit):
    pass


def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--control", type=int, choices=(0, 1), default=0,
                   help="put the cell's control of the correctness check "
                   "(core/control.py) in the program's place; such a run "
                   "has to come out not correct")
    return p.parse_args(argv)


def devices(chips, require_chip):
    """The devices the cell runs on; without a TPU, or with fewer chips
    than the cell asks for, the run stops here and prints no result."""
    import jax

    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu" or len(devs) < chips):
        print(f"run: the cell needs {chips} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        raise NoChip(3)
    return devs[:chips]


def compile_cache():
    """JAX's persistent cache at a fixed path inside the checkout, for
    every program, so only a checkout's first run compiles."""
    import jax

    path = os.path.join(OUT, "jax-cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


class Lowered:
    """Counts programs lowered, from JAX's own monitoring events."""

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, _secs, **_kw):
        if name == LOWERED:
            self.n += 1


def annotate(name, traced):
    import contextlib

    import jax

    return jax.profiler.TraceAnnotation(name) if traced \
        else contextlib.nullcontext()


def window(chk, test, pool, seconds, stop_trace=None):
    """The closed loop: returns (calls, t_open, t_close); each call is
    (pool index, result or None, t0, t1).  ``stop_trace``, if given, is
    called once, after the first call that ends `TRACED_S` or more into
    the window."""
    calls = []
    t_open = time.perf_counter()
    i = 0
    while True:
        idx = i % len(pool)
        t0 = time.perf_counter()
        try:
            with annotate("bench.call", stop_trace is not None):
                res = chk.check(test, pool[idx]["history"])
        except Exception:  # noqa: BLE001 — a failed request is counted
            traceback.print_exc()
            res = None
        t1 = time.perf_counter()
        calls.append((idx, res, t0, t1))
        if stop_trace is not None and t1 - t_open >= TRACED_S:
            stop_trace()
            stop_trace = None
        i += 1
        if t1 - t_open >= seconds:
            return calls, t_open, t1


def judge(calls, pool, config, model, verdicts):
    """Compare every verdict of the window with the reference, once per
    distinct history, and replay the certificates: every linearization
    the program gave with a valid verdict, and the deepest partial
    linearizations (knossos's final paths) that every invalid verdict
    has to carry, as deep as the reference's search reaches.  The batch
    kernel's valid verdicts carry no linearization; the share of keys
    whose valid verdict carries none is held under
    `UNCERTIFIED_LIMIT`, so that "valid" without a search cannot pass
    an all-valid cell.  Returns (numbers compared, ops decided, failed
    calls)."""
    init = config["init"]
    truth = {}
    mism = bad_cert = shallow = uncertified = failed = ops = keys = 0
    for idx, res, _t0, _t1 in calls:
        item = pool[idx]
        if idx not in truth:
            truth[idx] = {k: reference.check(model, rows, init)
                          for k, rows in item["rows"].items()}
        got = verdicts(res, config["layout"]) if res is not None else {}
        call_failed = res is None
        for k, (want, _seen, depth) in truth[idx].items():
            rows = item["rows"][k]
            r = got.get(k, {})
            valid = r.get("valid")
            keys += 1
            if valid not in (True, False):
                call_failed = True
            else:
                ops += len(rows)
            if valid is True and r.get("linearization") is None:
                uncertified += 1
            if valid is not want:
                mism += 1
            elif valid and r.get("linearization") is not None and not \
                    reference.replay(model, rows, r["linearization"], init):
                bad_cert += 1
            elif not valid and not _refutes(model, rows, r, depth, init):
                shallow += 1
        failed += call_failed
    checks = {"verdict_mismatches": {"value": mism, "limit": 0},
              "bad_certificates": {"value": bad_cert, "limit": 0},
              "shallow_refutations": {"value": shallow, "limit": 0},
              "uncertified_valid_pct": {
                  "value": 100.0 * uncertified / max(keys, 1),
                  "limit": UNCERTIFIED_LIMIT}}
    return checks, ops, failed


def _refutes(model, rows, r, depth, init):
    """An invalid verdict's search reached the reference's depth: its
    ``max_depth`` is that depth, and each of its deepest partial
    linearizations, of which there is at least one, replays and is
    that long."""
    paths = r.get("final_paths")
    return r.get("max_depth") == depth and bool(paths) and all(
        len(p) == depth
        and reference.replay(model, rows, p, init, whole=False)
        for p in paths)


def make_pool(args, config, traffic, model, to_history):
    """The run's histories, as the generator's events (for the
    reference) and as the program's ops (for the checker)."""
    out = []
    for events, _truth in gen.make_pool(args.seed, config, traffic, model):
        if config["layout"] == "independent":
            rows = {k: reference.rows_of(e)
                    for k, e in reference.split_keys(events).items()}
        else:
            rows = {None: reference.rows_of(events)}
        out.append({"history": to_history(events), "rows": rows})
    return out


def memory_peak(devs):
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def traced_metrics(wl, sp, ctx, root):
    """The cell's per-layer metrics, each from its own reader; a reader
    that finds nothing to read returns None and the metric is left
    out."""
    out = {}
    for m in spec.metrics(sp, wl["name"], traced=True):
        v = spec.reader(m, root)(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def run(argv, *, require_chip=True, config_override=None,
        traffic_override=None, sut=None, root=spec.ROOT):
    """One run; returns the result dict.  The keyword arguments are the
    tests' handles: a test runs on the CPU, at a smaller size, with
    another system (a planted fault) in the program's place, or from
    another checkout's `BENCHMARK.json` and benchmark files (``root``)."""
    args = parse(argv)
    sp = spec.load(root)
    wl, config, traffic = spec.cell(sp, args.workload, root)
    config = {**config, **(config_override or {})}
    traffic = {**traffic, **(traffic_override or {})}
    model = spec.model(config, root)
    devs = devices(wl["chips"], require_chip)
    compile_cache()
    if args.control:
        from core import control

        sut = control.Sut(model, traffic)
    elif sut is None:
        from core import sut
    import jax

    lowered = Lowered()
    pool = make_pool(args, config, traffic, model, sut.history)
    chk, program_model = sut.build(config)
    store = os.path.join(OUT, "store")
    test = {"name": wl["name"], "store_base": store, "start_time": "run",
            "model": program_model}
    warm = []
    for item in pool:
        t0 = time.perf_counter()
        chk.check(test, item["history"])
        warm.append(round(time.perf_counter() - t0, 3))
    traced = bool(args.trace)
    sut.tracing(traced)
    logdir = os.path.join(OUT, "trace", wl["name"])
    stop_trace, part = None, {}
    if traced:
        import shutil

        shutil.rmtree(logdir, ignore_errors=True)
        xtrace.start(logdir)
        with jax.profiler.TraceAnnotation(xtrace.SYNC):
            sync_perf = time.perf_counter()

        def stop_trace():
            part.update(t1=time.perf_counter(), counters=sut.counters())
            xtrace.stop()

    c0, low0 = sut.counters(), lowered.n
    setup_s = time.perf_counter() - T_START
    calls, t_open, t_close = window(chk, test, pool, args.seconds,
                                    stop_trace)
    compiles = lowered.n - low0
    if traced and not part:
        stop_trace()
    peak = memory_peak(devs)
    spans = sut.spans() if traced else []
    sut.tracing(False)
    del chk
    t_ref = time.perf_counter()
    checks, ops, failed = judge(calls, pool, config, model, sut.verdicts)
    print(f"run: {len(calls)} calls, (pool index, seconds) "
          f"{[(c[0], round(c[3] - c[2], 3)) for c in calls]}; "
          f"setup {setup_s:.3f} s, of it the pool's first pass {warm};"
          f" reference {time.perf_counter() - t_ref:.3f} s", file=sys.stderr)
    elapsed = t_close - t_open
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs), "memory_peak_bytes": peak}
    result = {"correct": all(c["value"] <= c["limit"]
                             for c in checks.values()),
              "attempted": len(calls), "failed": failed}
    if traced:
        # the per-layer metrics are read over the traced calls
        t_end = part["t1"]
        traced_calls = [c for c in calls if c[3] <= t_end]
        events = xtrace.align(xtrace.load(logdir), sync_perf)
        busy = xtrace.busy_s(events, t_open, t_end)
        # device.level spans split a slice's host wall by occupancy:
        # they say nothing of what the host did
        host = [s for s in spans if s["name"] != "device.level"] + [
            {"name": "bench.call", "t0": t0, "t1": t1}
            for _i, _r, t0, t1 in traced_calls]
        ctx = {"window": (t_open, t_end), "spans": spans,
               "calls": traced_calls, "events": events,
               "counters": {k: v - c0[k]
                            for k, v in part["counters"].items()},
               "compiles": compiles,
               "keys": sum(len(pool[i]["rows"])
                           for i, r, _t0, _t1 in traced_calls if r),
               "pool": pool, "state_bits": model.state_bits(config),
               "memory_peak_bytes": peak,
               "device_kind": devs[0].device_kind,
               "busy_s": busy}
        result["metrics"] = traced_metrics(wl, sp, ctx, root)
        dev.update(busy_s=busy, window_s=t_end - t_open)
        result["breakdown"] = {
            "device_ops": xtrace.top_ops(events, t_open, t_end),
            "idle_gaps": xtrace.idle_gaps(events, t_open, t_end, host)}
    else:
        e2e = {"verified_ops_per_s": ops / elapsed, "setup_s": setup_s}
        result["metrics"] = {
            m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
            for m in spec.metrics(sp, wl["name"], traced=False)}
    result["device"] = dev
    result["checks"] = checks
    return result


def main(argv=None):
    try:
        result = run(sys.argv[1:] if argv is None else argv)
    except NoChip as e:
        return e.code
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
