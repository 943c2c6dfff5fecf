"""Fleet tier: routing stability, the multi-writer cache store,
warm-boot admission, dead-worker salvage, and the routed-vs-single
parity smoke.

The load-bearing claims under test, in the order a fleet needs them:

  * rendezvous routing moves ~1/N of runs on a join and ONLY the dead
    worker's runs on a leave (a moved run is a re-checked prefix — the
    hash discipline is a correctness-cost bound, not aesthetics);
  * concurrent workers writing the shared verdict store never lose an
    insert, and a restarted worker sees everything the fleet decided;
  * a cold worker is refused admission until its warm-boot report
    verifies (zero kernel-cache misses on re-probe);
  * a killed worker's open runs finalize through the persist-dir
    salvage path and the run's suffix re-routes to a survivor;
  * verdicts through the routed fleet are bit-identical (minus cache
    counters) to one service checking the same histories.
"""

import json
import random
import socket
import threading
import time

from jepsen_tpu.fleet.admission import (
    AdmissionController,
    AdmissionPolicy,
    scale_signal,
)
from jepsen_tpu.fleet.cachestore import FleetCacheStore
from jepsen_tpu.fleet.router import (
    FleetRouter,
    WorkerSpec,
    make_router_server,
    merge_metrics_texts,
    merge_snapshots,
    route_run,
)
from jepsen_tpu.reconnect import Backoff
from jepsen_tpu.stream.service import make_server
from jepsen_tpu.synth import register_history


def _specs(n, port=1):
    return [WorkerSpec(f"w{i}", "127.0.0.1", port) for i in range(n)]


def _mk_history(seed, n_ops=80):
    rng = random.Random(seed)
    return register_history(rng, n_ops=n_ops, n_procs=4, overlap=3,
                            quiesce_every=8, n_values=5, cas=False)


def _op_lines(run_id, h):
    lines = [json.dumps({"run": run_id, "model": "register"})]
    lines += [json.dumps({"run": run_id, "op": op.to_dict()})
              for op in h]
    lines.append(json.dumps({"run": run_id, "end": True}))
    return lines


def _strip_cache(summary):
    out = dict(summary)
    stream = dict(out.get("stream") or {})
    for k in list(stream):
        if k.startswith("cache_"):
            stream.pop(k)
    out["stream"] = stream
    out.pop("finalized_by", None)
    return out


# ---------------------------------------------------------------------------
# rendezvous routing
# ---------------------------------------------------------------------------


def test_rendezvous_routing_is_deterministic_and_balanced():
    workers = _specs(4)
    runs = [f"run-{i}" for i in range(400)]
    placed = {r: route_run(r, workers).wid for r in runs}
    assert placed == {r: route_run(r, workers).wid for r in runs}
    counts = {w.wid: 0 for w in workers}
    for wid in placed.values():
        counts[wid] += 1
    # balanced within a loose bound (hash, not perfection): every
    # worker holds something, none holds a majority
    assert all(c > 0 for c in counts.values())
    assert max(counts.values()) < len(runs) // 2


def test_worker_join_moves_a_bounded_fraction():
    runs = [f"run-{i}" for i in range(500)]
    before = {r: route_run(r, _specs(4)).wid for r in runs}
    after = {r: route_run(r, _specs(5)).wid for r in runs}
    moved = [r for r in runs if before[r] != after[r]]
    # rendezvous: a join steals ~1/5 of the keyspace; everything that
    # moved must have moved TO the new worker
    assert len(moved) < len(runs) * 0.35
    assert all(after[r] == "w4" for r in moved)


def test_worker_leave_moves_only_its_own_runs():
    runs = [f"run-{i}" for i in range(500)]
    full = _specs(4)
    before = {r: route_run(r, full).wid for r in runs}
    survivors = [w for w in full if w.wid != "w2"]
    after = {r: route_run(r, survivors).wid for r in runs}
    for r in runs:
        if before[r] != "w2":
            assert after[r] == before[r], \
                "a survivor's run moved on an unrelated leave"
        else:
            assert after[r] != "w2"


# ---------------------------------------------------------------------------
# the multi-writer cache store
# ---------------------------------------------------------------------------


def test_cachestore_per_worker_segments_do_not_clobber(tmp_path):
    root = str(tmp_path / "store")
    a = FleetCacheStore(root, worker_id="w1", compact_bytes=0)
    b = FleetCacheStore(root, worker_id="w2", compact_bytes=0)
    n = 150
    done = threading.Event()

    def writer():
        for i in range(n):
            b.put_verdict(f"b{i}", i % 2 == 0)
        done.set()

    def spiller():
        # post-test loop condition: always complete at least one
        # insert+spill even if the writer already finished (the test
        # asserts a0 reached the base)
        i = 0
        while True:
            a.put_verdict(f"a{i}", True)
            a.compact()  # spill merges EVERY segment into the base
            i += 1
            if done.is_set():
                break

    threads = [threading.Thread(target=writer),
               threading.Thread(target=spiller)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    b.compact()
    # a restarted worker (fresh load: base + segments) sees every
    # insert from both writers — the hit ratio survives restarts
    fresh = FleetCacheStore(root, worker_id="w3")
    missing = [i for i in range(n) if fresh.get(f"b{i}") is None]
    assert missing == [], \
        f"spill race lost {len(missing)} concurrent insert(s)"
    assert fresh.get("a0")["v"] is True


def test_cachestore_spill_truncates_only_own_segment(tmp_path):
    import os

    root = str(tmp_path / "store")
    a = FleetCacheStore(root, worker_id="w1", compact_bytes=0)
    b = FleetCacheStore(root, worker_id="w2", compact_bytes=0)
    a.put_verdict("ka", True)
    b.put_verdict("kb", False)
    a.compact()
    seg = lambda wid: os.path.join(root, "segments", f"{wid}.jsonl")  # noqa: E731
    assert os.path.getsize(seg("w1")) == 0  # spilled
    assert os.path.getsize(seg("w2")) > 0   # untouched
    # both entries live in the base now / still reachable
    fresh = FleetCacheStore(root, worker_id="w9")
    assert fresh.get("ka")["v"] is True
    assert fresh.get("kb")["v"] is False


def test_cachestore_refresh_picks_up_peer_verdicts(tmp_path):
    root = str(tmp_path / "store")
    a = FleetCacheStore(root, worker_id="w1", compact_bytes=0)
    b = FleetCacheStore(root, worker_id="w2", compact_bytes=0)
    b.put_verdict("peer-key", True)
    assert a.get("peer-key") is None  # loaded before the peer wrote
    assert a.refresh() == 1
    assert a.get("peer-key")["v"] is True


# ---------------------------------------------------------------------------
# warm boot + admission
# ---------------------------------------------------------------------------


def test_warm_boot_compiles_then_verifies_zero_miss(tmp_path):
    from jepsen_tpu.fleet.warmup import WarmShape, warm_boot

    shape = WarmShape(n_det_pad=64, frontier=8)
    rep = warm_boot([shape])
    assert rep["shapes"] == 1
    assert rep["verified"] is True
    assert rep["wall_s"] > 0
    # a second boot of the same shape is all hits, still verified
    rep2 = warm_boot([shape])
    assert rep2["compiled"] == 0
    assert rep2["verified"] is True


def test_load_shapes_from_manifest_and_trace(tmp_path):
    from jepsen_tpu.fleet.warmup import load_shapes

    man = tmp_path / "shapes.json"
    man.write_text(json.dumps({"shapes": [
        {"model": ["register", 0, 1], "n_det_pad": 256,
         "frontier": 64}]}))
    shapes = load_shapes(str(man))
    assert len(shapes) == 1
    assert shapes[0].n_det_pad == 256 and shapes[0].window == 32
    trace = tmp_path / "trace.json"
    trace.write_text(json.dumps({"traceEvents": [
        {"name": "device.compile", "args": {
            "n_det_pad": 1024, "frontier": 128, "window": 64,
            "n_crash_pad": 32, "k": 4}},
        {"name": "device.compile", "args": {
            "n_det_pad": 1024, "frontier": 128, "window": 64,
            "n_crash_pad": 32, "k": 4}},  # duplicate span: dedup
        {"name": "device.slice", "args": {"frontier": 128}},
    ]}))
    shapes = load_shapes(str(trace))
    assert len(shapes) == 1
    assert shapes[0].n_det_pad == 1024 and shapes[0].window == 64


def test_admission_requires_verified_warmup():
    router = FleetRouter(require_warmup=True)
    cold = WorkerSpec("cold", "127.0.0.1", 1)
    assert not router.admit_worker(cold)
    assert not router.admit_worker(
        cold, warmup_report={"verified": False})
    assert router.admit_worker(
        cold, warmup_report={"verified": True, "shapes": 3})
    assert router.is_live("cold")


def test_admission_controller_decisions():
    t = {"now": 0.0}
    ctl = AdmissionController(
        AdmissionPolicy(max_open_runs=100, spawn_open_runs=10,
                        max_shed_rate=0.5, spawn_shed_rate=0.1,
                        min_spawn_interval_s=100.0),
        clock=lambda: t["now"])
    accept = {"open_runs": 1, "fold_backlog": 0,
              "shed_total": 0, "ops_total": 100}
    assert ctl.decide(accept) == "accept"
    assert ctl.decide({**accept, "open_runs": 500}) == "shed"
    # soft ceiling -> spawn signal, damped on repeat
    assert ctl.decide({**accept, "open_runs": 50}) == "spawn-worker"
    assert ctl.decide({**accept, "open_runs": 50}) == "accept"
    t["now"] = 200.0  # damping window passed
    assert ctl.decide({**accept, "open_runs": 50}) == "spawn-worker"
    # shed-rate path: the DELTA since the last sample decides
    ctl2 = AdmissionController(
        AdmissionPolicy(max_shed_rate=0.3, spawn_shed_rate=2.0))
    ctl2.decide({"open_runs": 0, "fold_backlog": 0,
                 "shed_total": 0, "ops_total": 100})
    assert ctl2.decide({"open_runs": 0, "fold_backlog": 0,
                        "shed_total": 80, "ops_total": 150}) == "shed"


def test_scale_signal_sums_labelled_metrics():
    sig = scale_signal({"values": {
        "jtpu_stream_runs_open": {"type": "gauge",
                                  "values": 3},
        "jtpu_shed_total": {"op-budget": 2.0, "draining": 1.0},
        "jtpu_stream_ops_ingested_total": 500.0,
    }})
    assert sig["open_runs"] == 3.0
    assert sig["shed_total"] == 3.0
    assert sig["ops_total"] == 500.0


# ---------------------------------------------------------------------------
# scrape merging
# ---------------------------------------------------------------------------


def test_merge_metrics_texts_adds_worker_label():
    merged = merge_metrics_texts({
        "w0": "# HELP jtpu_x things\n# TYPE jtpu_x counter\n"
              "jtpu_x 3\njtpu_y{reason=\"a\"} 1\n",
        "w1": "# HELP jtpu_x things\n# TYPE jtpu_x counter\n"
              "jtpu_x 4\n",
    })
    lines = merged.splitlines()
    assert lines.count("# HELP jtpu_x things") == 1  # deduped
    assert 'jtpu_x{worker="w0"} 3' in lines
    assert 'jtpu_x{worker="w1"} 4' in lines
    assert 'jtpu_y{worker="w0",reason="a"} 1' in lines


def test_merge_snapshots_sums_values_and_keeps_workers():
    merged = merge_snapshots({
        "w0": {"jtpu_a": {"type": "counter", "help": "h",
                          "values": 2},
               "jtpu_b": {"type": "counter", "help": "h",
                          "values": {"x": 1}},
               "derived": {"ratio": 0.5}},
        "w1": {"jtpu_a": {"type": "counter", "help": "h",
                          "values": 5},
               "jtpu_b": {"type": "counter", "help": "h",
                          "values": {"x": 2, "y": 7}}},
    })
    assert merged["n_workers"] == 2
    assert merged["jtpu_a"]["values"] == 7
    assert merged["jtpu_b"]["values"] == {"x": 3, "y": 7}
    assert "derived" not in merged
    assert merged["workers"]["w1"]["jtpu_a"]["values"] == 5


# ---------------------------------------------------------------------------
# the live fleet: routing, salvage, parity (tier-1 smoke)
# ---------------------------------------------------------------------------


def _boot_fleet(n=2, persist=None, probe_interval=0.05):
    servers, specs = [], []
    for i in range(n):
        srv = make_server("127.0.0.1", 0, persist_dir=persist)
        threading.Thread(target=srv.serve_forever,
                         daemon=True).start()
        servers.append(srv)
        specs.append(WorkerSpec(f"w{i}", "127.0.0.1",
                                srv.server_address[1], persist))
    router = FleetRouter(
        specs, probe_interval=probe_interval,
        backoff_factory=lambda: Backoff(base=0.01, cap=0.05,
                                        max_attempts=3, jitter=0.0))
    router.start_probes()
    rsrv = make_router_server("127.0.0.1", 0, router)
    threading.Thread(target=rsrv.serve_forever, daemon=True).start()
    return servers, specs, router, rsrv


def _teardown(servers, router, rsrv):
    router.stop_probes()
    rsrv.shutdown()
    rsrv.server_close()
    for srv in servers:
        try:
            srv.shutdown()
            srv.server_close()
        except OSError:
            pass


def _client(port, lines):
    s = socket.create_connection(("127.0.0.1", port), timeout=60)
    w = s.makefile("w")
    r = s.makefile("r")
    for li in lines:
        w.write(li + "\n")
    w.flush()
    s.shutdown(socket.SHUT_WR)
    out = [json.loads(x) for x in r if x.strip()]
    s.close()
    return out


def test_fleet_smoke_routed_verdicts_match_single_service():
    """2 workers + router + 8 concurrent clients: every run's final
    through the fleet equals the single-service verdict for the same
    history (cache counters aside)."""
    from jepsen_tpu.stream.service import StreamService

    servers, specs, router, rsrv = _boot_fleet(2)
    rport = rsrv.server_address[1]
    hists = {f"run-{i}": _mk_history(300 + i) for i in range(8)}
    finals = {}
    lock = threading.Lock()

    def go(rid, h):
        out = _client(rport, _op_lines(rid, h))
        fin = [d for d in out if "final" in d]
        assert len(fin) == 1, f"{rid}: {out}"
        with lock:
            finals[rid] = fin[0]["final"]

    threads = [threading.Thread(target=go, args=(rid, h))
               for rid, h in hists.items()]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert set(finals) == set(hists)
    # both workers actually took runs (rendezvous spread)
    placed = {router.route(rid).wid for rid in hists}
    assert placed == {"w0", "w1"}
    # parity: one service, fresh cache, same histories
    for rid, h in hists.items():
        svc = StreamService()
        replies = []
        for li in _op_lines(rid, h):
            svc.handle_line(li, replies.append)
        single = [d for d in replies if "final" in d][-1]["final"]
        assert _strip_cache(finals[rid]) == _strip_cache(single), \
            f"routed verdict diverged from single service on {rid}"
    _teardown(servers, router, rsrv)


def test_fleet_dead_worker_salvages_and_reroutes(tmp_path):
    """Kill the worker holding an open run: the router detects death
    by probe, salvages the persisted final (the worker's abandon path
    flushed it), answers the client, and re-routes the suffix to the
    survivor."""
    persist = str(tmp_path / "persist")
    servers, specs, router, rsrv = _boot_fleet(2, persist=persist)
    rport = rsrv.server_address[1]
    rid = "salvage-me"
    victim = router.route(rid)
    s = socket.create_connection(("127.0.0.1", rport))
    w = s.makefile("w")
    r = s.makefile("r")
    w.write(json.dumps({"run": rid, "model": "register"}) + "\n")
    for op in ({"process": 0, "type": "invoke", "f": "write",
                "value": 7},
               {"process": 0, "type": "ok", "f": "write",
                "value": 7}):
        w.write(json.dumps({"run": rid, "op": op}) + "\n")
    w.flush()
    time.sleep(0.4)
    for srv, spec in zip(servers, specs):
        if spec.wid == victim.wid:
            srv.shutdown()
            srv.server_close()
    deadline = time.time() + 10
    while router.is_live(victim.wid) and time.time() < deadline:
        time.sleep(0.05)
    assert not router.is_live(victim.wid), "probes never declared death"
    for op in ({"process": 1, "type": "invoke", "f": "read",
                "value": None},
               {"process": 1, "type": "ok", "f": "read", "value": 7}):
        w.write(json.dumps({"run": rid, "op": op}) + "\n")
    w.write(json.dumps({"run": rid, "end": True}) + "\n")
    w.flush()
    s.shutdown(socket.SHUT_WR)
    replies = [json.loads(x) for x in r if x.strip()]
    s.close()
    finals = [d["final"] for d in replies if "final" in d]
    assert any(f.get("finalized_by") == "salvage" for f in finals), \
        f"no salvaged final in {replies}"
    # the salvaged prefix verdict is the true one for what was ingested
    salvaged = next(f for f in finals
                    if f.get("finalized_by") == "salvage")
    assert salvaged["valid"] is True
    # and the suffix re-routed: the survivor answered an end for the
    # re-opened run (its own final for the suffix)
    assert len(finals) >= 2, "suffix never finalized on the survivor"
    _teardown(servers, router, rsrv)


def test_fleet_aggregated_scrape_merges_workers():
    import urllib.request

    servers, specs, router, rsrv = _boot_fleet(2)
    rport = rsrv.server_address[1]
    # push one run through so worker counters move
    _client(rport, _op_lines("scrape-run", _mk_history(42, 40)))
    stats = json.loads(urllib.request.urlopen(
        f"http://127.0.0.1:{rport}/api/stats", timeout=10).read())
    assert stats["n_workers"] == 3  # w0 + w1 + the router itself
    assert "jtpu_stream_ops_ingested_total" in stats
    assert "jtpu_fleet_routed_total" in stats
    text = urllib.request.urlopen(
        f"http://127.0.0.1:{rport}/metrics", timeout=10)\
        .read().decode()
    assert 'worker="router"' in text
    assert "jtpu_fleet_workers" in text
    _teardown(servers, router, rsrv)


def test_router_sheds_on_admission_decision():
    servers, specs, router, rsrv = _boot_fleet(2)
    # a policy that sheds everything: open_runs ceiling of 0
    router.admission = AdmissionController(
        AdmissionPolicy(max_open_runs=0))
    rport = rsrv.server_address[1]
    out = _client(rport, _op_lines("shed-me", _mk_history(9, 20)))
    assert any(d.get("overloaded") == "admission" for d in out)
    assert not any("final" in d for d in out)
    _teardown(servers, router, rsrv)


# ---------------------------------------------------------------------------
# verdict-cache spawn damping
# ---------------------------------------------------------------------------


def test_scale_signal_extracts_cache_hit_miss_labels():
    """The FleetCacheStore hit/miss labels ride into the signal; a
    worker that never fired the counter reports bare 0 -> 0.0."""
    sig = scale_signal({"values": {
        "jtpu_verdict_cache_total": {"hit": 40.0, "miss": 160.0,
                                     "insert": 12.0},
    }})
    assert sig["cache_hits"] == 40.0
    assert sig["cache_misses"] == 160.0
    assert scale_signal({"values": {
        "jtpu_verdict_cache_total": 0}})["cache_hits"] == 0.0


def test_admission_cold_cache_damps_spawn():
    """Spawn conditions met, but the fleet verdict cache is cold past
    the minimum-lookups floor: the controller admits instead of
    forking a worker that would boot colder still.  A warm cache (or
    too few lookups to mean anything) leaves spawn undamped."""
    t = {"now": 0.0}

    def ctl():
        return AdmissionController(
            AdmissionPolicy(spawn_open_runs=10,
                            min_spawn_interval_s=0.0,
                            spawn_min_cache_hit_ratio=0.2,
                            cache_signal_min_lookups=256),
            clock=lambda: t["now"])

    busy = {"open_runs": 50, "fold_backlog": 0,
            "shed_total": 0, "ops_total": 100}
    # cold cache, enough lookups: damped to accept
    cold = {**busy, "cache_hits": 30.0, "cache_misses": 470.0}
    c = ctl()
    assert c.cache_hit_ratio(cold) == 0.06
    assert c.decide(cold) == "accept"
    # warm cache: spawn goes through
    warm = {**busy, "cache_hits": 400.0, "cache_misses": 100.0}
    assert ctl().decide(warm) == "spawn-worker"
    # cold but below the lookup floor: ratio means nothing -> spawn
    sparse = {**busy, "cache_hits": 1.0, "cache_misses": 40.0}
    c = ctl()
    assert c.cache_hit_ratio(sparse) is None
    assert c.decide(sparse) == "spawn-worker"
    # no cache keys at all (legacy signal): unaffected
    assert ctl().decide(busy) == "spawn-worker"


def test_trace_shapes_carry_model_and_shard_coords(tmp_path):
    """A sharded device.compile span round-trips model descriptor,
    per-shard lanes and shard count into a WarmShape the warm boot can
    hand straight to get_sharded_batch_kernel."""
    from jepsen_tpu.fleet.warmup import load_shapes

    trace = tmp_path / "trace.json"
    trace.write_text(json.dumps({"traceEvents": [
        {"name": "device.compile", "args": {
            "engine": "xla", "n_det_pad": 64, "n_crash_pad": 32,
            "window": 32, "k": 4, "frontier": 64, "sharded": True,
            "shards": 8, "batch": 2, "masked": True,
            "masked_crash": False, "dedup": True, "vt": 8,
            "model": "cas-register", "model_init": -2147483648,
            "model_width": 1}},
    ]}))
    shapes = load_shapes(str(trace))
    assert len(shapes) == 1
    s = shapes[0]
    assert s.model == ("cas-register", -2147483648, 1)
    assert s.shards == 8
    assert s.batch == 16  # per-shard lanes x shard count
    assert s.masked and s.dedup


def test_worker_env_gives_each_worker_its_own_chip():
    """One chip per fleet worker, set in the worker's environment: the
    TPU runtime of worker k sees chip k alone, with runtime ports no
    other worker uses; off a TPU host the environment passes through."""
    from jepsen_tpu.fleet.__main__ import worker_env

    base = {"PATH": "/bin", "TPU_VISIBLE_CHIPS": "stale"}
    envs = [worker_env(c, base) for c in range(4)]
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
    for e in envs:
        assert e["PATH"] == "/bin"
        assert e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
        assert e["TPU_PROCESS_BOUNDS"] == "1,1,1"
    ports = [int(e[k]) for e in envs
             for k in ("TPU_PROCESS_PORT", "TPU_MESH_CONTROLLER_PORT")]
    assert len(set(ports)) == len(ports)
    assert all(e["TPU_MESH_CONTROLLER_ADDRESS"]
               == f"localhost:{e['TPU_MESH_CONTROLLER_PORT']}"
               for e in envs)
    assert worker_env(None, base) == base
    assert base["TPU_VISIBLE_CHIPS"] == "stale"  # the base is not mutated


def test_fleet_refuses_more_workers_than_chips(monkeypatch):
    """On a TPU host the supervisor refuses more workers than chips
    before it spawns anything (and without touching JAX)."""
    from jepsen_tpu.fleet import __main__ as fleet_main

    monkeypatch.setattr(fleet_main, "tpu_chips", lambda: 2)
    spawned = []
    monkeypatch.setattr(fleet_main, "WorkerProc",
                        lambda *a, **k: spawned.append(a))
    assert fleet_main.main(["--workers", "3"]) == 2
    assert spawned == []
