"""Flight recorder (jepsen_tpu/obs/): span tracing + metrics registry.

What must hold: spans nest and survive threads, ring buffers stay
bounded, the Chrome-trace export is schema-valid (Perfetto-loadable),
``/metrics`` on both the web UI and the stream service speaks
Prometheus text, ``/api/stats`` is a sane JSON snapshot, tracing OFF
costs ~nothing, and an instrumented end-to-end streamed run / traced
core.run actually produces the spans and files the docs promise.
"""

from __future__ import annotations

import json
import os
import re
import socket
import subprocess
import sys
import threading
import time
import urllib.request

import pytest

from jepsen_tpu import obs
from jepsen_tpu.history import info_op, invoke_op, ok_op
from jepsen_tpu.models import register
from jepsen_tpu.obs import metrics as obs_metrics
from jepsen_tpu.obs.report import phase_table, render_report
from jepsen_tpu.obs.trace import SpanRecorder

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def tracing():
    """Tracing forced on, in a throwaway run buffer."""
    obs.enable(True)
    try:
        yield
    finally:
        obs.enable(None)
        obs.set_run(None)


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------


def test_span_nesting_and_attrs(tracing):
    run = "t-nest"
    obs.drop_recorder(run)
    with obs.span("outer", cat="check", run=run):
        with obs.span("inner", cat="fold", run=run, rows=7):
            time.sleep(0.002)
    spans = {s["name"]: s for s in obs.recorder(run).spans()}
    assert set(spans) == {"outer", "inner"}
    assert spans["inner"]["args"] == {"rows": 7}
    assert spans["inner"]["dur"] <= spans["outer"]["dur"]
    # the inner span lies inside the outer's interval
    assert spans["outer"]["ts"] <= spans["inner"]["ts"]
    assert spans["inner"]["ts"] + spans["inner"]["dur"] \
        <= spans["outer"]["ts"] + spans["outer"]["dur"] + 1
    obs.drop_recorder(run)


def test_span_records_error_attr(tracing):
    run = "t-err"
    obs.drop_recorder(run)
    with pytest.raises(ValueError):
        with obs.span("boom", run=run):
            raise ValueError("x")
    (s,) = obs.recorder(run).spans()
    assert s["args"]["error"] == "ValueError"
    obs.drop_recorder(run)


def test_span_thread_safety(tracing):
    run = "t-threads"
    obs.drop_recorder(run)
    n_threads, per = 8, 200

    def work(i):
        for j in range(per):
            with obs.span(f"w{i}", cat="op", run=run, j=j):
                pass

    ts = [threading.Thread(target=work, args=(i,))
          for i in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    spans = obs.recorder(run).spans()
    assert len(spans) == n_threads * per
    # every thread's spans landed under its own tid
    assert len({s["tid"] for s in spans}) == n_threads
    obs.drop_recorder(run)


def test_ring_buffer_is_bounded():
    rec = SpanRecorder("t-ring", cap=100)
    t0 = time.perf_counter()
    for i in range(250):
        rec.record(f"s{i}", "op", t0, t0 + 1e-6)
    assert len(rec) == 100
    assert rec.dropped == 150
    # the survivors are the NEWEST spans
    assert rec.spans()[-1]["name"] == "s249"
    assert rec.spans()[0]["name"] == "s150"


def test_traced_decorator(tracing):
    obs.set_run(None)
    obs.recorder(None).clear()

    @obs.traced("myfn", cat="host")
    def fn(x):
        return x * 2

    assert fn(21) == 42
    names = [s["name"] for s in obs.recorder(None).spans()]
    assert "myfn" in names


def test_tracing_off_is_near_free():
    obs.enable(False)
    try:
        n = 50_000
        t0 = time.perf_counter()
        for _ in range(n):
            with obs.span("hot", cat="op", rows=1):
                pass
        dt = time.perf_counter() - t0
        # the off-path is one flag check + a shared no-op object; even
        # a loaded CI box does 50k in well under a second
        assert dt < 1.0, f"disabled tracing cost {dt:.3f}s for {n} spans"
    finally:
        obs.enable(None)


def test_tracing_off_enabled_check_allocates_nothing():
    """The per-op hot path gates on ``obs.enabled()`` (core.py builds
    the span name/attrs only inside the gate), so the OFF check itself
    must do zero allocation per call — the env knob is read once and
    cached, not ``os.environ.get(...).strip().lower()``ed per op."""
    import tracemalloc

    obs.enable(False)
    try:
        obs.enabled()  # prime any lazy caches outside the window
        tracemalloc.start()
        for _ in range(10_000):
            obs.enabled()
        _cur, peak = tracemalloc.get_traced_memory()
        tracemalloc.stop()
        # tracemalloc's own bookkeeping shows up as a few hundred
        # bytes; 10k string allocations would be hundreds of KB
        assert peak < 8_192, f"enabled() allocated {peak}B over 10k " \
                             f"off-mode calls"
    finally:
        obs.enable(None)


def test_telemetry_off_is_near_free():
    """The telemetry knob's off mode (same contract as tracing off):
    the per-drive gate is one cached flag check, no env lookup, no
    allocation — off-mode kernels are the exact pre-telemetry builds,
    so the flag check IS the entire off-mode cost."""
    import tracemalloc

    from jepsen_tpu.obs import telemetry as tele

    tele.enable(False)
    try:
        n = 50_000
        t0 = time.perf_counter()
        for _ in range(n):
            tele.enabled()
        dt = time.perf_counter() - t0
        assert dt < 1.0, f"disabled telemetry cost {dt:.3f}s for " \
                         f"{n} checks"
        tele.enabled()
        tracemalloc.start()
        for _ in range(10_000):
            tele.enabled()
        _cur, peak = tracemalloc.get_traced_memory()
        tracemalloc.stop()
        assert peak < 8_192, f"telemetry.enabled() allocated " \
                             f"{peak}B over 10k off-mode calls"
        # the off-mode accounting helpers are no-ops, not raisers
        tele.record_device_seconds(0.0)
        tele.record_transfer(0)
    finally:
        tele.enable(None)


def test_chrome_trace_schema(tracing):
    run = "t-schema"
    obs.drop_recorder(run)
    with obs.span("a", cat="check", run=run):
        with obs.span("b", cat="fold", run=run):
            pass
    tr = obs.chrome_trace(run)
    assert tr["displayTimeUnit"] == "ms"
    evs = tr["traceEvents"]
    assert isinstance(evs, list) and evs
    xs = [e for e in evs if e["ph"] == "X"]
    assert {e["name"] for e in xs} == {"a", "b"}
    for e in xs:
        # the Perfetto "complete event" contract
        assert isinstance(e["name"], str)
        assert isinstance(e["pid"], int)
        assert isinstance(e["tid"], int)
        assert isinstance(e["ts"], (int, float)) and e["ts"] >= 0
        assert isinstance(e["dur"], (int, float)) and e["dur"] >= 0
        assert isinstance(e["args"], dict)
    metas = [e for e in evs if e["ph"] == "M"]
    assert any(e["name"] == "thread_name" for e in metas)
    json.dumps(tr)  # serializes clean
    obs.drop_recorder(run)


def test_write_trace_roundtrip(tracing, tmp_path):
    run = "t-write"
    obs.drop_recorder(run)
    with obs.span("x", run=run):
        pass
    p = obs.write_trace(str(tmp_path / "trace.json"), run=run)
    with open(p) as f:
        tr = json.load(f)
    assert any(e["name"] == "x" for e in tr["traceEvents"])
    obs.drop_recorder(run)


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


#: one Prometheus sample line: name{labels} value
_PROM_LINE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? -?[0-9.e+Inf-]+$")


def test_prometheus_render_is_well_formed():
    reg = obs_metrics.Registry()
    c = reg.counter("t_ops_total", "ops", ("type",))
    c.inc(type="ok")
    c.inc(3, type="fail")
    g = reg.gauge("t_open", "open things")
    g.set(2)
    g.dec()
    h = reg.histogram("t_secs", "seconds", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(5.0)
    text = reg.render()
    assert text.endswith("\n")
    for ln in text.splitlines():
        if not ln or ln.startswith("#"):
            continue
        assert _PROM_LINE.match(ln), f"bad exposition line: {ln!r}"
    assert 't_ops_total{type="fail"} 3' in text
    assert "t_open 1" in text
    assert 't_secs_bucket{le="+Inf"} 2' in text
    assert "t_secs_count 2" in text
    # HELP/TYPE headers precede each family
    assert "# TYPE t_ops_total counter" in text
    assert "# TYPE t_open gauge" in text
    assert "# TYPE t_secs histogram" in text


def test_counter_label_discipline():
    reg = obs_metrics.Registry()
    c = reg.counter("t_x_total", "x", ("kind",))
    with pytest.raises(ValueError):
        c.inc()  # missing label
    with pytest.raises(ValueError):
        reg.gauge("t_x_total", "x", ("kind",))  # type clash


def test_snapshot_and_derived_ratios():
    reg = obs_metrics.Registry()
    vc = reg.counter("jtpu_verdict_cache_total", "vc", ("event",))
    for _ in range(3):
        vc.inc(event="hit")
    vc.inc(event="miss")
    b = reg.counter("jtpu_bucket_ops_total", "b", ("kind",))
    b.inc(65, kind="useful")
    b.inc(100, kind="padded")
    snap = reg.snapshot()
    assert snap["jtpu_verdict_cache_total"]["values"]["hit"] == 3
    d = snap["derived"]
    assert d["verdict_cache_hit_ratio"] == 0.75
    assert d["bucket_padding_efficiency"] == 0.65
    json.dumps(snap)


def test_reset_zeroes_in_place_keeping_handles():
    reg = obs_metrics.Registry()
    c = reg.counter("t_keep_total", "x")
    h = reg.histogram("t_keep_secs", "y")
    c.inc(5)
    h.observe(0.5)
    reg.reset()
    assert c.total() == 0
    # the ORIGINAL handle keeps feeding the registry after reset —
    # instrumented modules bind handles once at import
    c.inc()
    h.observe(1.0)
    assert reg.get("t_keep_total") is c
    assert "t_keep_total 1" in reg.render()
    assert "t_keep_secs_count 1" in reg.render()


def test_open_runs_gauge_counts_runs_not_header_lines():
    from jepsen_tpu.stream.service import StreamService

    g = obs_metrics.REGISTRY.gauge("jtpu_stream_runs_open", "")
    base = g.value()
    svc = StreamService(model=register(0))
    out: list = []
    svc.open_run("r1", register(0))
    svc.open_run("r1", register(0))  # reconnect replay of the header
    assert g.value() == base + 1
    svc.end_run("r1", out.append)
    assert g.value() == base


def test_service_drops_run_recorder_on_finalize(tracing):
    from jepsen_tpu.obs import trace as trace_mod
    from jepsen_tpu.stream.service import StreamService

    svc = StreamService(model=register(0))
    svc.open_run("r-drop", register(0))
    with obs.span("x", run="r-drop"):
        pass
    assert "r-drop" in trace_mod._recorders
    svc.end_run("r-drop", lambda d: None)
    # a finished run must not pin its ring buffer in a long-lived
    # multiplexing service
    assert "r-drop" not in trace_mod._recorders


def test_registry_declares_standing_catalog():
    # the acceptance set: cache-hit-ratio inputs, fold/fork, padding
    # efficiency, watchdog — declared up front so a fresh scrape shows
    # the whole catalog
    text = obs_metrics.render()
    for name in ("jtpu_verdict_cache_total", "jtpu_kernel_cache_total",
                 "jtpu_stream_segments_folded_total",
                 "jtpu_stream_forks_total", "jtpu_bucket_ops_total",
                 "jtpu_watchdog_total", "jtpu_shed_total",
                 "jtpu_backoff_exhausted_total",
                 "jtpu_stream_runs_open", "jtpu_ops_total"):
        assert f"# TYPE {name} " in text, name


# ---------------------------------------------------------------------------
# /metrics + /api/stats endpoints
# ---------------------------------------------------------------------------


def test_web_metrics_and_stats_endpoints(tmp_path):
    from jepsen_tpu import web

    srv = web.make_server("127.0.0.1", 0, base=str(tmp_path))
    port = srv.server_address[1]
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics") as r:
            assert r.status == 200
            assert "text/plain" in r.headers["Content-Type"]
            text = r.read().decode()
        assert "# TYPE jtpu_verdict_cache_total counter" in text
        assert "# TYPE jtpu_stream_runs_open gauge" in text
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/api/stats") as r:
            assert r.status == 200
            snap = json.loads(r.read().decode())
        assert "derived" in snap
        assert snap["jtpu_ops_total"]["type"] == "counter"
    finally:
        srv.shutdown()


def test_stream_service_tcp_metrics_scrape():
    from jepsen_tpu.stream.service import make_server

    srv = make_server("127.0.0.1", 0, model=register(0))
    port = srv.server_address[1]
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    try:
        with socket.create_connection(("127.0.0.1", port),
                                      timeout=5) as s:
            # realistic scraper request: extra headers must be drained
            # before the reply, or the close-with-unread-bytes RSTs
            s.sendall(b"GET /metrics HTTP/1.1\r\nHost: x\r\n"
                      b"Accept: */*\r\nUser-Agent: prom\r\n\r\n")
            buf = b""
            while True:
                chunk = s.recv(65536)
                if not chunk:
                    break
                buf += chunk
        head, _, body = buf.partition(b"\r\n\r\n")
        assert head.startswith(b"HTTP/1.0 200")
        assert b"text/plain" in head
        assert b"# TYPE jtpu_stream_runs_open gauge" in body
        # the same port still speaks the JSONL run protocol
        with socket.create_connection(("127.0.0.1", port),
                                      timeout=5) as s:
            f = s.makefile("rw")
            f.write(json.dumps({"run": "r1", "model": "register",
                                "init": 0}) + "\n")
            f.write(json.dumps({"run": "r1", "op": {
                "process": 0, "type": "invoke", "f": "write",
                "value": 1}}) + "\n")
            f.write(json.dumps({"run": "r1", "op": {
                "process": 0, "type": "ok", "f": "write",
                "value": 1}}) + "\n")
            f.write(json.dumps({"run": "r1", "end": True}) + "\n")
            f.flush()
            s.shutdown(socket.SHUT_WR)
            final = None
            for line in f:
                d = json.loads(line)
                if "final" in d:
                    final = d
            assert final is not None
            assert final["final"]["valid"] is True
    finally:
        srv.shutdown()


# ---------------------------------------------------------------------------
# end-to-end: streamed run + traced core.run
# ---------------------------------------------------------------------------


def _crashy_register_history():
    """A register history with one real quiescence cut (-> a fold), a
    crash, and enough post-crash completions at pseudo-quiescent
    points to trigger the bounded :info lookahead (-> a fork)."""
    h = [invoke_op(0, "write", 1), ok_op(0, "write", 1),
         # fresh invoke with nothing pending: closes the segment
         invoke_op(0, "write", 2), ok_op(0, "write", 2),
         invoke_op(1, "write", 3), info_op(1, "write", 3)]  # crash
    v = 10
    for i in range(2, 6):  # sequential post-crash oks (pending==0)
        h += [invoke_op(i, "write", v), ok_op(i, "write", v)]
        v += 1
    return h


def test_streamed_run_emits_fold_and_fork_spans(tracing):
    from jepsen_tpu.stream import StreamChecker

    run = "t-stream-spans"
    obs.drop_recorder(run)
    folded0 = obs_metrics.REGISTRY.counter(
        "jtpu_stream_segments_folded_total", "", ("route",)).total()
    forks0 = obs_metrics.REGISTRY.counter(
        "jtpu_stream_forks_total", "", ("outcome",)).value(
        outcome="spawned")
    sc = StreamChecker(register(0), info_lookahead=2, run_id=run)
    for op in _crashy_register_history():
        sc.ingest(op)
    res = sc.finalize()
    assert res["valid"] is True
    names = {s["name"] for s in obs.recorder(run).spans()}
    assert "stream.fold" in names, names
    assert "stream.fork" in names, names
    assert "stream.finalize" in names
    assert obs_metrics.REGISTRY.get(
        "jtpu_stream_segments_folded_total").total() > folded0
    assert obs_metrics.REGISTRY.get(
        "jtpu_stream_forks_total").value(outcome="spawned") > forks0
    obs.drop_recorder(run)


def _cas_run_test(state, store_base, **over):
    import random

    from jepsen_tpu import fixtures, generator as gen
    from jepsen_tpu.checker import linearizable as lin
    from jepsen_tpu.models import cas_register

    return fixtures.noop_test() | {
        "name": "obs-traced", "store_base": store_base,
        "db": fixtures.atom_db(state),
        "client": fixtures.atom_client(state),
        "model": cas_register(0),
        "checker": lin.linearizable(),
        "generator": gen.clients(
            gen.limit(30, gen.mix([
                {"type": "invoke", "f": "read", "value": None},
                lambda t, p: {"type": "invoke", "f": "write",
                              "value": random.randrange(5)}]))),
        "concurrency": 3,
    } | over


def test_traced_core_run_writes_trace_json(tracing, tmp_path):
    from jepsen_tpu import core, fixtures

    state = fixtures.AtomRegister()
    test = core.run(_cas_run_test(state, str(tmp_path)))
    assert test["results"]["valid"] is True
    run_dir = os.path.join(str(tmp_path), "obs-traced",
                           test["start_time"])
    p = os.path.join(run_dir, "trace.json")
    assert os.path.isfile(p), os.listdir(str(tmp_path))
    with open(p) as f:
        tr = json.load(f)
    xs = [e for e in tr["traceEvents"] if e["ph"] == "X"]
    names = {e["name"] for e in xs}
    # the whole vertical shows up: run envelope, phases, worker ops
    assert "run" in names
    assert "workload" in names
    assert "analyze" in names
    assert any(n.startswith("op:") for n in names)
    # the run envelope accounts for (almost) the whole trace extent
    run_span = next(e for e in xs if e["name"] == "run")
    t0 = min(e["ts"] for e in xs)
    t1 = max(e["ts"] + e["dur"] for e in xs)
    assert run_span["dur"] >= 0.90 * (t1 - t0)
    # always-on phase accounting rode along (campaign cells use it)
    assert set(test["phase_s"]) >= {"setup", "workload", "check"}


def test_phase_table_report(tracing, tmp_path):
    run = "t-report"
    obs.drop_recorder(run)
    with obs.span("run", cat="run", run=run):
        with obs.span("prep", cat="host", run=run):
            time.sleep(0.004)
        with obs.span("dispatch", cat="device", run=run):
            time.sleep(0.008)
    p = obs.write_trace(str(tmp_path / "trace.json"), run=run)
    rep = phase_table(json.load(open(p)))
    cats = {r["cat"]: r for r in rep["phases"]}
    assert {"run", "host", "device"} <= set(cats)
    assert cats["device"]["busy_s"] > cats["host"]["busy_s"] > 0
    # the run envelope is excluded from busy/idle accounting
    assert rep["idle_s"] < rep["wall_s"]
    assert rep["wall_s"] >= cats["device"]["busy_s"]
    assert "device" in render_report(rep)
    # a trace with NO telemetry spans keeps the pre-telemetry report
    # shape — no section in the dict, none in the rendering
    assert "telemetry" not in rep
    assert "device search telemetry" not in render_report(rep)
    obs.drop_recorder(run)


def test_phase_table_telemetry_section(tracing, tmp_path):
    """Traces recorded with device telemetry grow the per-level table
    + predicted-vs-observed prune row (the committed BENCH_trace_1k
    recording is the canonical instance)."""
    p = os.path.join(REPO, "BENCH_trace_1k.json")
    rep = phase_table(json.load(open(p)))
    t = rep["telemetry"]
    rows = t["levels"]
    assert rows and all(r["occupancy"] > 0 for r in rows)
    assert rows[0]["level"] == 0
    assert {"mask_kill_pct", "dedup_fold_pct", "busy_s"} \
        <= set(rows[0])
    s = t["search"]
    assert s["observed_prune_ratio"] is not None
    assert s["prune_ratio_delta"] is not None
    assert t["compiles"]["count"] >= 1
    assert t["transfer_bytes"] > 0
    txt = render_report(rep)
    assert "device search telemetry" in txt
    assert "prune ratio: observed" in txt
    assert "mask-kill%" in txt
    # the per-level table elides its middle rather than printing
    # hundreds of rows
    if len(rows) > 24:
        assert "elided" in txt


def test_trace_report_tool_smoke(tracing, tmp_path):
    run = "t-tool"
    obs.drop_recorder(run)
    with obs.span("fold", cat="fold", run=run):
        time.sleep(0.002)
    p = obs.write_trace(str(tmp_path / "trace.json"), run=run)
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "trace_report.py"),
         p, "--json"],
        capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    rep = json.loads(out.stdout)
    assert rep["phases"][0]["cat"] == "fold"
    obs.drop_recorder(run)


def test_obs_cli_trace_resolves_store_run(tracing, tmp_path):
    run = "t-cli"
    obs.drop_recorder(run)
    with obs.span("x", run=run):
        pass
    d = tmp_path / "mytest" / "20260101T000000"
    obs.write_trace(str(d / "trace.json"), run=run)
    from jepsen_tpu.obs.__main__ import resolve_trace

    assert resolve_trace("mytest/20260101T000000",
                         str(tmp_path)).endswith("trace.json")
    with pytest.raises(FileNotFoundError):
        resolve_trace("nope/run", str(tmp_path))
    obs.drop_recorder(run)


# ---------------------------------------------------------------------------
# log context + campaign tooltips
# ---------------------------------------------------------------------------


def test_log_ctx_stamps_fields(caplog):
    import logging

    lg = logging.getLogger("jepsen")
    with caplog.at_level(logging.WARNING, logger="jepsen"):
        obs.log_ctx(lg, run_id="r9", conn="1.2.3.4:5").warning(
            "line failed: %s", "boom")
    assert "[run_id=r9 conn=1.2.3.4:5] line failed: boom" \
        in caplog.text
    # None-valued fields are omitted, not rendered as "None"
    caplog.clear()
    with caplog.at_level(logging.WARNING, logger="jepsen"):
        obs.log_ctx(lg, run_id="r1", conn=None).warning("x")
    assert "[run_id=r1] x" in caplog.text


def test_campaign_grid_shows_phase_tooltips(tmp_path):
    from jepsen_tpu import web

    d = tmp_path / "campaigns" / "c1"
    os.makedirs(d)
    with open(d / "campaign.json", "w") as f:
        json.dump({"cells": [{
            "family": "kv", "nemesis": "kill-restart", "status": "ok",
            "valid": True,
            "phases": {"setup": 1.2, "workload": 8.0, "nemesis": 0.4,
                       "check": 0.6}}],
            "summary": {"ok": 1}}, f)
    page = web.campaign_html(str(tmp_path), "c1")
    assert 'title="setup 1.2s' in page
    assert "nemesis 0.4s" in page
    # the index page carries the fleet-health strip polling /api/stats
    idx = web.campaigns_html(str(tmp_path))
    assert "/api/stats" in idx


def test_phase_times_from_history():
    from dataclasses import replace

    from jepsen_tpu.history import Op
    from jepsen_tpu.live.campaign import _phase_times

    def nem(f, t):
        return Op(process="nemesis", type="info", f=f, value=None,
                  time=int(t * 1e9))

    test = {"phase_s": {"setup": 2.0, "workload": 9.0, "check": 1.0},
            "history": [nem("kill", 1.0), nem("kill", 1.5),
                        nem("restart", 2.0), nem("restart", 2.25)]}
    ph = _phase_times(test, "kill-restart")
    assert ph["setup"] == 2.0
    assert ph["workload"] == 9.0
    assert ph["check"] == 1.0
    assert ph["nemesis"] == pytest.approx(0.5)
    assert ph["heal"] == pytest.approx(0.25)
