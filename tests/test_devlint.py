"""Device-contract lint (jepsen_tpu/analyze/devlint.py) + the
thread/lock-discipline T-codes — the CI gates and the per-code rules.

``test_shipped_routes_stage_clean`` is the tier-1 guard for the
tentpole: every registered kernel route (single-XLA, bucketed-batch,
mesh-sharded, pallas-fused) must stage abstractly at representative
dims with zero K-code errors.  ``test_thread_tier_is_clean`` is its
T-code twin over the service tiers.  The fixture tests pin each
K001-K007 / T001-T004 rule on a minimal positive case plus a
suppressed (or corrected) negative, so the lint itself cannot rot
silently.
"""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from jepsen_tpu.analyze.devlint import (  # noqa: E402
    DEVLINT_CODES,
    check_donation,
    check_span_args,
    lint_jaxpr,
    lint_trace_spans,
    representative_dims,
    run_devlint,
    span_kind_for_args,
    stage_route,
)
from jepsen_tpu.analyze.suites import (  # noqa: E402
    SUITE_CODES,
    lint_thread_tier,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def codes(diags):
    return {d.code for d in diags}


# ---------------------------------------------------------------------------
# the CI gates
# ---------------------------------------------------------------------------


def test_shipped_routes_stage_clean():
    """Every registered kernel route stages abstractly with zero
    K-code errors (non-live: no compilation, milliseconds per route)."""
    rep = run_devlint(live=False)
    assert sorted(rep["routes"]) == [
        "bucketed-batch", "mesh-sharded", "pallas-fused", "single-xla"]
    errs = [d for d in rep["diagnostics"] if d["severity"] == "error"]
    assert errs == [], "device-contract errors:\n" + "\n".join(
        f"  {d['code']} {d['message']}" for d in errs)


def test_thread_tier_is_clean():
    findings = lint_thread_tier()
    errs = [(f, d) for f, ds in findings.items() for d in ds
            if d.severity == "error"]
    assert errs == [], "thread-discipline errors:\n" + "\n".join(
        f"  {d.message}" for _f, d in errs)


#: route -> the span_kind its compile span must classify as
_ROUTE_SPAN_KIND = {"solo-xla": "solo", "solo-pallas": "solo",
                    "batch": "batch", "batch-sharded": "batch-sharded",
                    "window-sharded": "window-sharded"}


@pytest.mark.parametrize("route", sorted(_ROUTE_SPAN_KIND))
def test_committed_traces_satisfy_k007(fresh_trace, route):
    """A trace recorded just now on each kernel route holds a compile
    span of the route's span_kind, and every compile span in it
    carries exactly the newest cache-key coordinate set (K007)."""
    p = fresh_trace(route)
    with open(p) as f:
        doc = json.load(f)
    spans = [ev["args"] for ev in doc["traceEvents"]
             if ev.get("name") == "device.compile"]
    assert spans, "the route compiled nothing"
    assert {span_kind_for_args(a) for a in spans} == {
        _ROUTE_SPAN_KIND[route]}
    if route == "solo-pallas":
        assert {a["engine"] for a in spans} == {"pallas"}
    diags = lint_trace_spans(doc, name=os.path.basename(p))
    assert diags == [], f"{p}:\n" + "\n".join(
        f"  {d.message}" for d in diags)


def test_devlint_cli_exit_codes():
    out = subprocess.run(
        [sys.executable, "-m", "jepsen_tpu.analyze", "--devlint",
         "--json"],
        capture_output=True, text=True, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stdout + out.stderr
    payload = json.loads(out.stdout)
    assert payload["errors"] == 0
    assert len(payload["routes"]) == 4


def test_lint_suites_cli_includes_thread_tier():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "lint_suites.py"),
         "--threads", "--json"],
        capture_output=True, text=True, cwd=REPO)
    assert out.returncode == 0, out.stdout + out.stderr
    payload = json.loads(out.stdout)
    assert set(payload) == {"errors", "warnings", "files"}
    assert payload["errors"] == 0


def test_codes_are_documented():
    for code in DEVLINT_CODES:
        assert code.startswith("K")
    for code in ("T001", "T002", "T003", "T004"):
        assert code in SUITE_CODES


# ---------------------------------------------------------------------------
# K-code fixtures (staged toy kernels)
# ---------------------------------------------------------------------------


def _cb(v):
    return np.asarray(v, np.int32)


def test_k001_host_callback_in_loop():
    def f(x):
        def body(c, _):
            y = jax.pure_callback(
                _cb, jax.ShapeDtypeStruct((), jnp.int32), c)
            return c + y, None
        out, _ = jax.lax.scan(body, x, None, length=4)
        return out

    jaxpr = jax.make_jaxpr(f)(jnp.int32(1))
    assert "K001" in codes(lint_jaxpr(jaxpr, route_name="fix"))


def test_k001_suppressed_on_line():
    def f(x):
        def body(c, _):
            y = jax.pure_callback(  # devlint: ok — fixture
                _cb, jax.ShapeDtypeStruct((), jnp.int32), c)
            return c + y, None
        out, _ = jax.lax.scan(body, x, None, length=4)
        return out

    jaxpr = jax.make_jaxpr(f)(jnp.int32(1))
    assert "K001" not in codes(lint_jaxpr(jaxpr, route_name="fix"))


def test_k002_float_in_int_only_route():
    def f(x):
        return x.astype(jnp.float32) * jnp.float32(2)

    jaxpr = jax.make_jaxpr(f)(jnp.arange(4, dtype=jnp.int32))
    diags = lint_jaxpr(jaxpr, route_name="fix", int_only=True)
    assert "K002" in codes(diags)
    # a float-carrying route (pallas MXU matmuls) only bans 64-bit
    diags = lint_jaxpr(jaxpr, route_name="fix", int_only=False)
    assert "K002" not in codes(diags)


def test_k003_weak_type_invar():
    jaxpr = jax.make_jaxpr(lambda x, y: x + y)(
        jnp.arange(4, dtype=jnp.int32), 3)  # python scalar operand
    assert "K003" in codes(lint_jaxpr(jaxpr, route_name="fix"))
    jaxpr = jax.make_jaxpr(lambda x, y: x + y)(
        jnp.arange(4, dtype=jnp.int32),
        jnp.asarray(3, dtype=jnp.int32))
    assert "K003" not in codes(lint_jaxpr(jaxpr, route_name="fix"))


_DONATING = textwrap.dedent("""\
    import jax

    def get_kernel(model, dims):
        return jax.jit(step, donate_argnums=(6,))
""")

_DONATING_OK = textwrap.dedent("""\
    import jax

    def get_kernel(model, dims):
        return jax.jit(step, donate_argnums=(6,))  # devlint: ok
""")

_NON_DONATING = textwrap.dedent("""\
    import jax

    def get_kernel(model, dims):
        return jax.jit(step)
""")


def test_k004_donation_policy_both_directions():
    # jit donates, route says don't: the slice driver re-feeds the
    # pre-overflow carry after a frontier escalation
    diags = check_donation(_DONATING, "get_kernel",
                           donate_carry=False, route_name="fix")
    assert "K004" in codes(diags)
    # declared donation the jit never performs
    diags = check_donation(_NON_DONATING, "get_kernel",
                           donate_carry=True, route_name="fix")
    assert "K004" in codes(diags)
    # matching policy in both directions is clean
    assert check_donation(_DONATING, "get_kernel",
                          donate_carry=True, route_name="fix") == []
    assert check_donation(_NON_DONATING, "get_kernel",
                          donate_carry=False, route_name="fix") == []


def test_k004_suppressed_on_jit_line():
    diags = check_donation(_DONATING_OK, "get_kernel",
                           donate_carry=False, route_name="fix")
    assert "K004" not in codes(diags)


def test_k004_missing_getter_is_warning():
    diags = check_donation(_NON_DONATING, "get_missing",
                           donate_carry=False, route_name="fix")
    assert [d.severity for d in diags] == ["warning"]


def test_k005_dynamic_shape_fails_staging():
    import types

    def f(x):
        return jnp.nonzero(x)[0]  # data-dependent output shape

    route = types.SimpleNamespace(
        name="fix", build=lambda model, dims: (
            f, (jnp.arange(8, dtype=jnp.int32),)))
    model, dims = representative_dims()
    jaxpr, diags = stage_route(route, model, dims)
    assert jaxpr is None
    assert codes(diags) == {"K005"}


def test_k006_transfer_in_scan_body():
    def f(x):
        def body(c, _):
            jax.debug.print("level {}", c)
            return c + 1, None
        out, _ = jax.lax.scan(body, x, None, length=4)
        return out

    jaxpr = jax.make_jaxpr(f)(jnp.int32(0))
    assert "K006" in codes(lint_jaxpr(jaxpr, route_name="fix"))


# ---------------------------------------------------------------------------
# K007 — the static cache-key model
# ---------------------------------------------------------------------------


def _full_solo_args(**over):
    args = {"engine": "xla", "frontier": 8, "n_det_pad": 64,
            "n_crash_pad": 32, "window": 32, "k": 2,
            "masked": False, "masked_crash": False, "dedup": False,
            "vt": 8, "model": "register", "model_init": 0,
            "model_width": 1}
    args.update(over)
    return args


def test_k007_full_coordinate_set_passes_strict():
    assert check_span_args(_full_solo_args()) == []
    batch = _full_solo_args(batch=256)
    assert span_kind_for_args(batch) == "batch"
    assert check_span_args(batch) == []
    sharded = _full_solo_args(batch=32, sharded=True, shards=8)
    assert span_kind_for_args(sharded) == "batch-sharded"
    assert check_span_args(sharded) == []


def test_k007_missing_coord_fails_strict():
    args = _full_solo_args()
    del args["masked_crash"]
    fails = check_span_args(args)
    assert fails and "masked_crash" in fails[0]


def test_k007_legacy_generation_needs_non_strict():
    """A PR 15-generation span (engine + two dims) fails K007: there is
    no non-strict mode left to accept it."""
    legacy = {"engine": "xla", "frontier": 8, "n_det_pad": 64}
    fails = check_span_args(legacy)
    assert fails and "missing coords" in fails[0]
    doc = {"traceEvents": [{"name": "device.compile", "args": legacy}]}
    assert codes(lint_trace_spans(doc)) == {"K007"}


def test_k007_domain_violation_fails_even_with_full_keys():
    fails = check_span_args(_full_solo_args(window=17))
    assert any("window" in f for f in fails)
    fails = check_span_args(_full_solo_args(engine="cuda"))
    assert any("engine" in f for f in fails)


def test_k007_runtime_coords_are_excluded():
    args = _full_solo_args(cache="miss", persistent_cache=False)
    assert check_span_args(args) == []


# ---------------------------------------------------------------------------
# warmup loader reports K007 instead of silently defaulting
# ---------------------------------------------------------------------------


def test_warmup_trace_loader_reports_k007(tmp_path):
    from jepsen_tpu.fleet.warmup import load_shapes

    trace = tmp_path / "trace.json"
    trace.write_text(json.dumps({"traceEvents": [
        {"name": "device.compile", "args": {
            "frontier": 8}},  # fits no documented generation
    ]}))
    with pytest.raises(ValueError, match="K007"):
        load_shapes(str(trace))
    diags = []
    shapes = load_shapes(str(trace), diagnostics=diags)
    assert shapes == []
    assert codes(diags) == {"K007"}


def test_warmup_manifest_validates_against_static_model(tmp_path):
    from jepsen_tpu.fleet.warmup import load_shapes

    man = tmp_path / "shapes.json"
    man.write_text(json.dumps({"shapes": [
        {"n_det_pad": 64, "frontier": 8, "window": 17}]}))
    with pytest.raises(ValueError, match="window"):
        load_shapes(str(man))


@pytest.mark.parametrize("route", ["batch", "batch-sharded", "solo-xla",
                                   "window-sharded"])
def test_warm_boot_from_recorded_trace(fresh_trace, cold_kernel_cache,
                                      route):
    """Warm boot from a trace recorded just now: the loader takes every
    compile span strictly, a boot from an empty kernel cache compiles
    each shape once and verifies, and a second boot compiles nothing."""
    from jepsen_tpu.fleet.warmup import load_shapes, warm_boot

    shapes = load_shapes(fresh_trace(route))
    assert shapes
    if route.endswith("sharded"):
        assert all(s.shards >= 2 for s in shapes)
    if route.startswith("batch"):
        assert all(s.batch >= 1 for s in shapes)
    with cold_kernel_cache():
        rep = warm_boot(shapes)
        again = warm_boot(shapes)
    assert "k007" not in rep
    assert rep["verified"] is True
    assert rep["shapes"] == len(shapes)
    assert rep["compiled"] == len(shapes)
    assert again["verified"] is True
    assert again["compiled"] == 0


def test_warm_boot_refuses_drifted_shapes():
    from jepsen_tpu.fleet.warmup import WarmShape, warm_boot

    rep = warm_boot([WarmShape(n_det_pad=64, frontier=8, window=17)])
    assert rep["verified"] is False
    assert rep["shapes"] == 0
    assert rep["k007"]


# ---------------------------------------------------------------------------
# T-code fixtures (lint_thread_tier over a tmp file)
# ---------------------------------------------------------------------------


def _tlint(tmp_path, source):
    p = tmp_path / "fixture.py"
    p.write_text(textwrap.dedent(source))
    findings = lint_thread_tier([p])
    return [d for ds in findings.values() for d in ds]


def test_t001_unlocked_rmw_from_thread(tmp_path):
    diags = _tlint(tmp_path, """\
        import threading

        COUNT = 0

        def worker():
            global COUNT
            COUNT += 1

        def start():
            threading.Thread(target=worker).start()
    """)
    assert codes(diags) == {"T001"}


def test_t001_lock_or_suppression_clears_it(tmp_path):
    diags = _tlint(tmp_path, """\
        import threading

        COUNT = 0
        LOCK = threading.Lock()

        def worker():
            global COUNT
            with LOCK:
                COUNT += 1

        def start():
            threading.Thread(target=worker).start()
    """)
    assert diags == []
    diags = _tlint(tmp_path, """\
        import threading

        COUNT = 0

        def worker():
            global COUNT
            COUNT += 1  # threadlint: ok — fixture

        def start():
            threading.Thread(target=worker).start()
    """)
    assert diags == []


def test_t001_check_then_act(tmp_path):
    diags = _tlint(tmp_path, """\
        import threading

        class Box:
            def worker(self):
                if self.slot is None:
                    self.slot = 1

            def start(self):
                threading.Thread(target=self.worker).start()
    """)
    assert codes(diags) == {"T001"}


def test_t002_bare_acquire_without_finally(tmp_path):
    diags = _tlint(tmp_path, """\
        import threading

        LOCK = threading.Lock()

        def worker():
            LOCK.acquire()
            step()
            LOCK.release()

        def start():
            threading.Thread(target=worker).start()
    """)
    assert "T002" in codes(diags)
    diags = _tlint(tmp_path, """\
        import threading

        LOCK = threading.Lock()

        def worker():
            LOCK.acquire()
            try:
                step()
            finally:
                LOCK.release()

        def start():
            threading.Thread(target=worker).start()
    """)
    assert "T002" not in codes(diags)


def test_t003_flock_write_without_fsync(tmp_path):
    diags = _tlint(tmp_path, """\
        import threading

        def worker(fh):
            with _locked():
                fh.write("entry")

        def start():
            threading.Thread(target=worker).start()
    """)
    assert "T003" in codes(diags)
    diags = _tlint(tmp_path, """\
        import os
        import threading

        def worker(fh):
            with _locked():
                fh.write("entry")
                os.fsync(fh.fileno())

        def start():
            threading.Thread(target=worker).start()
    """)
    assert "T003" not in codes(diags)


def test_t004_span_without_run_pin(tmp_path):
    diags = _tlint(tmp_path, """\
        import threading

        def worker():
            with obs.span("prep", keys=3):
                step()

        def start():
            threading.Thread(target=worker).start()
    """)
    assert "T004" in codes(diags)
    diags = _tlint(tmp_path, """\
        import threading

        def worker(run_pin):
            with obs.span("prep", run=run_pin, keys=3):
                step()

        def start():
            threading.Thread(target=worker).start()
    """)
    assert "T004" not in codes(diags)


def test_caller_holds_lock_fixpoint(tmp_path):
    """A helper whose every in-tier call site holds a lock is as
    protected as one taking the lock itself (stream/service.py's
    _handle pattern)."""
    diags = _tlint(tmp_path, """\
        import threading

        class Svc:
            def _apply(self):
                self.n += 1

            def handle_line(self):
                with self._lock:
                    self._apply()

            def start(self):
                threading.Thread(target=self.handle_line).start()
    """)
    assert diags == []


# ---------------------------------------------------------------------------
# regression pins for the defects the lints actually found
# ---------------------------------------------------------------------------


def test_admission_decide_is_serialized():
    """fleet/admission.py T001 fix: decide() runs on router handler
    threads; the scale-signal max-updates and the spawn damper
    check-then-act must hold the controller lock."""
    import threading

    from jepsen_tpu.fleet.admission import AdmissionController

    ctl = AdmissionController()
    assert isinstance(ctl._lock, type(threading.Lock()))
    n = 64
    sigs = [{"ops_total": float(i), "shed_total": 0.0}
            for i in range(n)]
    threads = [threading.Thread(
        target=lambda s=s: [ctl.decide(s) for _ in range(10)])
        for s in sigs]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # the max-update under contention must equal the true max, and
    # every decision must have been counted
    assert ctl._last_ops == float(n - 1)
    assert sum(ctl.decisions.values()) == n * 10


def test_env_knob_cache_clears_before_force_drop(monkeypatch):
    """obs trace/telemetry T001 fix: enable(None) must leave no stale
    cached env read visible after the force is gone."""
    from jepsen_tpu.obs import trace

    monkeypatch.delenv("JEPSEN_TPU_TRACE", raising=False)
    trace.enable(True)
    assert trace.enabled() is True
    trace.enable(None)
    assert trace.enabled() is False  # re-read, not stale cache
