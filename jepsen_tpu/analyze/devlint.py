"""Device-contract lint — K-codes over staged kernel jaxprs.

The bench contract (PR 10 onward, executable since PR 15) catches
recompiles, mid-search host transfers, and cache-key drift *after* a
bench run regresses.  This module is the static counterpart: it
abstractly stages every kernel route the checker can dispatch —
single-device XLA, bucketed batch, mesh-sharded, pallas fused,
enumerated from :data:`jepsen_tpu.checker.linearizable.KERNEL_ROUTES`
— over representative :class:`SearchDims`, then walks the resulting
jaxprs for the device-contract violations the runtime gates would only
see as a regressed number.  ``jax.make_jaxpr`` traces without
compiling, so the whole sweep is a few seconds on CPU and runs in
tier-1 (tests/test_devlint.py) and as a ``bench.py --trace``
preflight.

K-code reference (docs/analyze.md has the prose version):

  K001  host callback primitive (pure_callback / io_callback) staged
        inside the level loop — every BFS level would sync to host
  K002  float64 / 64-bit dtype, or any float in an int-only route —
        dtype widening doubles device bytes and splits the cache key
  K003  weak-type input aval: a python scalar leaked into the traced
        operands, so numerically identical calls re-trace and split
        the kernel cache key
  K004  carry-donation policy break: the route's cache getter's
        ``jax.jit`` donates buffers the slice driver still needs (or a
        donate_carry=True route whose jit never donates)
  K005  dynamic-shape primitive — staging raised a concretization /
        data-dependent-shape error, so the kernel cannot stage at all
  K006  effectful host round-trip (debug prints, ordered callbacks)
        inside the scan body — a device→host transfer per level
  K007  compile-span cache-key coords missing or drifted versus the
        static model below — ``fleet/warmup.py`` warm-boot and the
        committed ``BENCH_trace_*.json`` recordings round-trip kernels
        through exactly these coords, so drift means silent zero-miss
        -verify failures

Suppression: the staged checks (K001/K002/K003/K006) attribute
findings to source lines via the jaxpr's ``source_info``; a
``devlint: ok`` comment on the flagged line suppresses it, same
contract as ``suite-lint: ok`` / ``threadlint: ok``.  K004 is
AST-level and honours the comment on the ``jax.jit`` call line.
Suppressions are for *documented* false positives only.

Wired into: ``python -m jepsen_tpu.analyze --devlint`` (CLI),
``tools/lint_suites.py --json`` (suite sweep), ``tools/obs_guard.py``
(K007 over committed trace compile spans), and ``bench.py --trace``
preflight.
"""

from __future__ import annotations

import ast
import importlib
import linecache
from typing import Any, Iterable

from .lint import Diagnostic

DEVLINT_CODES = {
    "K001": "host callback primitive inside the level loop",
    "K002": "float64/dtype-widening leak in kernel dataflow",
    "K003": "weak-type or python-scalar leak splitting the kernel "
            "cache key",
    "K004": "carry-donation policy break in the route's jit call",
    "K005": "dynamic-shape primitive (kernel fails to stage)",
    "K006": "device->host transfer inside the scan body",
    "K007": "compile-span cache-key coords missing/drifted vs the "
            "static model",
}

#: primitives that round-trip to the host per invocation — fatal
#: inside the level loop (K001)
_CALLBACK_PRIMS = {"pure_callback", "io_callback", "callback",
                   "python_callback"}
#: effectful primitives that imply a device->host transfer when staged
#: inside the loop body (K006) — debug prints are the common leak
_TRANSFER_PRIMS = {"debug_callback", "debug_print", "device_put"}
#: loop-body primitives: anything staged under one of these runs once
#: per BFS level (or per op), not once per kernel call
_LOOP_PRIMS = {"while", "scan"}


# ---------------------------------------------------------------------------
# K007 — the static cache-key model
# ---------------------------------------------------------------------------

#: coords every route's compile span must carry (newest generation):
#: the full kernel cache key, so a recorded span alone reconstructs
#: the exact compiled kernel (fleet/warmup.py warm boot)
BASE_COORDS = frozenset({
    "engine", "frontier", "n_det_pad", "n_crash_pad", "window", "k",
    "masked", "masked_crash", "dedup", "vt",
    "model", "model_init", "model_width",
})

#: attrs ``obs/telemetry.compile_span`` itself adds — runtime facts,
#: not cache-key coords, so excluded from the model comparison
RUNTIME_COORDS = frozenset({"cache", "persistent_cache"})

#: span_kind -> required coord set, newest generation.  span_kind is
#: declared per route (KernelRoute.span_kind) and recoverable from a
#: recorded span's args (see :func:`span_kind_for_args`).
CACHE_KEY_MODEL = {
    "solo": BASE_COORDS,
    "batch": BASE_COORDS | {"batch"},
    "batch-sharded": BASE_COORDS | {"batch", "sharded", "shards"},
    "window-sharded": BASE_COORDS | {"shards"},
}

#: coord sets earlier PRs emitted, oldest first — committed
#: ``BENCH_trace_*.json`` recordings predating the full model are
#: validated against these; LIVE staging (and any trace recorded from
#: now on) must match the newest generation exactly
LEGACY_GENERATIONS = (
    # PR 15: first span accounting — engine + two dims only
    frozenset({"engine", "frontier", "n_det_pad"}),
    # PR 16 fleet tier: warm-boot needed window/k/crash pad
    frozenset({"engine", "frontier", "n_det_pad", "n_crash_pad",
               "window", "k"}),
)


def span_kind_for_args(args: dict) -> str:
    """Classify a recorded ``device.compile`` span into the coord
    model's span_kind.  Legacy spans missing the batch/sharded markers
    classify as solo — their generation check still passes."""
    if args.get("engine") == "device-sharded":
        return "window-sharded"
    if "sharded" in args or args.get("shards") is not None:
        return "batch-sharded"
    if "batch" in args:
        return "batch"
    return "solo"


def _coord_domain_errors(args: dict) -> list[str]:
    """Value-domain checks for whatever coords are present — a coord
    carrying an impossible value is drift even when the key set
    matches."""
    errs = []

    def _int(k):
        v = args.get(k)
        if v is None:
            return None
        try:
            return int(v)
        except (TypeError, ValueError):
            errs.append(f"coord {k}={v!r} is not an integer")
            return None

    w = _int("window")
    if w is not None and (w <= 0 or w % 32):
        errs.append(f"window={w} not a positive multiple of 32")
    cp = _int("n_crash_pad")
    if cp is not None and (cp < 0 or cp % 32 or cp > 64):
        errs.append(f"n_crash_pad={cp} not a multiple of 32 in [0,64]")
    for k, lo in (("frontier", 1), ("n_det_pad", 1), ("k", 1),
                  ("batch", 1), ("shards", 1), ("model_width", 1)):
        v = _int(k)
        if v is not None and v < lo:
            errs.append(f"coord {k}={v} < {lo}")
    eng = args.get("engine")
    if eng is not None and eng not in ("xla", "pallas",
                                       "device-sharded"):
        errs.append(f"unknown engine {eng!r}")
    mdl = args.get("model")
    if mdl is not None and not isinstance(mdl, str):
        errs.append(f"coord model={mdl!r} is not a name")
    return errs


def check_span_args(args: dict, *, kind: str | None = None,
                    strict: bool = True) -> list[str]:
    """K007 core: validate one ``device.compile`` span's args against
    the static cache-key model.

    ``strict=True`` (live staging, bench preflight, newly recorded
    traces): the coord key set must equal the newest generation for
    its span_kind.  ``strict=False`` (committed historical traces): a
    legacy generation's key set is also accepted.  Returns a list of
    failure strings, empty when clean."""
    keys = frozenset(args) - RUNTIME_COORDS
    if kind is None:
        kind = span_kind_for_args(args)
    required = CACHE_KEY_MODEL.get(kind)
    if required is None:
        return [f"unknown span_kind {kind!r}"]
    failures = []
    if keys != required:
        legacy_ok = (not strict) and keys in LEGACY_GENERATIONS
        if not legacy_ok:
            missing = sorted(required - keys)
            extra = sorted(keys - required)
            parts = []
            if missing:
                parts.append(f"missing coords {missing}")
            if extra:
                parts.append(f"unmodelled coords {extra}")
            failures.append(f"[{kind}] " + ", ".join(parts))
    failures.extend(_coord_domain_errors(args))
    return failures


# ---------------------------------------------------------------------------
# staging + jaxpr walking
# ---------------------------------------------------------------------------


def representative_dims(model=None):
    """The SearchDims every route is staged at: small enough to trace
    in milliseconds, big enough to exercise padding, crash lanes and
    the windowed frontier."""
    from ..checker.linearizable import SearchDims
    from ..models import register

    m = model if model is not None else register(0)
    return m, SearchDims(n_det_pad=64, n_crash_pad=32, window=32, k=2,
                         state_width=m.state_width, frontier=8)


def _subjaxprs(eqn) -> Iterable[Any]:
    """Nested jaxprs inside one equation's params (while/scan bodies,
    cond branches, pjit/pallas callees)."""
    for v in eqn.params.values():
        vals = v if isinstance(v, (list, tuple)) else (v,)
        for sub in vals:
            inner = getattr(sub, "jaxpr", sub)
            if hasattr(inner, "eqns"):
                yield inner


def walk_jaxpr(jaxpr, path=()):
    """Yield ``(eqn, path)`` for every equation, depth-first; ``path``
    is the tuple of enclosing primitive names (so ``"scan" in path``
    means inside a loop body)."""
    for eqn in jaxpr.eqns:
        yield eqn, path
        sub_path = path + (eqn.primitive.name,)
        for inner in _subjaxprs(eqn):
            yield from walk_jaxpr(inner, sub_path)


def _eqn_line(eqn) -> tuple[str, int] | None:
    """(filename, lineno) of the user frame that staged this equation,
    when jax kept one — the anchor for ``devlint: ok`` suppression."""
    from jax._src import source_info_util

    fr = source_info_util.user_frame(eqn.source_info.traceback)
    if fr is None:
        return None
    line = getattr(fr, "start_line", None) or getattr(fr, "line_num", 0)
    return fr.file_name, int(line or 0)


def _suppressed(eqn) -> bool:
    loc = _eqn_line(eqn)
    if loc is None:
        return False
    return "devlint: ok" in linecache.getline(loc[0], loc[1])


def _at(eqn) -> str:
    loc = _eqn_line(eqn)
    return f" at {loc[0]}:{loc[1]}" if loc else ""


def _in_loop(path) -> bool:
    return any(p in _LOOP_PRIMS for p in path)


def lint_jaxpr(jaxpr, *, route_name: str = "<kernel>",
               int_only: bool = True) -> list[Diagnostic]:
    """Walk one staged (closed or open) jaxpr for K001/K002/K003/K006.

    ``int_only`` is the route's dtype contract: the search kernels
    pack everything into int32/bool lanes, so ANY float is a widening
    leak; routes that legitimately carry floats only get the 64-bit
    check."""
    inner = getattr(jaxpr, "jaxpr", jaxpr)
    diags: list[Diagnostic] = []

    # K003 — weak-type avals on the traced inputs: a python scalar
    # reached the operand list, so every numerically-distinct call
    # site re-traces under a different cache key
    for i, var in enumerate(inner.invars):
        aval = getattr(var, "aval", None)
        if aval is not None and getattr(aval, "weak_type", False):
            diags.append(Diagnostic(
                "K003", "error",
                f"{route_name}: traced input #{i} has a weak-type aval "
                f"({aval.dtype}) — a python scalar leaked into the "
                f"kernel operands and splits the jit cache key",
                index=i, f=route_name))

    for eqn, path in walk_jaxpr(inner):
        prim = eqn.primitive.name
        in_loop = _in_loop(path)
        if prim in _CALLBACK_PRIMS and in_loop:
            if not _suppressed(eqn):
                diags.append(Diagnostic(
                    "K001", "error",
                    f"{route_name}: host callback '{prim}' staged "
                    f"inside the level loop (path {'>'.join(path)})"
                    f"{_at(eqn)} — every BFS level syncs to host",
                    f=route_name))
            continue
        if prim in _TRANSFER_PRIMS and in_loop:
            if not _suppressed(eqn):
                diags.append(Diagnostic(
                    "K006", "error",
                    f"{route_name}: effectful '{prim}' inside the "
                    f"scan body{_at(eqn)} — a device->host transfer "
                    f"per level",
                    f=route_name))
            continue
        # K002 — dtype scan over the equation's outputs
        for var in eqn.outvars:
            aval = getattr(var, "aval", None)
            dt = getattr(aval, "dtype", None)
            if dt is None:
                continue
            name = str(dt)
            wide = name in ("float64", "int64", "uint64", "complex128")
            floaty = int_only and name.startswith(("float", "complex",
                                                   "bfloat"))
            if (wide or floaty) and not _suppressed(eqn):
                why = ("64-bit dtype" if wide
                       else "float dtype in an int-only route")
                diags.append(Diagnostic(
                    "K002", "error",
                    f"{route_name}: '{prim}' produces {name}{_at(eqn)}"
                    f" — {why} widens the device dataflow",
                    f=route_name))
                break  # one K002 per equation is enough signal
    return diags


def stage_route(route, model=None, dims=None):
    """Abstractly stage one route at representative dims.  Returns
    ``(closed_jaxpr | None, diagnostics)`` — staging failure IS the
    K005 finding."""
    import jax

    if model is None or dims is None:
        model, dims = representative_dims(model)
    try:
        fn, args = route.build(model, dims)
        jaxpr = jax.make_jaxpr(fn)(*args)
    except Exception as exc:  # ConcretizationTypeError & friends
        kind = type(exc).__name__
        msg = str(exc).splitlines()[0][:200]
        return None, [Diagnostic(
            "K005", "error",
            f"{route.name}: kernel fails to stage abstractly "
            f"({kind}: {msg}) — a data-dependent shape or python "
            f"control flow on traced values",
            f=route.name)]
    return jaxpr, []


# ---------------------------------------------------------------------------
# K004 — donation policy (AST over the route's cache getter)
# ---------------------------------------------------------------------------


def _jit_calls(fn_node: ast.AST):
    for node in ast.walk(fn_node):
        if isinstance(node, ast.Call):
            f = node.func
            name = None
            if isinstance(f, ast.Attribute):
                name = f.attr
            elif isinstance(f, ast.Name):
                name = f.id
            if name == "jit":
                yield node


def check_donation(source: str, getter: str, *,
                   donate_carry: bool, route_name: str = "<route>",
                   filename: str = "<source>") -> list[Diagnostic]:
    """K004 over one module's source: find ``getter``'s ``jax.jit``
    calls and compare ``donate_argnums`` presence against the route's
    declared carry-donation policy.  Both directions are contract
    breaks: donating buffers the slice driver re-feeds after a
    frontier escalation (declared False, jit donates), and declaring
    donation that the jit never performs (declared True, no
    donate_argnums)."""
    try:
        tree = ast.parse(source)
    except SyntaxError as exc:
        return [Diagnostic(
            "K004", "warning",
            f"{route_name}: cannot parse {filename} for the donation "
            f"check ({exc})", f=route_name)]
    lines = source.splitlines()

    def suppressed(lineno: int) -> bool:
        return (0 < lineno <= len(lines)
                and "devlint: ok" in lines[lineno - 1])

    fn = next((n for n in ast.walk(tree)
               if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
               and n.name == getter), None)
    if fn is None:
        return [Diagnostic(
            "K004", "warning",
            f"{route_name}: getter '{getter}' not found in {filename}",
            f=route_name)]
    diags = []
    donated_anywhere = False
    for call in _jit_calls(fn):
        donates = any(kw.arg in ("donate_argnums", "donate_argnames")
                      for kw in call.keywords)
        donated_anywhere = donated_anywhere or donates
        if donates and not donate_carry and not suppressed(call.lineno):
            diags.append(Diagnostic(
                "K004", "error",
                f"{route_name}: {getter}'s jax.jit at {filename}:"
                f"{call.lineno} donates buffers but the route declares "
                f"donate_carry=False — the slice driver re-feeds the "
                f"pre-overflow carry after a frontier escalation",
                index=call.lineno, f=route_name))
    if donate_carry and not donated_anywhere:
        diags.append(Diagnostic(
            "K004", "error",
            f"{route_name}: route declares donate_carry=True but no "
            f"jax.jit call in {getter} ({filename}) donates",
            f=route_name))
    return diags


def lint_route_source(route) -> list[Diagnostic]:
    """K004 for a registered route: load its module's source and run
    the donation check on the declared getter."""
    import inspect

    try:
        mod = importlib.import_module(route.module)
        source = inspect.getsource(mod)
        filename = inspect.getsourcefile(mod) or route.module
    except Exception as exc:
        return [Diagnostic(
            "K004", "warning",
            f"{route.name}: cannot load {route.module} source ({exc})",
            f=route.name)]
    return check_donation(source, route.getter,
                          donate_carry=route.donate_carry,
                          route_name=route.name, filename=filename)


# ---------------------------------------------------------------------------
# live span capture — K007 against the real cache getters
# ---------------------------------------------------------------------------

_DEVLINT_RUN = "__devlint__"


def capture_compile_spans(route, model=None, dims=None) -> list[dict]:
    """Request the route through its REAL cache getter under a private
    trace recorder and return the ``device.compile`` spans it emitted.
    An already-warm cache emits none (the miss path never runs) —
    callers treat that as vacuous, not clean."""
    from ..obs import trace as _trace

    if model is None or dims is None:
        model, dims = representative_dims(model)
    prev_forced = _trace._forced
    prev_run = _trace.current_run()
    _trace.enable(True)
    _trace.set_run(_DEVLINT_RUN)
    try:
        route.request(model, dims)
        rec = _trace.recorder(_DEVLINT_RUN)
        return [s for s in rec.spans() if s["name"] == "device.compile"]
    finally:
        _trace.set_run(prev_run)
        _trace.enable(prev_forced)
        _trace.drop_recorder(_DEVLINT_RUN)


def lint_compile_spans(route, spans: list[dict]) -> list[Diagnostic]:
    """K007 over live-captured spans: strict (newest-generation)
    coord check against the route's declared span_kind."""
    diags = []
    for s in spans:
        for fail in check_span_args(s.get("args", {}),
                                    kind=route.span_kind, strict=True):
            diags.append(Diagnostic(
                "K007", "error",
                f"{route.name}: device.compile span coords drift vs "
                f"the static cache-key model: {fail}",
                f=route.name))
    return diags


def lint_trace_spans(trace_obj: dict, *, name: str = "<trace>"
                     ) -> list[Diagnostic]:
    """K007 over one committed Chrome-trace JSON object
    (``BENCH_trace_*.json``): every ``device.compile`` event's args
    must match the static model, legacy generations allowed.  Traces
    with no compile spans pass vacuously (a fully warm recording)."""
    diags = []
    for ev in trace_obj.get("traceEvents", ()):
        if ev.get("name") != "device.compile":
            continue
        args = ev.get("args", {}) or {}
        for fail in check_span_args(args, strict=False):
            diags.append(Diagnostic(
                "K007", "error",
                f"{name}: committed compile span drifts vs the static "
                f"cache-key model: {fail}"))
    return diags


# ---------------------------------------------------------------------------
# the sweep
# ---------------------------------------------------------------------------


def lint_kernel_routes(routes=None, *, live: bool = False,
                       model=None) -> list[Diagnostic]:
    """Stage + walk every registered kernel route.  ``live=True`` also
    requests each route through its real getter and K007-checks the
    emitted compile spans (meaningful in a fresh process — warm caches
    emit no span)."""
    from ..checker.linearizable import kernel_routes

    if routes is None:
        routes = kernel_routes()
    m, dims = representative_dims(model)
    diags: list[Diagnostic] = []
    for name in sorted(routes):
        route = routes[name]
        jaxpr, stage_diags = stage_route(route, m, dims)
        diags.extend(stage_diags)
        if jaxpr is not None:
            diags.extend(lint_jaxpr(jaxpr, route_name=route.name,
                                    int_only=route.int_only))
        diags.extend(lint_route_source(route))
        if live:
            spans = capture_compile_spans(route, m, dims)
            diags.extend(lint_compile_spans(route, spans))
    return diags


def run_devlint(*, live: bool = False) -> dict:
    """The CLI/test entry: sweep all routes, return the result block
    ``{"routes": [names], "diagnostics": [...], "errors": n,
    "warnings": n}``."""
    from ..checker.linearizable import kernel_routes

    routes = kernel_routes()
    diags = lint_kernel_routes(routes, live=live)
    return {
        "routes": sorted(routes),
        "diagnostics": [d.to_dict() for d in diags],
        "errors": sum(1 for d in diags if d.severity == "error"),
        "warnings": sum(1 for d in diags if d.severity == "warning"),
    }
