"""Warm-boot: compile the steady-state bucket kernels BEFORE a worker
is admitted to the fleet.

A cold ``stream.service`` worker pays the 1.4-2.4s-per-kernel XLA
compile tax on its first runs — exactly the runs the router just
routed at it because it looked healthy.  The warm-boot gate inverts
that: at worker start, :func:`warm_boot` compiles every kernel shape
the steady state needs (shapes read from the ``device.compile`` spans
of a trace recorded with ``obs.write_trace``, or from an explicit
shape manifest) and **verifies** the warmth by re-requesting each
kernel and asserting a zero miss delta on
``checker.linearizable.KERNEL_CACHE_STATS``.  Only a verified worker
is admitted (fleet/__main__.py parses the report line stream/__main__
prints).

``jax.jit`` is lazy — merely building the jitted callable compiles
nothing.  Warm-boot therefore *invokes* each kernel once on a minimal
padded search (one step at the shape's full dims) and blocks until
ready; the resulting executable lands in the in-process kernel cache
and, when a persistent XLA compile cache is configured, on disk where
future worker boots skip the trace+compile entirely (the report's
``persistent_cache`` field says which regime you're in).

Shape manifest format (JSON)::

    {"shapes": [{"model": ["register", 0, 1], "n_det_pad": 1024,
                 "n_crash_pad": 32, "window": 32, "k": 4,
                 "frontier": 128}, ...]}

Optional per-shape fields ``batch`` (total lane count — warms the
vmapped batch kernel instead of the solo one) and ``shards`` (wraps it
in ``shard_map`` over that many local devices: the bucketed mesh
scheduler's steady-state shapes).  ``device.compile`` spans from
sharded runs carry both, so a trace recorded from a sharded run
round-trips into exactly the kernel set the scheduler will request.

Trace format: a telemetry trace (``{"traceEvents": [...]}``) whose
``device.compile`` spans carry the full cache-key coordinate set the
kernel getters stamp (``engine``, the dims, the phase-2 flags and the
model descriptor).

Every loaded shape is validated against the **static cache-key model**
(:func:`jepsen_tpu.analyze.devlint.check_span_args` — the same K007
contract live kernel staging is held to).  A span or
manifest entry whose coordinates drifted from the kernel cache key
used to be *silently dropped or defaulted*, which surfaced much later
as an unexplained zero-miss-verify failure (warm boot compiled the
wrong kernel set and the steady state paid fresh compiles anyway).
Now it is a loud K007: the loaders raise ``ValueError`` naming the bad
span, or — when the caller passes ``diagnostics=[]`` — append
:class:`~jepsen_tpu.analyze.lint.Diagnostic` objects and skip only the
offending shapes.
"""

from __future__ import annotations

import dataclasses
import json
import time

#: steady-state defaults for manifest entries that leave them out
DEFAULT_WINDOW = 32
DEFAULT_N_CRASH_PAD = 32
DEFAULT_K = 4
DEFAULT_FRONTIER = 64
DEFAULT_MODEL = ("register", 0, 1)


@dataclasses.dataclass(frozen=True)
class WarmShape:
    """One kernel shape to compile at boot (mirrors SearchDims plus
    the model and phase-2 flags of the kernel cache key)."""

    model: tuple = DEFAULT_MODEL  # (name, init, width)
    n_det_pad: int = 64
    n_crash_pad: int = DEFAULT_N_CRASH_PAD
    window: int = DEFAULT_WINDOW
    k: int = DEFAULT_K
    frontier: int = DEFAULT_FRONTIER
    masked: bool = False
    masked_crash: bool = False
    dedup: bool = False
    vt: int = 8
    #: batch > 0 warms the vmapped BATCH kernel at that total lane
    #: count (0 = the solo kernel); shards > 0 additionally wraps it
    #: in shard_map over that many local devices — the steady-state
    #: shapes the bucketed mesh scheduler runs
    batch: int = 0
    shards: int = 0


def _shape_span_args(s: WarmShape) -> dict:
    """A WarmShape rendered as the ``device.compile`` span-args dict
    its warmed kernel will stamp — the shared currency between this
    loader and devlint's static cache-key model."""
    args = {
        "engine": "xla",
        "frontier": s.frontier, "n_det_pad": s.n_det_pad,
        "n_crash_pad": s.n_crash_pad, "window": s.window, "k": s.k,
        "masked": s.masked, "masked_crash": s.masked_crash,
        "dedup": s.dedup, "vt": s.vt,
        "model": s.model[0], "model_init": s.model[1],
        "model_width": s.model[2],
    }
    if s.batch:
        args["batch"] = s.batch
    if s.shards:
        args["sharded"] = True
        args["shards"] = s.shards
        # span convention: sharded spans record PER-SHARD lanes
        args["batch"] = max(1, s.batch // s.shards)
    return args


def _k007(diagnostics, where: str, errs: list[str]):
    """Report one shape's cache-key drift: append K007 diagnostics to
    ``diagnostics`` when the caller collects them, raise otherwise —
    the drift must never again surface only as a warm boot that
    compiles the wrong kernel set."""
    from ..analyze.lint import Diagnostic

    if diagnostics is None:
        raise ValueError(
            f"K007 {where}: cache-key coordinates drifted from the "
            f"static model (analyze/devlint.py): " + "; ".join(errs))
    for e in errs:
        diagnostics.append(Diagnostic("K007", "error", f"{where}: {e}"))


def validate_shapes(shapes, *,
                    diagnostics: list | None = None) -> list[WarmShape]:
    """Filter ``shapes`` to the ones whose coordinates satisfy the
    static cache-key model; drifted shapes raise (or, with
    ``diagnostics``, are reported as K007 and dropped)."""
    from ..analyze.devlint import check_span_args

    good = []
    for i, s in enumerate(shapes):
        errs = check_span_args(_shape_span_args(s))
        if errs:
            _k007(diagnostics, f"warm shape #{i} ({s.model[0]})", errs)
            continue
        good.append(s)
    return good


def shapes_from_manifest(doc: dict, *,
                         diagnostics: list | None = None
                         ) -> list[WarmShape]:
    shapes = []
    for s in doc.get("shapes", []):
        m = s.get("model", list(DEFAULT_MODEL))
        shapes.append(WarmShape(
            model=(str(m[0]), int(m[1]) if len(m) > 1 else 0,
                   int(m[2]) if len(m) > 2 else 1),
            n_det_pad=int(s.get("n_det_pad", 64)),
            n_crash_pad=int(s.get("n_crash_pad",
                                  DEFAULT_N_CRASH_PAD)),
            window=int(s.get("window", DEFAULT_WINDOW)),
            k=int(s.get("k", DEFAULT_K)),
            frontier=int(s.get("frontier", DEFAULT_FRONTIER)),
            masked=bool(s.get("masked", False)),
            masked_crash=bool(s.get("masked_crash", False)),
            dedup=bool(s.get("dedup", False)),
            vt=int(s.get("vt", 8)),
            batch=int(s.get("batch", 0)),
            shards=int(s.get("shards", 0)),
        ))
    return validate_shapes(shapes, diagnostics=diagnostics)


def shapes_from_trace(doc: dict, *,
                      diagnostics: list | None = None
                      ) -> list[WarmShape]:
    """The kernel shapes a recorded campaign actually compiled: every
    ``device.compile`` span in the trace, deduplicated.

    A span whose cache-key coordinates fail the static model would
    reconstruct a kernel the steady state never requests, so it is
    K007: raised, or reported-and-skipped when the caller passes
    ``diagnostics``."""
    from ..analyze.devlint import check_span_args

    out = []
    seen = set()
    n_span = 0
    for ev in doc.get("traceEvents", []):
        if ev.get("name") != "device.compile":
            continue
        args = ev.get("args", {}) or {}
        n_span += 1
        errs = check_span_args(args)
        if errs:
            _k007(diagnostics, f"device.compile span #{n_span}", errs)
            continue
        # sharded spans record PER-SHARD lanes + the shard count; the
        # batch kernel getter wants the total lane axis back
        shards = int(args.get("shards", 0) or 0)
        batch = int(args.get("batch", 0) or 0)
        s = WarmShape(
            model=(str(args["model"]), int(args["model_init"]),
                   int(args["model_width"])),
            n_det_pad=int(args["n_det_pad"]),
            n_crash_pad=int(args["n_crash_pad"]),
            window=int(args["window"]),
            k=int(args["k"]),
            frontier=int(args["frontier"]),
            masked=bool(args["masked"]),
            masked_crash=bool(args["masked_crash"]),
            dedup=bool(args["dedup"]),
            vt=int(args["vt"]),
            batch=batch * shards if shards else batch,
            shards=shards,
        )
        if s not in seen:
            seen.add(s)
            out.append(s)
    return out


def load_shapes(path: str, *,
                diagnostics: list | None = None) -> list[WarmShape]:
    """Sniff ``path``: a shape manifest (``{"shapes": [...]}``) or a
    recorded telemetry trace (``{"traceEvents": [...]}``).  Shapes are
    K007-validated against the static cache-key model — see the module
    docstring for the raise-vs-``diagnostics`` contract."""
    with open(path) as f:
        doc = json.load(f)
    if "shapes" in doc:
        return shapes_from_manifest(doc, diagnostics=diagnostics)
    if "traceEvents" in doc:
        return shapes_from_trace(doc, diagnostics=diagnostics)
    raise ValueError(
        f"{path}: neither a shape manifest ({{'shapes': [...]}}) nor "
        f"a telemetry trace ({{'traceEvents': [...]}})")


def _tiny_seq(model):
    """A minimal one-op history the model accepts — enough to invoke
    the kernel once at full padded dims."""
    from ..history import encode_ops, invoke_op, ok_op

    fc = model.f_codes
    try:
        names = list(fc)
    except TypeError:  # _AnyFCodes (noop model): accepts anything
        names = ["write"]
    for cand in ("write", "enqueue", "acquire"):
        if cand in names:
            f = cand
            break
    else:
        f = names[0]
    v = 1 if f in ("write", "enqueue") else None
    return encode_ops([invoke_op(0, f, v), ok_op(0, f, v)],
                      fc)


def _compile_one(shape: WarmShape, *, telemetry: bool):
    """Build + INVOKE one kernel at the shape's dims (jit is lazy —
    invocation is what compiles), blocking until the executable is
    ready.  Returns ``(dims, model, rerequest)`` where ``rerequest``
    re-asks the cache for the SAME kernel (warm_boot's verify pass)."""
    import jax
    import jax.numpy as jnp

    from ..checker import linearizable as lin
    from ..decompose.schedule import model_from_descriptor

    name, init, width = shape.model
    model = model_from_descriptor((name, (init,), width))
    dims = lin.SearchDims(
        n_det_pad=max(64, int(shape.n_det_pad)),
        n_crash_pad=max(32, int(shape.n_crash_pad)),
        window=max(32, int(shape.window)),
        k=max(1, int(shape.k)),
        state_width=model.state_width,
        frontier=max(8, int(shape.frontier)),
    )
    es = lin.encode_search(_tiny_seq(model))
    esp = lin.pad_search(es, dims.n_det_pad, dims.n_crash_pad)
    if shape.batch:
        b = max(1, int(shape.batch))
        mesh = axis = None
        if shape.shards:
            import numpy as np
            from jax.sharding import Mesh

            devs = jax.devices()
            if len(devs) >= shape.shards and b % shape.shards == 0:
                mesh = Mesh(np.array(devs[:shape.shards]), ("shard",))
                axis = "shard"
        if mesh is not None:
            def getter():
                return lin.get_sharded_batch_kernel(
                    model, dims, batch=b, mesh=mesh, axis=axis,
                    masked=shape.masked,
                    masked_crash=shape.masked_crash,
                    dedup=shape.dedup, vt=shape.vt,
                    telemetry=telemetry)
        else:
            def getter():
                return lin.get_batch_kernel(
                    model, dims, batch=b, allow_pallas=False,
                    masked=shape.masked,
                    masked_crash=shape.masked_crash,
                    dedup=shape.dedup, vt=shape.vt,
                    telemetry=telemetry)
        fn = getter()
        args = lin.stack_batch([esp] * b)
        carry = tuple(jnp.asarray(c)
                      for c in lin._init_batch_carry(b, dims, model))
        out = fn(*args, jnp.int32(64), jnp.int32(4), jnp.bool_(False),
                 *carry)
        jax.block_until_ready(out)
        return dims, model, getter

    def getter():
        return lin.get_kernel(model, dims, masked=shape.masked,
                              masked_crash=shape.masked_crash,
                              dedup=shape.dedup, vt=shape.vt,
                              telemetry=telemetry)

    fn = getter()
    args = lin.search_args(esp, es)
    carry = tuple(jnp.asarray(c) for c in lin._init_carry(dims, model))
    out = fn(*args, jnp.int32(64), jnp.int32(4), jnp.bool_(False),
             *carry)
    jax.block_until_ready(out)
    return dims, model, getter


def warm_boot(shapes, *, verify: bool = True) -> dict:
    """Compile every shape, then verify warmth: a second
    :func:`get_kernel` pass over the same shapes must be all hits
    (zero miss delta on ``KERNEL_CACHE_STATS``).

    Returns the admission-gate report::

        {"shapes": N, "compiled": n_misses, "hits": n_hits,
         "verified": bool, "persistent_cache": bool, "wall_s": float}

    Shapes that fail the static cache-key model (K007) are not warmed
    — the kernel they'd compile is one the steady state never requests
    — and the report carries their messages under ``"k007"`` with
    ``verified`` forced false, so the admission gate refuses the
    worker with a cause instead of admitting a boot that silently
    warmed the wrong kernel set."""
    from ..checker import linearizable as lin
    from ..obs import telemetry as _tele

    t0 = time.perf_counter()
    k007: list = []
    shapes = validate_shapes(list(shapes), diagnostics=k007)
    telemetry = _tele.enabled()
    before = dict(lin.KERNEL_CACHE_STATS)
    warmed = []
    for s in shapes:
        warmed.append((s, *_compile_one(s, telemetry=telemetry)))
    mid = dict(lin.KERNEL_CACHE_STATS)
    verified = True
    if verify:
        # re-request every kernel: each lookup must be a cache hit —
        # the executable, not just the builder, is resident
        for _s, _dims, _model, rerequest in warmed:
            rerequest()
        after = dict(lin.KERNEL_CACHE_STATS)
        verified = after["misses"] == mid["misses"]
    rep = {
        "shapes": len(shapes),
        "compiled": mid["misses"] - before["misses"],
        "hits": mid["hits"] - before["hits"],
        "verified": bool(verified) and not k007,
        "persistent_cache": _tele.persistent_cache_configured(),
        "wall_s": round(time.perf_counter() - t0, 6),
    }
    if k007:
        rep["k007"] = [d.message for d in k007]
    return rep


def parse_warmup_line(line: str) -> dict | None:
    """Parse the ``stream service warmup: ...`` stderr line a worker
    prints (stream/__main__.py) back into a report dict — the fleet
    admission gate's wire format."""
    marker = "stream service warmup:"
    if marker not in line:
        return None
    out = {}
    for tok in line.split(marker, 1)[1].split():
        if "=" not in tok:
            continue
        k, v = tok.split("=", 1)
        if v in ("true", "false"):
            out[k] = v == "true"
        else:
            try:
                out[k] = int(v)
            except ValueError:
                try:
                    out[k] = float(v)
                except ValueError:
                    out[k] = v
    return out or None
