"""Test configuration: force JAX onto a virtual 8-device CPU platform.

Tests run on the CPU; multi-chip sharding is validated on a host-platform
device mesh (tests/test_chip_compile.py compiles for a described chip).
Must run before the first `import jax` anywhere in the test process.
"""

import contextlib
import os

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

# Pin the platform via config too, in case jax was imported before this
# conftest ran (backends are not yet initialized here, so this still
# takes effect).
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# The CLI turns the persistent compilation cache on for every test it
# drives; tests never write it (tests/test_chip_compile.py would leave
# entries no CPU run can read back).  Tests of the cache opt in.
jax.config.update("jax_enable_compilation_cache", False)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running (excluded from the tier-1 "
                   "'not slow' gate)")


# ---------------------------------------------------------------------------
# fresh traces: one small CPU search per device.compile span kind
# ---------------------------------------------------------------------------

#: span kind -> how the route is driven (see _run_route)
TRACE_KINDS = ("solo-xla", "solo-pallas", "batch", "batch-sharded",
               "window-sharded")

#: kind -> the trace text recorded by the first test that asked for it
_TRACES: dict = {}


def _route_seqs(n_keys: int = 4):
    """Small corrupted CAS-register histories: no greedy witness and
    no static verdict, so every route runs its device kernel."""
    import random

    from jepsen_tpu.history import encode_ops
    from jepsen_tpu.models import cas_register
    from jepsen_tpu.synth import corrupt_read, register_history

    model = cas_register()
    seqs = []
    for k in range(n_keys):
        rng = random.Random(500 + k)
        h = register_history(rng, n_ops=40, n_procs=4, overlap=3,
                             crash_p=0.0, n_values=3)
        seqs.append(encode_ops(corrupt_read(rng, h, at=0.8),
                               model.f_codes))
    return seqs, model


def _run_route(kind: str):
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from jepsen_tpu.checker import linearizable as lin

    seqs, model = _route_seqs()
    mesh = Mesh(np.array(jax.devices()), ("shard",))
    if kind in ("solo-xla", "solo-pallas"):
        old = lin._ENGINE_MODE
        lin._ENGINE_MODE = kind.split("-")[1]
        try:
            return lin.search_opseq(seqs[0], model, dpor=False)
        finally:
            lin._ENGINE_MODE = old
    if kind == "batch":
        return lin.search_batch(seqs, model, dpor=False)[0]
    if kind == "batch-sharded":
        return lin.search_batch(
            seqs, model, dpor=False,
            sharding=NamedSharding(mesh, PartitionSpec("shard")))[0]
    assert kind == "window-sharded", kind
    return lin.search_opseq_sharded(seqs[0], model, mesh, dpor=False,
                                    frontier_per_device=64)


@contextlib.contextmanager
def _cold_kernel_cache():
    """Empty the kernel caches for the block, so every kernel it asks
    for compiles; the kernels cached before are put back after it."""
    from jepsen_tpu.checker import linearizable as lin

    caches = (lin._KERNEL_CACHE, lin._SHARDED_CACHE)
    saved = [dict(c) for c in caches]
    for c in caches:
        c.clear()
    try:
        yield
    finally:
        for c, s in zip(caches, saved):
            c.update(s)


@pytest.fixture
def cold_kernel_cache():
    """The :func:`_cold_kernel_cache` context manager."""
    return _cold_kernel_cache


def _record_trace(kind: str, path: str) -> str:
    """Run one ``kind`` route with tracing and telemetry on, from an
    empty kernel cache (so its compile records a ``device.compile``
    span), and ``obs.write_trace`` it to ``path``."""
    from jepsen_tpu import obs
    from jepsen_tpu.obs import telemetry as tele

    if len(jax.devices()) < 2 and kind.endswith("sharded"):
        pytest.skip("needs a multi-device mesh")
    run = f"fresh-trace-{kind}"
    obs.drop_recorder(run)
    obs.enable(True)
    tele.enable(True)
    obs.set_run(run)
    try:
        with _cold_kernel_cache():
            res = _run_route(kind)
        assert str(res.get("engine", "")).startswith("device"), res
        obs.write_trace(path, run=run)
    finally:
        obs.set_run(None)
        obs.drop_recorder(run)
        obs.enable(None)
        tele.enable(None)
    with open(path) as f:
        return f.read()


@pytest.fixture
def fresh_trace(tmp_path):
    """``fresh_trace(kind) -> path``: a trace of one small search on
    the route of ``kind`` (:data:`TRACE_KINDS`), written under
    ``tmp_path``.  Each kind is recorded once per process."""
    def record(kind: str) -> str:
        path = str(tmp_path / f"trace-{kind}.json")
        if kind in _TRACES:
            with open(path, "w") as f:
                f.write(_TRACES[kind])
        else:
            _TRACES[kind] = _record_trace(kind, path)
        return path
    return record
