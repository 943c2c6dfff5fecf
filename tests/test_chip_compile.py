"""Compile the main path's device kernels for a described TPU v5e.

No chip is needed: the TPU compiler is installed here and compiles for
a topology that is described, not attached, so Mosaic and XLA refuse
here what they would refuse on the chip (unaligned lane slices,
selects of booleans, scoped-VMEM overruns, unpartitionable programs).
Nothing runs, so these tests say nothing about results or speed.

This is the only file that describes a TPU topology.  The description
lives in a module-scoped fixture (never at import, in conftest.py or in
a parametrize argument): only one process may load the TPU library, so
every pytest-xdist worker must collect the same tests and only the one
given this file may load it.  The persistent compilation cache is off
inside the tests — a compile for a described chip cannot be read back
without one.
"""

import numpy as np
import pytest

import jax

from jepsen_tpu.checker import linearizable as lin
from jepsen_tpu.checker import pallas_level
from jepsen_tpu.models import cas_register, mutex


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_compilation_cache():
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _specs(args, sharding):
    return [jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype,
                                 sharding=sharding) for a in args]


def _dims(model, *, frontier, window, n_crash_pad, n_det_pad, k=16):
    return lin.SearchDims(n_det_pad=n_det_pad, n_crash_pad=n_crash_pad,
                          window=window, k=k,
                          state_width=model.state_width,
                          frontier=frontier)


@pytest.fixture
def tpu_selectors(monkeypatch):
    """The kernel builders ask ``_backend()`` which compaction and
    dominance forms to trace; here it answers for the chip."""
    monkeypatch.setattr(lin, "_backend", lambda: "tpu")


def test_single_xla_kernel_at_1k_tier_size(one_chip, tpu_selectors):
    # the 1k tier's dims on a chip (1,000 ops, window 47 -> 64, 3
    # crashes -> 32, concurrency 11 -> k 16, first rung 128)
    model = cas_register()
    dims = _dims(model, frontier=128, window=64, n_crash_pad=32,
                 n_det_pad=1024)
    args = lin.route_sample_inputs(model, dims)
    compiled = jax.jit(lin.build_search_step_fn(model, dims)).lower(
        *_specs(args, one_chip)).compile()
    assert "tpu_custom_call" not in compiled.as_text()


@pytest.mark.parametrize("model_name,n_det_pad", [
    ("cas-register", 1024),    # eligible() limits, 1k-tier tables
    ("mutex", 16384),          # the mutex family at 10k-scale tables
])
def test_pallas_kernel_at_eligible_limits(one_chip, model_name,
                                          n_det_pad):
    model = {"cas-register": cas_register, "mutex": mutex}[model_name]()
    dims = _dims(model, frontier=64, window=64, n_crash_pad=64,
                 n_det_pad=n_det_pad, k=128)
    assert pallas_level.eligible(model, dims)
    args = lin.route_sample_inputs(model, dims)
    step = pallas_level.build_pallas_step_fn(model, dims)
    compiled = jax.jit(step).lower(*_specs(args, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sharded_batch_kernel_on_four_chips(topo, tpu_selectors):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    # BASELINE config 3's key shape (128 ops, 8 procs) across a 2x2
    # mesh: 256 keys, 64 per chip
    model = cas_register()
    dims = _dims(model, frontier=64, window=32, n_crash_pad=32,
                 n_det_pad=128, k=8)
    mesh = Mesh(np.array(topo.devices[:4]), ("shard",))
    cached = set(lin._KERNEL_CACHE)
    try:
        fn = lin.get_sharded_batch_kernel(model, dims, batch=256,
                                          mesh=mesh, axis="shard")
    finally:
        # the cache keys a mesh by device ids, which the described
        # chips share with the CPU devices later tests mesh over
        for k in set(lin._KERNEL_CACHE) - cached:
            del lin._KERNEL_CACHE[k]
    args = lin.route_sample_inputs(model, dims, batch=256)
    keyed = NamedSharding(mesh, P("shard"))
    repl = NamedSharding(mesh, P())
    specs = [jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype,
                                  sharding=repl if np.ndim(a) == 0
                                  else keyed) for a in args]
    compiled = fn.lower(*specs).compile()
    text = compiled.as_text()
    # keys are independent: the partitioned program needs no
    # collective at all
    for coll in ("all-reduce", "all-gather", "all-to-all",
                 "collective-permute"):
        assert coll not in text, coll
    assert compiled.memory_analysis() is not None
