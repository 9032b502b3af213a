"""Ahead-of-time compiles of the chip path for a described TPU v5e.

The TPU compiler ships with the installed jaxlib, and it compiles for a
topology that is described rather than attached, so these tests need no
chip.  They compile the jitted steps a one-chip run executes -- the
``lax`` count step of both dispatchers, the ``lax`` list step and the
listing's device decode -- at a
batch of 256 tiles, every tile width of the pipeline's bins, and l = 3, 4
(k = 5, 6), and they pin the backend choice that makes ``auto`` land on
them.  A compile that passes is not a chip run: results and times come
only from the chip (``chip_smoke.py``).

The topology is described inside a module fixture, never at import time:
only one process may load the TPU library, and under pytest-xdist every
worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import listing
from repro.kernels import ops
from repro.runtime import dispatch

BATCH = 256
WIDTHS = (32, 64, 128, 256)
LEVELS = (3, 4)
LIST_CAPACITY = 256


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    # keep the TPU compiler's logs out of the temp directory
    prev = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        desc = None
        reason = f"no v5e:2x2 topology can be described here: {e}"
    if desc is None:
        pytest.skip(reason)
    yield desc
    if prev is None:
        os.environ.pop("TPU_LOG_DIR", None)
    else:
        os.environ["TPU_LOG_DIR"] = prev


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off meanwhile."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _batch_shapes(T, sharding):
    W = T // 32
    return (jax.ShapeDtypeStruct((BATCH, T, W), jnp.uint32, sharding=sharding),
            jax.ShapeDtypeStruct((BATCH, W), jnp.uint32, sharding=sharding))


@pytest.mark.parametrize("l", LEVELS)
@pytest.mark.parametrize("T", WIDTHS)
def test_lax_count_step_compiles_for_v5e(one_chip, no_compile_cache, T, l):
    # the exact jitted step Dispatcher runs when the backend resolves to lax
    step = dispatch._device_step(l, "auto", True, None, "lax")
    compiled = step.lower(*_batch_shapes(T, one_chip)).compile()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes > 0
    assert "tpu_custom_call" not in compiled.as_text()  # no Mosaic kernel


@pytest.mark.parametrize("l", LEVELS)
@pytest.mark.parametrize("T", WIDTHS)
def test_lax_list_step_compiles_for_v5e(one_chip, no_compile_cache, T, l):
    step = jax.jit(lambda A, cand: ops.list_tiles(
        A, cand, l, LIST_CAPACITY, backend="lax"))
    compiled = step.lower(*_batch_shapes(T, one_chip)).compile()
    mem = compiled.memory_analysis()
    # the (B, capacity, l) int32 emit buffer plus the two (B,) vectors
    assert mem.output_size_in_bytes >= BATCH * LIST_CAPACITY * l * 4
    assert "tpu_custom_call" not in compiled.as_text()


@pytest.mark.parametrize("T", WIDTHS)
def test_list_decode_step_compiles_for_v5e(one_chip, no_compile_cache, T):
    # the device decode a sized listing batch runs after its list kernel
    l, cap = 2, 128
    shapes = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
              for shape, dtype in (((BATCH, cap, l), jnp.int32),
                                   ((BATCH,), jnp.uint32),
                                   ((BATCH,), jnp.uint32),
                                   ((BATCH, T), jnp.int32),
                                   ((BATCH, 2), jnp.int32))]
    compiled = listing._decode_step(l).lower(*shapes).compile()
    # the (k, B*cap + cap) int32 rows plus the row total
    assert compiled.memory_analysis().output_size_in_bytes >= \
        (l + 2) * (BATCH + 1) * cap * 4
    assert "tpu_custom_call" not in compiled.as_text()


def test_auto_resolves_to_the_compiling_backend_on_tpu(monkeypatch):
    monkeypatch.delenv(ops.BACKEND_ENV, raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops.compiling_backends() == ("lax",)
    assert ops.resolve_backend("auto") == "lax"
    assert ops.resolve_backend() == "lax"
    # an explicit Pallas request is honoured, so its launch raises the
    # lowering's own error instead of quietly running another backend
    assert ops.resolve_backend("pallas") == "pallas"
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert ops.compiling_backends() == ("lax", "pallas")
    assert ops.resolve_backend("auto") == "lax"
