"""Compile rehearsals of the Pallas kernels for a described TPU v5e chip.

Nothing runs: each test lowers and compiles a kernel at a real width for one
chip of a ``v5e:2x2`` topology that the installed TPU compiler describes,
and checks that the compiled program holds the Mosaic kernel
(``tpu_custom_call``) rather than the interpreter's loop. The topology is
described inside a fixture, never at import, and every test skips where it
cannot be described (no TPU compiler installed).
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.flatten import make_codec
from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.scored_reduce import scored_reduce
from repro.models.small import init_small


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around these
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _flat_size(model: str) -> int:
    return make_codec(init_small(jax.random.PRNGKey(0), model)).n


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("model,U", [("fcn", 64), ("mlp", 256)])
def test_scored_reduce_compiles_for_v5e(one_chip, model, U):
    N = _flat_size(model)
    compiled = scored_reduce.lower(
        _spec((U, N), jnp.float32, one_chip),
        _spec((N,), jnp.float32, one_chip), interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_scored_reduce_default_mode_follows_platform(one_chip):
    """No ``interpret`` argument: compiled when lowered for the TPU,
    interpreted (no Mosaic call) when lowered for this host's CPU."""
    U, N = 16, 4096
    tpu = scored_reduce.lower(_spec((U, N), jnp.float32, one_chip),
                              _spec((N,), jnp.float32, one_chip)).compile()
    assert "tpu_custom_call" in tpu.as_text()
    cpu = scored_reduce.lower(jax.ShapeDtypeStruct((U, N), jnp.float32),
                              jax.ShapeDtypeStruct((N,), jnp.float32))
    assert "tpu_custom_call" not in cpu.compile().as_text()


def test_scored_reduce_kernel_is_named_for_v5e(one_chip):
    """The Mosaic call carries ``kernel_name = "scored_reduce"`` when lowered
    and names its instruction ``%scored_reduce.N`` when compiled: the name
    the profiler's trace shows for the kernel."""
    U, N = 16, 4096
    lowered = scored_reduce.lower(_spec((U, N), jnp.float32, one_chip),
                                  _spec((N,), jnp.float32, one_chip),
                                  interpret=False)
    calls = [ln for ln in lowered.as_text().splitlines()
             if "tpu_custom_call" in ln]
    assert calls and all('kernel_name = "scored_reduce"' in ln
                         for ln in calls)
    compiled = [ln.strip() for ln in lowered.compile().as_text().splitlines()
                if 'custom_call_target="tpu_custom_call"' in ln]
    assert compiled and all(ln.removeprefix("ROOT ").startswith(
        "%scored_reduce") for ln in compiled)


def test_flash_attention_compiles_for_v5e(one_chip):
    B, H, Hkv, S, D = 1, 8, 2, 1024, 128
    compiled = flash_attention_bhsd.lower(
        _spec((B, H, S, D), jnp.bfloat16, one_chip),
        _spec((B, Hkv, S, D), jnp.bfloat16, one_chip),
        _spec((B, Hkv, S, D), jnp.bfloat16, one_chip),
        interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
