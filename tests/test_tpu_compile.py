"""Compile guard for the chip: the served path's dequant kernel, prefill
and decode step, compiled at real widths for a described TPU v5e that is not
attached. Nothing runs; the chip's compiler refuses what it cannot
lower (unsupported casts, shape casts, block shapes, memory)."""
import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.kv_dequant.kernel import kv_dequant
from repro.models import build_model

# one 1024-token chunk plane of sparkv-qwen3-4b: 8 KV heads x head_dim
# 128, quantization group 64 -> one group per kernel row
GROUP = 64
CHUNK_ROWS = 1024 * 8 * 128 // GROUP
V5E_HBM_BYTES = 16 * 2 ** 30


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # any failure means no compiler for the chip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _spec(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


@pytest.mark.parametrize("out_dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("planes", [1, 288])
def test_kv_dequant_compiles_for_v5e(one_chip, planes, out_dtype):
    """One chunk plane, and one launch over the 288 K and V planes of a
    4096-token context (4 chunks x 36 layers x 2), as a full stream
    loads them."""
    rows = planes * CHUNK_ROWS
    col = _spec(one_chip, (rows, 1), jnp.float32)
    compiled = kv_dequant.lower(
        _spec(one_chip, (rows, GROUP), jnp.uint8), col, col,
        interpret=False, out_dtype=out_dtype).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _two_layer_model():
    """sparkv-qwen3-4b at its published widths, depth cut to 2 layers."""
    cfg = dataclasses.replace(get_config("sparkv-qwen3-4b"), num_layers=2)
    return build_model(cfg)


def _fits_v5e(compiled):
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    return 0 < total < V5E_HBM_BYTES


def test_prefill_compiles_for_v5e(one_chip):
    """The jitted prefill register_context runs, over 4096 tokens."""
    model = _two_layer_model()
    place = lambda s: _spec(one_chip, s.shape, s.dtype)
    compiled = jax.jit(model.prefill).lower(
        jax.tree.map(place, model.abstract_params()),
        {"tokens": _spec(one_chip, (1, 4096), jnp.int32)}).compile()
    assert _fits_v5e(compiled)


def test_decode_step_compiles_for_v5e(one_chip):
    """The decode step over a 4096-token context cache."""
    model = _two_layer_model()
    place = lambda s: _spec(one_chip, s.shape, s.dtype)
    params = jax.tree.map(place, model.abstract_params())
    cache = jax.tree.map(place, jax.eval_shape(
        lambda: model.init_cache(1, 4096)))
    compiled = jax.jit(model.decode_step, donate_argnums=(1,)).lower(
        params, cache, _spec(one_chip, (1,), jnp.int32),
        _spec(one_chip, (), jnp.int32)).compile()
    assert _fits_v5e(compiled)
