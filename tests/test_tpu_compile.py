"""The main path's kernels and decision core compile for one described
TPU v5e chip at real widths (nothing runs: no chip is attached).

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and test workers import
every test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_config
from repro.core import controller as C
from repro.core.cutoff import order_stats
from repro.core.runtime_model.api import RuntimeModel
from repro.kernels.fused_adam import fused_adam
from repro.kernels.masked_grad_agg import masked_grad_agg


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler, or the library is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_described_chip_is_a_v5e(topo):
    assert topo.devices[0].device_kind == "TPU v5 lite"


@pytest.mark.parametrize("workers", [4, 16, 158])
def test_masked_grad_agg_compiles(one_chip, workers):
    # a qwen2-0.5b MLP leaf (4864 x 896), padded to the 2048 block
    n = -(-4864 * 896 // 2048) * 2048
    hlo = masked_grad_agg.lower(
        _spec((workers, n), jnp.float32, one_chip),
        _spec((workers, 1), jnp.float32, one_chip)).compile().as_text()
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_fused_adam_compiles_on_embedding_leaf(one_chip, dtype):
    V, D = 151936, 896
    rows, cols = V * D // 128, 128          # ops.adam_update_tree's tiling
    p = _spec((rows, cols), dtype, one_chip)
    f32 = _spec((rows, cols), jnp.float32, one_chip)
    hlo = fused_adam.lower(p, p, f32, f32, _spec((3,), jnp.float32, one_chip),
                           wd=0.01).compile().as_text()
    assert "tpu_custom_call" in hlo


def test_fused_observe_decide_compiles_at_158(one_chip):
    n, k = 158, 64
    rm = RuntimeModel(n_workers=n, lag=20)
    params = jax.eval_shape(lambda: rm.init(0).params)
    cast = lambda s: _spec(s.shape, s.dtype, one_chip)  # noqa: E731
    obs = {"times": _spec((n,), jnp.float32, one_chip),
           "mask": _spec((n,), jnp.bool_, one_chip),
           "mu": _spec((n,), jnp.float32, one_chip),
           "std": _spec((n,), jnp.float32, one_chip),
           "key": _spec((2,), jnp.uint32, one_chip)}
    compiled = C._fused_observe_decide.lower(
        jax.tree.map(cast, params), _spec((21, n), jnp.float32, one_chip),
        _spec((), jnp.int32, one_chip), obs,
        _spec((2,), jnp.uint32, one_chip), _spec((), jnp.float32, one_chip),
        mode="censored", k_samples=k,
        lo=order_stats.min_frac_floor(n, 0.5)).compile()
    assert compiled.memory_analysis() is not None
