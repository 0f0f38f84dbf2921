"""Compile-only checks for a TPU v5e that is described, not attached.

The TPU compiler is installed beside jax, so the main path's kernels and
the full-width decode step are compiled here for one chip of a described
``v5e:2x2`` topology: what Mosaic or XLA would refuse on the chip (block
shapes off the (8, 128) tiling, too much VMEM, a program over the 16 GB of
HBM) fails here at no chip time.  Nothing runs, so nothing here says
anything about results or speed.

The topology is described inside module-scoped fixtures only — never at
import — because one process at a time may load the TPU library.  Every
test of this kind lives in this one file so a single worker loads it.
"""
import contextlib
import dataclasses
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.fused_elementwise.ops import fused_elementwise
from repro.kernels.fused_reduce.ops import fused_reduce
from repro.kernels.matmul.ops import matmul_fused
from repro.models.registry import get_model

HBM_BYTES = 16e9  # one TPU v5e (Google Cloud documentation, "TPU v5e")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    # keep the TPU library's logs out of /tmp while it loads
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means "cannot"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@contextlib.contextmanager
def _no_persistent_cache():
    """A program compiled for a described chip is written to the
    persistent cache but cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()


def _compile(fn, *args):
    with _no_persistent_cache():
        return jax.jit(fn).lower(*args).compile()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("m,k,n", [
    (256, 3072, 9216),   # a minitron MLP up-projection at 256 tokens
    (128, 128, 192),     # N and K off the 128-lane tiling: padded or
    (128, 96, 128),      # taken whole, never split into narrow lane
    (200, 64, 100),      # blocks
])
def test_matmul_fused_compiles(one_chip, m, k, n):
    # a bf16 accumulator dtype: the SiLU epilogue must not reach Mosaic
    # as bf16 transcendental math, which a v5e cannot do
    def fn(a, b, r):
        return matmul_fused(a, b, [r], lambda acc, res: jax.nn.silu(acc) + res,
                            valid_mnk=(m, n, k), out_dtypes=[jnp.bfloat16],
                            acc_dtype=jnp.bfloat16)[0]

    bf16 = functools.partial(_sds, dtype=jnp.bfloat16, sharding=one_chip)
    c = _compile(fn, bf16((m, k)), bf16((k, n)), bf16((m, n)))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_elementwise_compiles(one_chip, dtype):
    def fn(x, y):
        return fused_elementwise(
            lambda a, b: a * jax.nn.sigmoid(a) * jax.lax.rsqrt(b * b + 1.0),
            [x, y], 4000 * 3072, [dtype])[0]

    x = _sds((4096, 3072), dtype, one_chip)
    c = _compile(fn, x, x)
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_reduce_compiles(one_chip, dtype):
    def fn(x):
        return fused_reduce(lambda a: a * a, [x], 3000, "sum")

    c = _compile(fn, _sds((4096, 3072), dtype, one_chip))
    assert "tpu_custom_call" in c.as_text()


def test_minitron_decode_step_fits_one_chip(one_chip):
    # published widths, depth cut to 2 layers to keep the compile short
    cfg = dataclasses.replace(get_config("minitron_4b"), n_layers=2)
    model = get_model(cfg)
    batch, max_len = 4, 1024

    def place(tree):
        return jax.tree.map(lambda s: _sds(s.shape, s.dtype, one_chip), tree)

    params = place(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = place(jax.eval_shape(lambda: model.init_cache(batch, max_len)))
    c = _compile(model.decode_step, params, cache,
                 _sds((batch, 1), jnp.int32, one_chip),
                 _sds((batch,), jnp.int32, one_chip))
    mem = c.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert params["head"].shape == (3072, 256000)
    assert total < HBM_BYTES, total
