"""Per-kernel validation: Pallas (interpreted on the CPU) vs pure-jnp oracles.

Sweeps shapes and dtypes per kernel; every kernel must match its ref.py
oracle within per-dtype tolerances.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.fused_elementwise.ops import fused_elementwise
from repro.kernels.fused_elementwise.ref import fused_elementwise_ref
from repro.kernels.fused_reduce.ops import fused_reduce
from repro.kernels.fused_reduce.ref import fused_reduce_ref
from repro.kernels.softmax.ops import masked_softmax
from repro.kernels.softmax.ref import masked_softmax_ref
from repro.kernels.rmsnorm.ops import rmsnorm
from repro.kernels.rmsnorm.ref import rmsnorm_ref
from repro.kernels.layernorm.ops import layernorm
from repro.kernels.layernorm.ref import layernorm_ref
from repro.kernels.flash_attention.ops import flash_attention, flash_decode
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.matmul.ops import (matmul, matmul_fused,
                                      select_gemm_version)
from repro.kernels.matmul.ref import matmul_ref
from repro.kernels.rwkv6.ops import rwkv6_scan
from repro.kernels.rwkv6.ref import rwkv6_ref
from repro.kernels.mamba2.ops import mamba2_scan
from repro.kernels.mamba2.ref import mamba2_ref

TOL = {jnp.float32: dict(rtol=1e-5, atol=1e-5),
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


def _rand(rng, shape, dtype):
    return jnp.asarray(rng.randn(*shape), dtype=dtype)


class TestFusedElementwise:
    @pytest.mark.parametrize("shape", [(1024,), (4096,), (8, 256), (3, 7, 64)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_expr_cluster(self, shape, dtype):
        rng = np.random.RandomState(0)
        x = _rand(rng, shape, dtype)
        y = _rand(rng, shape, dtype)

        def expr(a, b):
            return jnp.tanh(a) * b + a

        total = int(np.prod(shape))
        n_valid = total - 7 if total > 7 else total
        got = fused_elementwise(expr, [x, y], n_valid, [dtype])[0]
        want = fused_elementwise_ref(expr, [x.ravel(), y.ravel()], n_valid,
                                     [dtype])[0].reshape(shape)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), **TOL[dtype])

    def test_multi_output(self):
        rng = np.random.RandomState(1)
        x = _rand(rng, (2048,), jnp.float32)

        def expr(a):
            return jnp.exp(a), a * 2.0

        got = fused_elementwise(expr, [x], 2000, [jnp.float32, jnp.float32])
        want = fused_elementwise_ref(expr, [x], 2000,
                                     [jnp.float32, jnp.float32])
        for g, w in zip(got, want):
            np.testing.assert_allclose(g, w, rtol=1e-6)


class TestFusedReduce:
    @pytest.mark.parametrize("kind", ["sum", "max", "min", "prod"])
    @pytest.mark.parametrize("shape", [(16, 128), (64, 33), (8, 1024)])
    def test_reduce_kinds(self, kind, shape):
        rng = np.random.RandomState(2)
        x = _rand(rng, shape, jnp.float32)
        n_valid = shape[1] - 3 if shape[1] > 3 else shape[1]

        def expr(a):
            return a * 0.5 + 1.0

        got = fused_reduce(expr, [x], n_valid, kind)
        want = fused_reduce_ref(expr, [x], n_valid, kind)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    def test_dynamic_cols_sweep(self):
        rng = np.random.RandomState(3)
        x = _rand(rng, (8, 64), jnp.float32)
        for n in (1, 13, 37, 64):
            got = fused_reduce(lambda a: jnp.exp(a), [x], n, "sum")
            want = fused_reduce_ref(lambda a: jnp.exp(a), [x], n, "sum")
            np.testing.assert_allclose(got, want, rtol=1e-5)


class TestMaskedSoftmax:
    @pytest.mark.parametrize("shape", [(8, 64), (2, 4, 128), (16, 100)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, shape, dtype):
        rng = np.random.RandomState(4)
        x = _rand(rng, shape, dtype)
        n = shape[-1] // 2 + 1
        got = masked_softmax(x, n)
        want = masked_softmax_ref(x.reshape(-1, shape[-1]).astype(jnp.float32),
                                  n).reshape(shape)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), **TOL[dtype])

    def test_padded_cols_zero(self):
        x = jnp.ones((8, 32))
        out = masked_softmax(x, 10)
        assert np.all(np.asarray(out)[:, 10:] == 0.0)
        np.testing.assert_allclose(np.asarray(out).sum(-1), 1.0, rtol=1e-6)


class TestNorms:
    @pytest.mark.parametrize("shape", [(8, 64), (4, 16, 128), (256, 512)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_rmsnorm(self, shape, dtype):
        rng = np.random.RandomState(5)
        x = _rand(rng, shape, dtype)
        w = _rand(rng, shape[-1:], dtype)
        np.testing.assert_allclose(
            np.asarray(rmsnorm(x, w), np.float32),
            np.asarray(rmsnorm_ref(x, w), np.float32), **TOL[dtype])

    @pytest.mark.parametrize("shape", [(8, 64), (3, 5, 32)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_layernorm(self, shape, dtype):
        rng = np.random.RandomState(6)
        x = _rand(rng, shape, dtype)
        g = _rand(rng, shape[-1:], dtype)
        b = _rand(rng, shape[-1:], dtype)
        np.testing.assert_allclose(
            np.asarray(layernorm(x, g, b), np.float32),
            np.asarray(layernorm_ref(x, g, b), np.float32), **TOL[dtype])


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("hkv", [4, 1])  # MHA-group / MQA
    def test_varlen_matches_ref(self, causal, hkv):
        rng = np.random.RandomState(7)
        b, h, s, d = 2, 4, 32, 16
        q = _rand(rng, (b, h, s, d), jnp.float32)
        k = _rand(rng, (b, hkv, s, d), jnp.float32)
        v = _rand(rng, (b, hkv, s, d), jnp.float32)
        lens = jnp.array([s, s // 2 + 1], jnp.int32)
        got = flash_attention(q, k, v, lens, causal=causal,
                              block_q=8, block_k=8)
        want = attention_ref(q, k, v, lens, causal=causal)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)

    def test_length_sweep(self):
        rng = np.random.RandomState(8)
        b, h, s, d = 1, 2, 64, 8
        q = _rand(rng, (b, h, s, d), jnp.float32)
        k = _rand(rng, (b, h, s, d), jnp.float32)
        v = _rand(rng, (b, h, s, d), jnp.float32)
        for n in (1, 9, 33, 64):
            lens = jnp.array([n], jnp.int32)
            got = flash_attention(q, k, v, lens, causal=True,
                                  block_q=8, block_k=8)
            want = attention_ref(q, k, v, lens, causal=True)
            np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)

    def test_bf16(self):
        rng = np.random.RandomState(9)
        b, h, s, d = 1, 2, 16, 8
        q = _rand(rng, (b, h, s, d), jnp.bfloat16)
        k = _rand(rng, (b, h, s, d), jnp.bfloat16)
        v = _rand(rng, (b, h, s, d), jnp.bfloat16)
        lens = jnp.array([11], jnp.int32)
        got = flash_attention(q, k, v, lens, causal=True, block_q=8, block_k=8)
        want = attention_ref(q, k, v, lens, causal=True)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=3e-2, atol=3e-2)

    def test_decode(self):
        rng = np.random.RandomState(10)
        b, h, smax, d = 2, 4, 64, 16
        q = _rand(rng, (b, h, 1, d), jnp.float32)
        kc = _rand(rng, (b, h, smax, d), jnp.float32)
        vc = _rand(rng, (b, h, smax, d), jnp.float32)
        lens = jnp.array([37, 5], jnp.int32)
        got = flash_decode(q, kc, vc, lens)
        want = attention_ref(q, kc, vc, lens, causal=False)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


class TestMatmulLibrary:
    @pytest.mark.parametrize("mkn", [(128, 128, 128), (256, 128, 384),
                                     (8, 128, 128), (128, 512, 128)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_library_kernels(self, mkn, dtype):
        m, k, n = mkn
        rng = np.random.RandomState(11)
        a = _rand(rng, (m, k), dtype)
        b = _rand(rng, (k, n), dtype)
        got = matmul(a, b)
        want = matmul_ref(a, b)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=3e-2 if dtype == jnp.bfloat16 else 1e-4,
                                   atol=3e-1 if dtype == jnp.bfloat16 else 1e-3)

    @pytest.mark.parametrize("mkn", [(128, 128, 192), (128, 96, 128),
                                     (200, 64, 100), (7, 300, 130)])
    def test_fused_off_lane_tiling(self, mkn):
        # N/K that are no multiple of 128 are padded (or taken whole) and
        # the tails masked: the result is the plain epilogue(a @ b)
        m, k, n = mkn
        rng = np.random.RandomState(13)
        a = _rand(rng, (m, k), jnp.float32)
        b = _rand(rng, (k, n), jnp.float32)
        r = _rand(rng, (m, n), jnp.float32)
        got, = matmul_fused(a, b, [r], lambda acc, res: jax.nn.silu(acc) + res,
                            valid_mnk=(m, n, k), out_dtypes=[jnp.float32])
        want = jax.nn.silu(matmul_ref(a, b)) + r
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)

    def test_selection_interface(self):
        assert select_gemm_version(2048, 1024, 2048) == "square_big"
        assert select_gemm_version(8, 128, 128) == "skinny_m"
        assert select_gemm_version(128, 1024, 128) == "deep_k"
        assert select_gemm_version(128, 128, 128) == "balanced"
        assert select_gemm_version(100, 100, 100) is None  # vendor fallback


class TestRWKV6:
    @pytest.mark.parametrize("t", [16, 48, 100])
    def test_matches_sequential_ref(self, t):
        rng = np.random.RandomState(12)
        b, h, dk, dv = 2, 2, 8, 8
        r = _rand(rng, (b, h, t, dk), jnp.float32) * 0.5
        k = _rand(rng, (b, h, t, dk), jnp.float32) * 0.5
        v = _rand(rng, (b, h, t, dv), jnp.float32) * 0.5
        w = jax.nn.sigmoid(_rand(rng, (b, h, t, dk), jnp.float32))
        u = _rand(rng, (h, dk), jnp.float32) * 0.1
        got = rwkv6_scan(r, k, v, w, u)
        want = rwkv6_ref(r, k, v, w, u)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


class TestMamba2:
    @pytest.mark.parametrize("t", [16, 64, 70])
    def test_matches_sequential_ref(self, t):
        rng = np.random.RandomState(13)
        b, h, n, p = 2, 2, 8, 8
        x = _rand(rng, (b, h, t, p), jnp.float32) * 0.5
        a = jax.nn.sigmoid(_rand(rng, (b, h, t, 1), jnp.float32))
        bb = _rand(rng, (b, h, t, n), jnp.float32) * 0.5
        c = _rand(rng, (b, h, t, n), jnp.float32) * 0.5
        got = mamba2_scan(x, a, bb, c)
        want = mamba2_ref(x, a, bb, c)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
