"""Validate the trip-count-aware HLO cost analyzer against known programs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.roofline.analysis import peaks_for
from repro.roofline.hlo_cost import analyze_hlo_text


def _compiled_text(fn, *sds):
    return jax.jit(fn).lower(*sds).compile().as_text()


class TestHloCost:
    def test_scan_trip_count_multiplies_flops(self):
        def body(x, _):
            return x @ x, None

        def f(x):
            y, _ = jax.lax.scan(body, x, None, length=10)
            return y

        sds = jax.ShapeDtypeStruct((256, 256), jnp.float32)
        cost = analyze_hlo_text(_compiled_text(f, sds))
        expected = 10 * 2 * 256**3
        assert expected <= cost.flops <= expected * 1.2
        # XLA's own analysis undercounts by ~10x (the motivation)
        xla = jax.jit(f).lower(sds).compile().cost_analysis()
        if isinstance(xla, (list, tuple)):  # newer jax: one dict per program
            xla = xla[0] if xla else {}
        assert cost.flops > 5 * float(xla.get("flops", 0))

    def test_dot_flops_formula(self):
        def f(a, b):
            return a @ b

        sa = jax.ShapeDtypeStruct((64, 128), jnp.float32)
        sb = jax.ShapeDtypeStruct((128, 32), jnp.float32)
        cost = analyze_hlo_text(_compiled_text(f, sa, sb))
        expected = 2 * 64 * 32 * 128
        assert expected <= cost.flops <= expected * 1.1

    def test_nested_scans_multiply(self):
        def inner(x, _):
            return jnp.tanh(x), None

        def outer(x, _):
            y, _ = jax.lax.scan(inner, x, None, length=4)
            return y, None

        def f(x):
            y, _ = jax.lax.scan(outer, x, None, length=3)
            return y

        sds = jax.ShapeDtypeStruct((1024,), jnp.float32)
        cost = analyze_hlo_text(_compiled_text(f, sds))
        # tanh = 12 elementwise ops: at least 3*4*1024 elementwise flops
        assert cost.flops >= 3 * 4 * 1024

    def test_collectives_counted_with_loop_multiplier(self):
        import os
        if jax.device_count() < 2:
            pytest.skip("needs >1 device")

    def test_bytes_exclude_fused_internals(self):
        def f(x):
            return jnp.exp(x) * 2.0 + 1.0  # one fusion

        sds = jax.ShapeDtypeStruct((4096,), jnp.float32)
        cost = analyze_hlo_text(_compiled_text(f, sds))
        # boundary traffic ~ in + out (not 4 tensors worth)
        assert cost.bytes <= 4 * 4096 * 4


class TestPeaks:
    def test_v5e_published_peaks(self):
        peaks = peaks_for("TPU v5 lite")
        assert peaks.flops_bf16 == 197e12 and peaks.hbm_bw == 819e9

    def test_unknown_device_kind_is_an_error(self):
        with pytest.raises(ValueError, match="no published peaks"):
            peaks_for("cpu")
