"""The float32 reference against the program's own model, and the seeded
weights both sides draw."""
import dataclasses

import jax
import numpy as np
import pytest

from bench_tiny import TINY_ARCH
from bench.lib import reference, weights

SEED = 2**31 + 77


def f32(name):
    return dict(TINY_ARCH[name], dtype="f32")


def program_logits(arch, tokens):
    from repro.models.common import ArchConfig
    from repro.models.registry import get_model

    model = get_model(ArchConfig(**arch))
    params = weights.program_params(arch, SEED)
    with jax.default_matmul_precision("highest"):
        return np.asarray(model.forward(params, {"tokens": tokens}))


@pytest.mark.parametrize("name", sorted(TINY_ARCH))
def test_reference_matches_the_model(name):
    """GQA with RMSNorm and a SiLU-gated MLP, and MQA with LayerNorm,
    GELU and a tied head: the reference and the program's model agree at
    float32 on the same weights."""
    arch = f32(name)
    tokens = np.random.default_rng(0).integers(2, arch["vocab"], (2, 24),
                                               dtype=np.int32)
    ref = reference.forward(arch, SEED, tokens)
    got = program_logits(arch, tokens)
    assert ref.shape == got.shape == (2, 24, arch["vocab"])
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("name", sorted(TINY_ARCH))
def test_control_departs_from_the_reference(name):
    arch = f32(name)
    tokens = np.random.default_rng(1).integers(2, arch["vocab"], (2, 24),
                                               dtype=np.int32)
    ref = reference.forward(arch, SEED, tokens)
    ctl = reference.forward(arch, SEED, tokens, control=True)
    err = np.abs(ctl - ref).max() / np.abs(ref).max()
    assert 1e-3 < err < 0.5


@pytest.mark.parametrize("name", sorted(TINY_ARCH))
def test_layer_draws_match_the_program_tree(name):
    arch = TINY_ARCH[name]
    tree = weights.program_params(arch, SEED)
    for layer in range(arch["n_layers"]):
        one = weights.layer_weights(arch, SEED, layer)
        for path, leaf in jax.tree_util.tree_leaves_with_path(one):
            stacked = tree["blocks"]
            for k in path:
                stacked = stacked[k.key]
            assert np.array_equal(np.asarray(stacked[layer]),
                                  np.asarray(leaf))
    top = weights.top_weights(arch, SEED)
    assert np.array_equal(np.asarray(top["embed"]),
                          np.asarray(tree["embed"]))
    assert ("head" in tree) == (not arch["tie_embeddings"])


def test_seeds_differ_beyond_32_bits():
    a = weights.seed_key(5)
    b = weights.seed_key(5 + 2**32)
    assert not np.array_equal(np.asarray(jax.random.key_data(a)),
                              np.asarray(jax.random.key_data(b)))


def test_fake_fp8_keeps_about_three_bits():
    w = jax.random.normal(jax.random.PRNGKey(0), (256, 64))
    q = reference.fake_fp8(w, axis=0)
    rel = np.abs(np.asarray(q - w)) / np.abs(np.asarray(w)).max(0)
    assert 0 < rel.max() <= 2.0 ** -4


def test_compare_reads_served_and_control_gaps():
    arch = TINY_ARCH["dense_gqa"]
    prompt = np.arange(2, 12, dtype=np.int32)
    full = reference.forward(arch, SEED, prompt[None])[0]
    best = int(full[-1].argmax())
    worst = int(full[-1].argmin())
    g = reference.compare(arch, SEED, [(prompt, 10, np.array([best])),
                                       (prompt, 10, np.array([worst]))],
                          control=True)
    assert g["served"][0] == pytest.approx(0.0, abs=1e-5)
    assert g["served"][1] == pytest.approx(full[-1].max() - full[-1].min(),
                                           rel=1e-5)
    assert len(g["control"]) == 2 and min(g["control"]) >= 0
