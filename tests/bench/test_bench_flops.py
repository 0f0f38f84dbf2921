"""FLOP and byte counts against hand counts at the cells' sizes."""
import json

import jax
import numpy as np
import pytest

from bench_tiny import DENSE, ROOT
from bench.lib import flops


def arch(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())["arch"]


# per layer: attention d*h*hd + 2*d*hkv*hd + h*hd*d, MLP 3 (gated) or 2
# matrices of d x d_ff; KV: layers x 2 x hkv x hd x 2 bytes
HAND = {
    "minitron_4b": {"layer": 3072 * 3072 * 2 + 2 * 3072 * 1024
                    + 2 * 3072 * 9216, "kv": 32 * 2 * 8 * 128 * 2},
    "granite_20b": {"layer": 6144 * 6144 * 2 + 2 * 6144 * 128
                    + 2 * 6144 * 24576, "kv": 13 * 2 * 1 * 128 * 2},
}


@pytest.mark.parametrize("name", sorted(HAND))
def test_layer_params_and_kv_bytes(name):
    a = arch(name)
    assert DENSE.layer_matmul_params(a) == HAND[name]["layer"]
    assert DENSE.kv_bytes_per_token(a) == HAND[name]["kv"]


def test_hand_counts_at_published_widths():
    assert HAND["minitron_4b"]["layer"] == 81_788_928
    assert HAND["minitron_4b"]["kv"] == 131_072
    assert HAND["granite_20b"]["layer"] == 379_060_224
    assert HAND["granite_20b"]["kv"] == 6_656


@pytest.mark.parametrize("name", sorted(HAND))
def test_weight_bytes_match_the_model(name):
    """Weights read per launch: every parameter of the model's tree but
    an untied embedding table (read only at the looked-up rows)."""
    from repro.models.common import ArchConfig
    from repro.models.registry import get_model

    a = arch(name)
    shapes = jax.eval_shape(get_model(ArchConfig(**a)).init,
                            jax.random.PRNGKey(0))
    total = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                for x in jax.tree.leaves(shapes))
    table = 0 if a["tie_embeddings"] else a["vocab"] * a["d_model"] * 2
    assert DENSE.weight_bytes(a) == total - table


def test_minitron_counts():
    a = arch("minitron_4b")
    # 4.19 B parameters: 32 layers, two LayerNorms each, final LayerNorm,
    # embedding table and untied head
    assert 32 * (81_788_928 + 4 * 3072) + 2 * 3072 + 2 * 256_000 * 3072 \
        == 4_190_509_056
    per_tok = 2 * 32 * 81_788_928
    head = 2 * 3072 * 256_000
    att = 4 * 32 * 24 * 128
    assert flops.decode_flops(DENSE, a, [0]) == per_tok + head + att
    assert flops.decode_flops(DENSE, a, [9, 99]) == \
        2 * (per_tok + head) + att * (10 + 100)
    assert flops.prefill_flops(DENSE, a, [1]) == \
        flops.decode_flops(DENSE, a, [0])
    assert flops.prefill_flops(DENSE, a, [1500]) == \
        per_tok * 1500 + att * 1500 * 1501 / 2 + head
    assert flops.decode_bytes(DENSE, a, [9]) == (DENSE.weight_bytes(a)
                                          + 131_072 * 10 + 3072 * 2)
    assert flops.prefill_bytes(DENSE, a, [100, 50]) == (
        DENSE.weight_bytes(a) + 150 * 3072 * 2 + 150 * 131_072)


def test_granite_weight_bytes_and_least_time():
    a = arch("granite_20b")
    assert DENSE.weight_bytes(a) == \
        13 * (379_060_224 * 2 + 2 * 2 * 6144 * 4) + 2 * 6144 * 4 \
        + 6144 * 49152 * 2 == 10_460_872_704
    # one decode row is bound by bytes, a long prefill by operations
    t = flops.least_time(flops.decode_flops(DENSE, a, [255]),
                         flops.decode_bytes(DENSE, a, [255]), 197e12, 819e9)
    assert t == pytest.approx(flops.decode_bytes(DENSE, a, [255]) / 819e9)
    lens = [1024] * 8
    t = flops.least_time(flops.prefill_flops(DENSE, a, lens),
                         flops.prefill_bytes(DENSE, a, lens), 197e12, 819e9)
    assert t == pytest.approx(flops.prefill_flops(DENSE, a, lens) / 197e12)
