"""The model modules: the dense decoder gives, to the bit, the weights,
reference logits and work counts that the benchmark gave before the
shapes moved out of ``bench/lib``; layers may stack in more than one
run; a configuration that names no module, or a missing one, is a clear
error."""
import hashlib
import json
import types

import jax
import numpy as np
import pytest

from bench_tiny import DENSE, ROOT, TINY_ARCH
from bench.lib import flops, models, reference, weights
from bench.lib.models import Leaf

SEED = 2**31 + 77

# taken at the parent of the change that made bench/models/: sha256 (first
# 16 hex digits) of each leaf of the program's tree and of the float32
# reference's logits over TOKENS, the widest gaps of compare() over
# SEQS, and the work counts over the lengths and fills below
GOLDEN = {
    "dense_gqa": {
        "leaves": {
            "blocks/attn/wk": "f556b95973768c05",
            "blocks/attn/wo": "cb4e3124655f7202",
            "blocks/attn/wq": "51a25c0e4b25a5a3",
            "blocks/attn/wv": "c2258cfd72ff774c",
            "blocks/ffn/w_gate": "ecce1e966716b203",
            "blocks/ffn/w_in": "488a6ada8e0a609a",
            "blocks/ffn/w_out": "a627eb9c867d07fd",
            "blocks/ln1/scale": "f79eff3275821a45",
            "blocks/ln2/scale": "6f2e1d125b879a3c",
            "embed": "c72797d91aa5c6ac",
            "head": "1c7db36e4cc608f6",
            "ln_f/scale": "20b3a78389a58db2",
        },
        "logits": "36526206c2aacf19",
        "control_logits": "bbf8c0cfc62a3ca2",
        "served_gaps": [4.093471527099609, 1.755429744720459],
        "control_gaps": [0.03502988815307617, 0.24384593963623047],
        "counts": {
            "layer_matmul_params": 36864,
            "kv_bytes_per_token": 256,
            "weight_bytes": 214272,
            "prefill_flops": 25701376.0,
            "prefill_bytes": 272256,
            "decode_flops": 695808.0,
            "decode_bytes": 243072,
        },
    },
    "dense_mqa": {
        "leaves": {
            "blocks/attn/wk": "9e5908df81ea7ebb",
            "blocks/attn/wo": "cb4e3124655f7202",
            "blocks/attn/wq": "51a25c0e4b25a5a3",
            "blocks/attn/wv": "228ec0c32609909a",
            "blocks/ffn/w_in": "96fd4bdb01012c2f",
            "blocks/ffn/w_out": "22c9b5abd72936a4",
            "blocks/ln1/bias": "0c5728e05ac25e4d",
            "blocks/ln1/scale": "f79eff3275821a45",
            "blocks/ln2/bias": "bf3ab1f50321b741",
            "blocks/ln2/scale": "6f2e1d125b879a3c",
            "embed": "d80cc6db0f65de04",
            "ln_f/bias": "efb30cc8ea043e07",
            "ln_f/scale": "20b3a78389a58db2",
        },
        "logits": "c92c3c174b63027e",
        "control_logits": "736cdf8659304a1d",
        "served_gaps": [0.549602210521698, 0.3073596656322479],
        "control_gaps": [0.0, 0.0],
        "counts": {
            "layer_matmul_params": 43008,
            "kv_bytes_per_token": 128,
            "weight_bytes": 223744,
            "prefill_flops": 29363200.0,
            "prefill_bytes": 262400,
            "decode_flops": 720384.0,
            "decode_bytes": 238336,
        },
    },
}
MINITRON_COUNTS = {
    "layer_matmul_params": 81788928,
    "kv_bytes_per_token": 131072,
    "weight_bytes": 6808952832,
    "prefill_flops": 19410320621568.0,
    "prefill_bytes": 7287013376,
    "decode_flops": 21792817152.0,
    "decode_bytes": 7265888256,
}


def digest(x) -> str:
    return hashlib.sha256(
        np.ascontiguousarray(np.asarray(x)).tobytes()).hexdigest()[:16]


def tokens(arch):
    return np.random.default_rng(0).integers(2, arch["vocab"], (2, 24),
                                             dtype=np.int32)


SEQS = [(np.arange(2, 30, dtype=np.int32), 20, np.array([5, 9, 7, 11])),
        (np.arange(2, 30, dtype=np.int32), 28, np.array([3]))]


def counts(a, prefill_lens, decode_fills):
    return {
        "layer_matmul_params": DENSE.layer_matmul_params(a),
        "kv_bytes_per_token": DENSE.kv_bytes_per_token(a),
        "weight_bytes": DENSE.weight_bytes(a),
        "prefill_flops": flops.prefill_flops(DENSE, a, prefill_lens),
        "prefill_bytes": flops.prefill_bytes(DENSE, a, prefill_lens),
        "decode_flops": flops.decode_flops(DENSE, a, decode_fills),
        "decode_bytes": flops.decode_bytes(DENSE, a, decode_fills),
    }


@pytest.mark.parametrize("name", sorted(GOLDEN))
def test_dense_weights_match_the_parent(name):
    tree = weights.program_params(DENSE, TINY_ARCH[name], SEED)
    got = {"/".join(k.key for k in path): digest(leaf)
           for path, leaf in jax.tree_util.tree_leaves_with_path(tree)}
    assert got == GOLDEN[name]["leaves"]


@pytest.mark.parametrize("name", sorted(GOLDEN))
@pytest.mark.parametrize("control", [False, True])
def test_dense_reference_logits_match_the_parent(name, control):
    arch = TINY_ARCH[name]
    got = reference.forward(DENSE, arch, SEED, tokens(arch),
                            control=control)
    assert digest(got) == GOLDEN[name][
        "control_logits" if control else "logits"]


@pytest.mark.parametrize("name", sorted(GOLDEN))
def test_dense_gaps_match_the_parent(name):
    g = reference.compare(DENSE, TINY_ARCH[name], SEED, SEQS, control=True)
    assert g["served"] == GOLDEN[name]["served_gaps"]
    assert g["control"] == GOLDEN[name]["control_gaps"]


@pytest.mark.parametrize("name", sorted(GOLDEN) + ["minitron_4b"])
def test_dense_counts_match_the_parent(name):
    if name == "minitron_4b":
        a = json.loads((ROOT / "bench" / "configs" / "minitron_4b.json")
                       .read_text())["arch"]
        assert counts(a, [1920, 1500, 64], [0, 1500, 1983]) == \
            MINITRON_COUNTS
    else:
        assert counts(TINY_ARCH[name], [100, 50, 1], [0, 9, 99]) == \
            GOLDEN[name]["counts"]


def two_stacks():
    """A module whose first layer is dense and whose others are not:
    they stack apart, under ``dense`` and ``blocks``."""
    def layer_leaves(a, layer):
        d = a["d_model"]
        if layer == 0:
            return [Leaf(("ffn", "w_in"), (d, 3 * d), "matrix")]
        return [Leaf(("ffn", "w_in"), (4, d, d), "matrix", fan_in=1),
                Leaf(("ffn", "router"), (d, 4), "matrix", dtype="f32")]

    mod = types.ModuleType("two_stacks")
    mod.top_leaves = lambda a: [Leaf(("embed",), (a["vocab"], a["d_model"]),
                                     "embed", fan_in=1)]
    mod.layer_leaves = layer_leaves
    mod.stacks = lambda a: [(("dense",), [0]),
                            (("blocks",), list(range(1, a["n_layers"])))]
    return mod


def test_layers_stack_as_the_module_says():
    mod = two_stacks()
    a = {"d_model": 8, "vocab": 16, "n_layers": 4, "dtype": "bf16"}
    tree = weights.program_params(mod, a, SEED)
    assert tree["dense"]["ffn"]["w_in"].shape == (1, 8, 24)
    assert tree["blocks"]["ffn"]["w_in"].shape == (3, 4, 8, 8)
    assert tree["blocks"]["ffn"]["router"].dtype == np.float32
    assert tree["blocks"]["ffn"]["w_in"].dtype == jax.numpy.bfloat16
    for layer in range(4):
        one = weights.layer_weights(mod, a, SEED, layer)
        stack, i = ("dense", 0) if layer == 0 else ("blocks", layer - 1)
        for path, leaf in jax.tree_util.tree_leaves_with_path(one):
            full = tree[stack]
            for k in path:
                full = full[k.key]
            assert np.array_equal(np.asarray(full[i]), np.asarray(leaf))
    # a matrix's scale follows its fan-in axis, not its first
    w = np.asarray(tree["blocks"]["ffn"]["w_in"], np.float32)
    assert 0.25 < w.std() * np.sqrt(8) < 4
    # the control rounds the served matrices along their fan-in, and
    # leaves the float32 router alone
    assert reference._rounded(mod, a, 2) == ((("ffn", "w_in"), 1),)


def test_a_stack_of_unlike_layers_is_refused():
    mod = two_stacks()
    mod.stacks = lambda a: [(("blocks",), list(range(a["n_layers"])))]
    a = {"d_model": 8, "vocab": 16, "n_layers": 2, "dtype": "bf16"}
    with pytest.raises(ValueError, match="share a stack"):
        weights.program_params(mod, a, SEED)


def test_a_configuration_names_its_module(tmp_path):
    (tmp_path / "bench" / "models").mkdir(parents=True)
    with pytest.raises(KeyError, match="names no model"):
        models.of_config(tmp_path, {"arch": {}}, "nameless")
    with pytest.raises(FileNotFoundError, match="'moe_decoder'"):
        models.of_config(tmp_path, {"model": "moe_decoder"}, "cfg")
    path = models.of_config(ROOT, {"model": "dense_decoder"}, "cfg")
    assert models.load(path) is DENSE
