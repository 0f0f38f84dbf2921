"""Decoder-only transformer LM covering dense / MoE / MLA / VLM families.

Layers are homogeneous and stacked: ``jax.lax.scan`` over a (L, ...) param
pytree keeps HLO size O(1) in depth (critical for 40–81-layer dry-run
compiles).  ``cfg.remat`` wraps the block in ``jax.checkpoint``.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..dist.context import maybe_shard
from . import layers as L
from .common import ArchConfig, cross_entropy_loss, param_init

Params = Dict[str, Any]


# ----------------------------------------------------------------- block --
def block_init(rng, cfg: ArchConfig) -> Params:
    k1, k2, k3, k4 = jax.random.split(rng, 4)
    p = {"ln1": L.norm_init(k1, cfg), "ln2": L.norm_init(k2, cfg)}
    if cfg.mla_kv_lora:
        p["attn"] = L.mla_init(k3, cfg)
    else:
        p["attn"] = L.attn_init(k3, cfg)
    p["ffn"] = L.moe_init(k4, cfg) if cfg.is_moe else L.mlp_init(k4, cfg)
    return p


def block_specs(cfg: ArchConfig) -> Params:
    p = {"ln1": L.norm_specs(cfg), "ln2": L.norm_specs(cfg)}
    p["attn"] = L.mla_specs(cfg) if cfg.mla_kv_lora else L.attn_specs(cfg)
    p["ffn"] = L.moe_specs(cfg) if cfg.is_moe else L.mlp_specs(cfg)
    return p


def block_apply(cfg: ArchConfig, p: Params, x, *, positions, lens,
                cache: Optional[Params] = None, offsets=None):
    h = L.norm_apply(cfg, p["ln1"], x)
    if cfg.mla_kv_lora:
        a, new_cache = L.mla_apply(cfg, p["attn"], h, positions=positions,
                                   lens=lens, cache=cache, offsets=offsets)
    else:
        a, new_cache = L.attn_apply(cfg, p["attn"], h, positions=positions,
                                    lens=lens, cache=cache, offsets=offsets)
    x = x + a
    h = L.norm_apply(cfg, p["ln2"], x)
    f = L.moe_apply(cfg, p["ffn"], h) if cfg.is_moe \
        else L.mlp_apply(cfg, p["ffn"], h)
    return x + f, new_cache


def _maybe_remat(cfg: ArchConfig, fn):
    if cfg.remat == "none":
        return fn
    policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
              if cfg.remat == "dots" else None)
    return jax.checkpoint(fn, policy=policy)


# ------------------------------------------------------------------- LM --
# jitted so each float32 draw fuses into its cast: run eagerly, the f32
# transients of a full-width model (3.6 GB for one stacked d_model x d_ff
# leaf) would sit beside the bf16 weights already made
@functools.partial(jax.jit, static_argnums=0)
def init(cfg: ArchConfig, rng) -> Params:
    dt = jnp.bfloat16 if cfg.dtype == "bf16" else jnp.float32
    k_e, k_b, k_h, k_n = jax.random.split(rng, 4)
    blocks = jax.vmap(lambda k: block_init(k, cfg))(
        jax.random.split(k_b, cfg.n_layers))
    p = {
        "embed": param_init(k_e, (cfg.vocab, cfg.d_model), dt, scale=0.02),
        "blocks": blocks,
        "ln_f": L.norm_init(k_n, cfg),
    }
    if not cfg.tie_embeddings:
        p["head"] = param_init(k_h, (cfg.d_model, cfg.vocab), dt)
    return p


def specs(cfg: ArchConfig) -> Params:
    blocks = jax.tree.map(lambda s: P(*((None,) + tuple(s))),
                          block_specs(cfg),
                          is_leaf=lambda s: isinstance(s, P))
    p = {
        "embed": L.wspec(cfg, "model", "data"),
        "blocks": blocks,
        "ln_f": L.norm_specs(cfg),
    }
    if not cfg.tie_embeddings:
        p["head"] = L.wspec(cfg, "data", "model")
    return p


def _run_blocks(cfg: ArchConfig, blocks: Params, x, *, positions, lens,
                caches: Optional[Params] = None, offsets=None):
    if caches is None:
        def body(h, bp):
            h2, _ = block_apply(cfg, bp, h, positions=positions, lens=lens)
            return h2, None

        body = _maybe_remat(cfg, body)
        x, _ = jax.lax.scan(body, x, blocks)
        return x, None

    def body(h, xs):
        bp, c = xs
        h2, c2 = block_apply(cfg, bp, h, positions=positions, lens=lens,
                             cache=c, offsets=offsets)
        return h2, c2

    x, new_caches = jax.lax.scan(body, x, (blocks, caches))
    return x, new_caches


def embed_tokens(cfg: ArchConfig, params: Params, tokens) -> jax.Array:
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], tokens, axis=0)
        return maybe_shard(x, L.act_bsd(cfg))


def logits_from_hidden(cfg: ArchConfig, params: Params, x) -> jax.Array:
    with jax.named_scope("head"):
        head = params["embed"].T if cfg.tie_embeddings else params["head"]
        logits = x @ head
        spec = (P(L._DP_ALL, None, None) if cfg.sharding_profile == "fsdp"
                else P(("pod", "data"), None, "model"))
        return maybe_shard(logits, spec)


def forward(cfg: ArchConfig, params: Params, tokens, *, lens=None,
            extra_embeds=None) -> jax.Array:
    """Full-sequence forward (train / prefill).

    ``extra_embeds`` (B, S_img, D) are prefix embeddings (llava image
    tokens from the anyres-tiling stub) prepended to the token embeds."""
    x = embed_tokens(cfg, params, tokens)
    if extra_embeds is not None:
        x = jnp.concatenate([extra_embeds.astype(x.dtype), x], axis=1)
    b, s, _ = x.shape
    positions = jnp.arange(s)[None, :]
    x, _ = _run_blocks(cfg, params["blocks"], x, positions=positions,
                       lens=lens)
    x = L.norm_apply(cfg, params["ln_f"], x)
    return logits_from_hidden(cfg, params, x)


def loss_fn(cfg: ArchConfig, params: Params, batch: Dict[str, jax.Array]):
    logits = forward(cfg, params, batch["tokens"], lens=batch.get("lens"),
                     extra_embeds=batch.get("image_embeds"))
    labels = batch["labels"]
    if batch.get("image_embeds") is not None:
        logits = logits[:, -labels.shape[1]:]
    return cross_entropy_loss(logits, labels, batch.get("mask"))


# -------------------------------------------------------------- prefill --
def _prefill_hidden(cfg: ArchConfig, params: Params, cache: Params, tokens,
                    lens, offsets) -> Tuple[jax.Array, Params]:
    """Shared chunk pass for :func:`prefill` and :func:`verify`: embed,
    run the blocks at absolute positions ``offset + arange(S)``, norm —
    returns the (B, S, D) hidden states plus the updated cache."""
    x = embed_tokens(cfg, params, tokens)
    s = x.shape[1]
    positions = offsets[:, None] + jnp.arange(s)[None, :]
    x, new_cache = _run_blocks(cfg, params["blocks"], x, positions=positions,
                               lens=lens, caches=cache, offsets=offsets)
    return L.norm_apply(cfg, params["ln_f"], x), new_cache


def prefill(cfg: ArchConfig, params: Params, cache: Params, tokens, lens,
            offsets) -> Tuple[jax.Array, Params]:
    """Single-pass batched prefill with cache offset (the serve path).

    ``tokens`` (B, S) right-padded prompt chunks; ``lens`` (B,) true chunk
    lengths; ``offsets`` (B,) current per-row cache fill (0 = fresh).  One
    launch computes every chunk position's K/V, writes them at absolute
    cache positions ``[offset, offset+len)``, and returns
    ``(last_logits, new_cache)`` where ``last_logits[r]`` is the logits at
    row r's final valid position — the head runs on that single hidden
    state per row, never on the full (B, S, vocab) tensor.
    """
    x, new_cache = _prefill_hidden(cfg, params, cache, tokens, lens, offsets)
    b = x.shape[0]
    idx = jnp.maximum(lens - 1, 0)[:, None, None]
    last = jnp.take_along_axis(
        x, jnp.broadcast_to(idx, (b, 1, x.shape[-1])), axis=1)
    return logits_from_hidden(cfg, params, last)[:, 0], new_cache


def verify(cfg: ArchConfig, params: Params, cache: Params, tokens, lens,
           offsets) -> Tuple[jax.Array, Params]:
    """Speculative-verify pass: :func:`prefill` semantics, but the head
    runs at EVERY chunk position — ``logits[r, j]`` is the model's
    next-token distribution after consuming ``tokens[r, j]``, so one
    widened launch scores a whole drafted chunk per row.  Rows with
    ``lens[r] == 0`` write nothing (same masks as prefill)."""
    x, new_cache = _prefill_hidden(cfg, params, cache, tokens, lens, offsets)
    return logits_from_hidden(cfg, params, x), new_cache


# --------------------------------------------------------------- decode --
def init_cache(cfg: ArchConfig, batch: int, max_len: int) -> Params:
    if cfg.mla_kv_lora:
        one = lambda: L.mla_cache_init(cfg, batch, max_len)
    else:
        one = lambda: L.attn_cache_init(cfg, batch, max_len)
    return jax.tree.map(
        lambda *xs: jnp.stack(xs), *[one() for _ in range(cfg.n_layers)]) \
        if cfg.n_layers > 1 else jax.tree.map(lambda x: x[None], one())


def cache_specs(cfg: ArchConfig) -> Params:
    one = L.mla_cache_specs(cfg) if cfg.mla_kv_lora else L.attn_cache_specs(cfg)
    return jax.tree.map(lambda s: P(*((None,) + tuple(s))), one,
                        is_leaf=lambda s: isinstance(s, P))


def init_block_pool(cfg: ArchConfig, n_blocks: int,
                    block_size: int) -> Params:
    """Physical KV block pool for paged serving: the fixed-row cache with
    the batch axis reinterpreted as the block-id axis and the sequence
    axis cut to one block — leaves are ``(L, n_blocks, ..., block_size,
    ...)``.  Callers reserve id 0 as the null block (see
    :func:`repro.models.layers.paged_gather`)."""
    return init_cache(cfg, n_blocks, block_size)


def page_axes(cfg: ArchConfig) -> Params:
    """Per-leaf sequence-axis index of the layer-stacked cache/pool
    leaves (the block axis is always axis 1, per
    :func:`repro.models.registry.cache_batch_axis`)."""
    if cfg.mla_kv_lora:
        return {"kv_c": 2, "k_pe": 2}   # (L, B, S, lora/rdim)
    return {"k": 3, "v": 3}             # (L, B, hkv, S, hd)


def decode_step(cfg: ArchConfig, params: Params, cache: Params, tokens,
                lens) -> Tuple[jax.Array, Params]:
    """One decode step: tokens (B, 1), lens (B,) current cache fill."""
    x = embed_tokens(cfg, params, tokens)
    positions = lens[:, None]
    x, new_cache = _run_blocks(cfg, params["blocks"], x,
                               positions=positions, lens=lens,
                               caches=cache)
    x = L.norm_apply(cfg, params["ln_f"], x)
    return logits_from_hidden(cfg, params, x), new_cache
