"""Composable model-zoo layers (pure JAX, mesh-aware).

Every layer family exposes ``<name>_init(rng, cfg) -> params``,
``<name>_specs(cfg) -> PartitionSpec tree`` (congruent), and a pure apply
function usable in train (full-sequence) and decode (KV/state cache) modes.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..dist.context import get_mesh, maybe_shard
from .common import ArchConfig, param_init

Params = Dict[str, Any]

# activation sharding specs (logical) — "tp" profile
A_BSD = P(("pod", "data"), None, None)      # (B, S, D)
A_BSH = P(("pod", "data"), None, "model", None)  # (B, S, H, hd)
A_BSF = P(("pod", "data"), None, "model")   # (B, S, F)

# "fsdp" profile (§Perf H2): both mesh axes are data-parallel; params are
# fully sharded and gathered per layer; no TP activation collectives
_DP_ALL = ("pod", "data", "model")


def act_bsd(cfg: ArchConfig) -> P:
    return P(_DP_ALL, None, None) if cfg.sharding_profile == "fsdp" else A_BSD


def act_bsh(cfg: ArchConfig) -> P:
    return (P(_DP_ALL, None, None, None)
            if cfg.sharding_profile == "fsdp" else A_BSH)


def act_bsf(cfg: ArchConfig) -> P:
    return P(_DP_ALL, None, None) if cfg.sharding_profile == "fsdp" else A_BSF


def wspec(cfg: ArchConfig, *entries) -> P:
    """Weight spec under the arch's profile: in "fsdp", every sharded dim
    folds onto the joint DP axis group, one dim only (ZeRO-3 layout)."""
    if cfg.sharding_profile != "fsdp":
        return P(*entries)
    out, used = [], False
    for e in entries:
        if e is None or used:
            out.append(None)
        else:
            out.append(_DP_ALL)
            used = True
    return P(*out)


# ---------------------------------------------------------------- norms --
def norm_init(rng, cfg: ArchConfig, d: Optional[int] = None) -> Params:
    d = d or cfg.d_model
    p = {"scale": jnp.ones((d,), jnp.float32)}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros((d,), jnp.float32)
    return p


def norm_specs(cfg: ArchConfig) -> Params:
    p = {"scale": P(None)}
    if cfg.norm == "layernorm":
        p["bias"] = P(None)
    return p


def norm_apply(cfg: ArchConfig, p: Params, x: jax.Array) -> jax.Array:
    with jax.named_scope("norm"):
        xf = x.astype(jnp.float32)
        if cfg.norm == "layernorm":
            mu = xf.mean(-1, keepdims=True)
            xc = xf - mu
            var = (xc * xc).mean(-1, keepdims=True)
            y = xc * jax.lax.rsqrt(var + 1e-5) * p["scale"] + p["bias"]
        else:
            ms = (xf * xf).mean(-1, keepdims=True)
            y = xf * jax.lax.rsqrt(ms + 1e-6) * p["scale"]
        return y.astype(x.dtype)


# ----------------------------------------------------------------- rope --
def rope_tables(positions: jax.Array, dim: int, theta: float) -> Tuple:
    """positions (...,) -> cos/sin tables (..., dim/2)."""
    inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x (..., S, H, hd); cos/sin (..., S, hd/2) broadcast over heads."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    c = cos[..., None, :]
    s = sin[..., None, :]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c],
                           axis=-1).astype(x.dtype)


# ------------------------------------------------------------ attention --
def attn_init(rng, cfg: ArchConfig) -> Params:
    d, h, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    dt = jnp.bfloat16 if cfg.dtype == "bf16" else jnp.float32
    ks = jax.random.split(rng, 4)
    return {
        "wq": param_init(ks[0], (d, h * hd), dt),
        "wk": param_init(ks[1], (d, hkv * hd), dt),
        "wv": param_init(ks[2], (d, hkv * hd), dt),
        "wo": param_init(ks[3], (h * hd, d), dt),
    }


def attn_specs(cfg: ArchConfig) -> Params:
    return {"wq": wspec(cfg, "data", "model"),
            "wk": wspec(cfg, "data", "model"),
            "wv": wspec(cfg, "data", "model"),
            "wo": wspec(cfg, "model", "data")}


_CHUNK_THRESHOLD = 2048  # beyond this, scores are never materialized


def _pick_chunk(s: int, prefer: int = 1024) -> int:
    for c in (prefer, 512, 256, 128, 64, 32, 16, 8, 4, 2, 1):
        if s % c == 0 and c <= s:
            return c
    return 1


def _q_positions(sq: int, q_offset) -> jax.Array:
    """Absolute query positions (1|B, Sq): ``q_offset`` is a scalar or a
    per-row (B,) vector of cache offsets (batched prefill)."""
    off = jnp.asarray(q_offset)
    base = jnp.arange(sq)
    if off.ndim == 0:
        return (base + off)[None, :]
    return off[:, None] + base[None, :]


def _sdpa_chunked(q, k, v, *, causal: bool, lens, q_offset,
                  scale: Optional[float] = None) -> jax.Array:
    """FlashAttention-style online-softmax in pure jnp (XLA path).

    Identical math to kernels/flash_attention, for shapes where the full
    (Sq, Sk) score matrix must never exist (32k prefill, 4k train).
    q (B,H,Sq,hd) x k,v (B,Hkv,Sk,hd) -> (B,H,Sq,hd).
    """
    b, h, sq, hd = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    dv = v.shape[-1]  # may differ from hd (MLA: qk 192, v 128)
    group = h // hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    qc = _pick_chunk(sq)
    kc = _pick_chunk(sk)
    nq, nk = sq // qc, sk // kc
    qf = (q.astype(jnp.float32) * scale).reshape(b, hkv, group, nq, qc, hd)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    lens_b = None if lens is None else lens[:, None, None, None, None]

    def q_step(_, iq):
        qi = jax.lax.dynamic_index_in_dim(qf, iq, axis=3, keepdims=False)
        q_idx = (_q_positions(qc, q_offset) + iq * qc)[:, None, None, :, None]

        def k_step(carry, ik):
            # NOTE (§Perf H2 iter2, REFUTED): casting these einsum operands
            # to bf16 was hypothesized to halve score/probability traffic;
            # the dry-run measured +3.5–15% bytes instead — XLA already
            # fuses the p-matrix into the PV dot here, and the casts only
            # added convert-op boundary copies.  Reverted; on-target the
            # dtype choice lives inside the Pallas FA kernel's VMEM tiles.
            m, l, acc = carry
            ki = jax.lax.dynamic_slice_in_dim(kf, ik * kc, kc, axis=2)
            vi = jax.lax.dynamic_slice_in_dim(vf, ik * kc, kc, axis=2)
            s = jnp.einsum("bgnqd,bgkd->bgnqk", qi, ki)
            k_idx = (ik * kc + jnp.arange(kc))[None, None, None, None, :]
            neg = jnp.asarray(-1e30, s.dtype)
            if lens_b is not None:
                s = jnp.where(k_idx < lens_b, s, neg)
            if causal:
                s = jnp.where(k_idx <= q_idx, s, neg)
            m_new = jnp.maximum(m, s.max(-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            l_new = alpha * l + p.sum(-1, keepdims=True)
            acc_new = acc * alpha + jnp.einsum("bgnqk,bgkd->bgnqd", p, vi)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, hkv, group, qc, 1), -1e30, jnp.float32)
        l0 = jnp.zeros((b, hkv, group, qc, 1), jnp.float32)
        a0 = jnp.zeros((b, hkv, group, qc, dv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(k_step, (m0, l0, a0), jnp.arange(nk))
        l = jnp.where(l == 0.0, 1.0, l)
        return None, acc / l

    _, outs = jax.lax.scan(q_step, None, jnp.arange(nq))
    # outs: (nq, b, hkv, group, qc, dv)
    out = outs.transpose(1, 2, 3, 0, 4, 5).reshape(b, h, sq, dv)
    return out.astype(q.dtype)


def _sdpa(q, k, v, *, causal: bool, lens: Optional[jax.Array],
          q_offset=0) -> jax.Array:
    """q (B,H,Sq,hd) x k,v (B,Hkv,Sk,hd) -> (B,H,Sq,hd); f32 softmax."""
    b, h, sq, hd = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    if sq >= _CHUNK_THRESHOLD or sk > 4 * _CHUNK_THRESHOLD:
        return _sdpa_chunked(q, k, v, causal=causal, lens=lens,
                             q_offset=q_offset)
    group = h // hkv
    qf = q.astype(jnp.float32) / math.sqrt(hd)
    # grouped matmul without materializing repeated K/V
    qg = qf.reshape(b, hkv, group, sq, hd)
    s = jnp.einsum("bgnqd,bgkd->bgnqk", qg, k.astype(jnp.float32))
    k_idx = jnp.arange(sk)[None, None, None, None, :]
    neg = jnp.asarray(-1e30, s.dtype)
    if lens is not None:
        s = jnp.where(k_idx < lens[:, None, None, None, None], s, neg)
    if causal:
        q_idx = _q_positions(sq, q_offset)[:, None, None, :, None]
        s = jnp.where(k_idx <= q_idx, s, neg)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bgnqk,bgkd->bgnqd", p, v.astype(jnp.float32))
    return o.reshape(b, h, sq, hd).astype(q.dtype)


def write_chunk(cache: jax.Array, chunk: jax.Array, offsets: jax.Array,
                lens: jax.Array) -> jax.Array:
    """Write row r's first ``lens[r]`` chunk positions at cache positions
    ``[offsets[r], offsets[r] + lens[r])``; every other cache element is
    left bit-identical.

    ``cache`` (B, H, Lc, hd), ``chunk`` (B, H, S, hd), ``offsets`` >= 0.
    Each row reads and rewrites one window of ``W = min(S, Lc)``
    positions, so the work scales with B x S: the window starts at
    ``offset`` clipped to ``[0, Lc - W]`` (a plain dynamic update slice
    would clamp a chunk that runs past ``Lc`` onto the wrong positions),
    and the chunk, left-padded with W zeros, is sliced at the resulting
    shift so that window position j holds chunk position ``j - shift``.
    """
    b, _, lc, _ = cache.shape
    w = min(chunk.shape[2], lc)
    padded = jnp.pad(chunk.astype(cache.dtype),
                     ((0, 0), (0, 0), (w, 0), (0, 0)))
    start = jnp.clip(offsets, 0, lc - w)
    shift = offsets - start
    j = jnp.arange(w)[None, :] - shift[:, None]                 # (B, W)
    written = (j >= 0) & (j < lens[:, None])
    for r in range(b):
        window = jax.lax.dynamic_slice_in_dim(cache[r], start[r], w, axis=1)
        src = jax.lax.dynamic_slice_in_dim(padded[r], w - shift[r], w,
                                           axis=1)
        row = jnp.where(written[r][None, :, None], src, window)
        cache = jax.lax.dynamic_update_slice(cache, row[None],
                                             (r, 0, start[r], 0))
    return cache


def attn_apply(cfg: ArchConfig, p: Params, x: jax.Array, *,
               positions: jax.Array, lens: Optional[jax.Array] = None,
               cache: Optional[Params] = None, causal: bool = True,
               kv_source: Optional[jax.Array] = None,
               offsets: Optional[jax.Array] = None):
    """Full attention; ``cache`` switches to decode mode (x is (B,1,D)).

    ``cache`` + ``offsets`` switches to *batched prefill* mode instead
    (serve path): x is a (B, S, D) chunk whose row r holds ``lens[r]``
    true tokens destined for absolute cache positions
    ``[offsets[r], offsets[r] + lens[r])``; the chunk's K/V reach the
    cache by a per-row slice write (:func:`write_chunk`, work in B x S,
    not B x max_seq) that leaves every other position, padded chunk
    positions included, untouched; queries then attend causally against
    the whole cache at absolute positions.

    ``kv_source`` enables cross-attention (whisper decoder).

    Named scopes (HLO ``op_name`` metadata only) split the layer for a
    profile: ``attn/qkv`` (projections and rotary), ``attn/cache_write``
    (the cache update), ``attn/core`` (the attention itself) and
    ``attn/out`` (the output projection)."""
    b, s, d = x.shape
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    src = x if kv_source is None else kv_source
    with jax.named_scope("attn/qkv"):
        q = (x @ p["wq"]).reshape(b, s, h, hd)
        k = (src @ p["wk"]).reshape(b, src.shape[1], hkv, hd)
        v = (src @ p["wv"]).reshape(b, src.shape[1], hkv, hd)
        q = maybe_shard(q, act_bsh(cfg))
        if kv_source is None:  # self-attention: rope
            cos, sin = rope_tables(positions, hd, cfg.rope_theta)
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)
        q = q.transpose(0, 2, 1, 3)
        k = k.transpose(0, 2, 1, 3)
        v = v.transpose(0, 2, 1, 3)
    new_cache = None
    if cache is not None and offsets is not None:
        # batched prefill: a per-row slice write puts the chunk's K/V at
        # absolute positions [offset, offset+len) — padded chunk positions
        # are never written — then queries attend causally against the
        # whole cache
        with jax.named_scope("attn/cache_write"):
            kc = write_chunk(cache["k"], k, offsets, lens)
            vc = write_chunk(cache["v"], v, offsets, lens)
            new_cache = {"k": kc, "v": vc}
        with jax.named_scope("attn/core"):
            o = _sdpa(q, kc.astype(q.dtype), vc.astype(q.dtype), causal=True,
                      lens=None, q_offset=offsets)
    elif cache is not None:
        # decode: append to cache at position lens (per batch row)
        with jax.named_scope("attn/cache_write"):
            kc, vc = cache["k"], cache["v"]
            idx = lens[:, None, None, None]  # (B,1,1,1) write position
            pos_iota = jnp.arange(kc.shape[2])[None, None, :, None]
            write = pos_iota == idx
            kc = jnp.where(write, k.astype(kc.dtype), kc)
            vc = jnp.where(write, v.astype(vc.dtype), vc)
            new_cache = {"k": kc, "v": vc}
        with jax.named_scope("attn/core"):
            o = _sdpa(q, kc.astype(q.dtype), vc.astype(q.dtype),
                      causal=False, lens=lens + 1)
    else:
        with jax.named_scope("attn/core"):
            o = _sdpa(q, k, v, causal=causal and kv_source is None,
                      lens=lens, q_offset=0)
    with jax.named_scope("attn/out"):
        o = o.transpose(0, 2, 1, 3).reshape(b, s, h * hd)
        out = o @ p["wo"]
        return maybe_shard(out, act_bsd(cfg)), new_cache


def attn_cache_init(cfg: ArchConfig, batch: int, max_len: int) -> Params:
    hkv, hd = cfg.n_kv_heads, cfg.hd
    dt = jnp.bfloat16 if cfg.dtype == "bf16" else jnp.float32
    return {"k": jnp.zeros((batch, hkv, max_len, hd), dt),
            "v": jnp.zeros((batch, hkv, max_len, hd), dt)}


def attn_cache_specs(cfg: ArchConfig) -> Params:
    # few KV heads (< model-axis size 16, e.g. MQA/GQA): shard the sequence
    # axis of the cache instead of heads so the 16-way split divides evenly
    kv_spec = (P(("pod", "data"), "model", None, None)
               if cfg.n_kv_heads >= 16 else
               P(("pod", "data"), None, "model", None))
    return {"k": kv_spec, "v": kv_spec}


# ------------------------------------------------------ MLA (deepseek) --
MLA_ABSORBED_DECODE = True  # §Perf H3 switch (tests bisect against False)


def mla_init(rng, cfg: ArchConfig) -> Params:
    d, h, hd = cfg.d_model, cfg.n_heads, cfg.hd
    lora, rdim = cfg.mla_kv_lora, cfg.mla_rope_dim
    dt = jnp.bfloat16 if cfg.dtype == "bf16" else jnp.float32
    ks = jax.random.split(rng, 6)
    return {
        "wq": param_init(ks[0], (d, h * (hd + rdim)), dt),
        "w_dkv": param_init(ks[1], (d, lora), dt),
        "w_kpe": param_init(ks[2], (d, rdim), dt),
        "w_uk": param_init(ks[3], (lora, h * hd), dt),
        "w_uv": param_init(ks[4], (lora, h * hd), dt),
        "wo": param_init(ks[5], (h * hd, d), dt),
    }


def mla_specs(cfg: ArchConfig) -> Params:
    return {"wq": P("data", "model"), "w_dkv": P("data", None),
            "w_kpe": P("data", None), "w_uk": P(None, "model"),
            "w_uv": P(None, "model"), "wo": P("model", "data")}


def mla_apply(cfg: ArchConfig, p: Params, x: jax.Array, *,
              positions: jax.Array, lens=None, cache=None,
              offsets: Optional[jax.Array] = None):
    """Multi-head latent attention: cache holds the 512-d compressed kv.

    ``cache`` + ``offsets`` is batched prefill mode (see
    :func:`attn_apply`): the chunk's compressed K/V are scattered to
    absolute cache positions and queries attend causally at absolute
    positions through the expansion path (never the absorbed-decode
    shortcut)."""
    b, s, d = x.shape
    h, hd, rdim = cfg.n_heads, cfg.hd, cfg.mla_rope_dim
    q = (x @ p["wq"]).reshape(b, s, h, hd + rdim)
    q_nope, q_pe = q[..., :hd], q[..., hd:]
    kv_c = x @ p["w_dkv"]                       # (B,S,lora)
    k_pe = (x @ p["w_kpe"]).reshape(b, s, 1, rdim)
    cos, sin = rope_tables(positions, rdim, cfg.rope_theta)
    q_pe = apply_rope(q_pe, cos, sin)
    k_pe = apply_rope(k_pe, cos, sin)
    k_pe = k_pe[..., 0, :]                      # (B,S,rdim)
    new_cache = None
    if cache is not None and offsets is not None:
        # batched prefill: scatter the chunk's compressed K/V to absolute
        # positions [offset, offset+len) per row (padded positions are
        # never written), then attend causally at absolute positions
        lc = cache["kv_c"].shape[1]
        j = jnp.arange(lc)[None, :] - offsets[:, None]          # (B, Lc)
        written = ((j >= 0) & (j < lens[:, None]))[:, :, None]
        jc = jnp.clip(j, 0, s - 1)
        kv_al = jnp.take_along_axis(
            kv_c, jnp.broadcast_to(jc[:, :, None], (b, lc, kv_c.shape[-1])),
            axis=1)
        kpe_al = jnp.take_along_axis(
            k_pe, jnp.broadcast_to(jc[:, :, None], (b, lc, rdim)), axis=1)
        kv_all = jnp.where(written, kv_al.astype(cache["kv_c"].dtype),
                           cache["kv_c"])
        kpe_all = jnp.where(written, kpe_al.astype(cache["k_pe"].dtype),
                            cache["k_pe"])
        new_cache = {"kv_c": kv_all, "k_pe": kpe_all}
        eff_lens = None
        causal = True
    elif cache is not None:
        pos = jnp.arange(cache["kv_c"].shape[1])[None, :, None]
        write = pos == lens[:, None, None]
        kv_all = jnp.where(write, kv_c.astype(cache["kv_c"].dtype),
                           cache["kv_c"])
        kpe_all = jnp.where(write, k_pe.astype(cache["k_pe"].dtype),
                            cache["k_pe"])
        new_cache = {"kv_c": kv_all, "k_pe": kpe_all}
        eff_lens = lens + 1
        causal = False
    else:
        kv_all, kpe_all = kv_c, k_pe
        eff_lens = lens
        causal = True
    if cache is not None and offsets is None and s == 1 \
            and MLA_ABSORBED_DECODE:
        # §Perf H3: ABSORBED decode — W_uk folds into the query and W_uv
        # into the output, so attention runs directly against the 512-d
        # latent cache; the (B, S, H, hd) K/V expansion never exists.
        lora = cfg.mla_kv_lora
        w_uk = p["w_uk"].reshape(lora, h, hd)
        w_uv = p["w_uv"].reshape(lora, h, hd)
        q_abs = jnp.einsum("bqhd,lhd->bqhl", q_nope.astype(jnp.float32),
                           w_uk.astype(jnp.float32))      # (B,1,H,lora)
        kvf = kv_all.astype(jnp.bfloat16)
        # bf16 outputs + explicit f32 upcast (XLA:CPU lacks the mixed
        # BF16xBF16=F32 dot thunk; TPU MXU accumulates f32 regardless)
        s_nope = jnp.einsum("bqhl,bsl->bhqs",
                            q_abs.astype(jnp.bfloat16),
                            kvf).astype(jnp.float32)
        s_pe = jnp.einsum("bqhd,bsd->bhqs", q_pe.astype(jnp.float32),
                          kpe_all.astype(jnp.float32))
        sc = (s_nope + s_pe) * (1.0 / math.sqrt(hd + rdim))
        k_idx = jnp.arange(kv_all.shape[1])[None, None, None, :]
        sc = jnp.where(k_idx < eff_lens[:, None, None, None], sc, -1e30)
        prob = jax.nn.softmax(sc, axis=-1)
        o_lat = jnp.einsum("bhqs,bsl->bqhl",
                           prob.astype(jnp.bfloat16),
                           kvf).astype(jnp.float32)  # (B,1,H,lora)
        o = jnp.einsum("bqhl,lhd->bqhd", o_lat,
                       w_uv.astype(jnp.float32))
        out = o.reshape(b, s, h * hd).astype(x.dtype) @ p["wo"]
        return maybe_shard(out, A_BSD), new_cache

    # prefill/train: expand per-head keys/values from the compressed cache,
    # then fold the rope component into the head dim: scores =
    # [q_nope|q_pe]·[k_nope|k_pe] so the chunked SDPA path applies unchanged
    sk = kv_all.shape[1]
    k_nope = (kv_all @ p["w_uk"]).reshape(b, sk, h, hd)
    v = (kv_all @ p["w_uv"]).reshape(b, sk, h, hd)
    q_eff = jnp.concatenate([q_nope, q_pe], axis=-1)      # (B,S,H,hd+r)
    k_pe_b = jnp.broadcast_to(kpe_all[:, :, None, :], (b, sk, h, rdim))
    k_eff = jnp.concatenate([k_nope, k_pe_b.astype(k_nope.dtype)], axis=-1)
    q_eff = q_eff.transpose(0, 2, 1, 3)
    k_eff = k_eff.transpose(0, 2, 1, 3)
    v_t = v.transpose(0, 2, 1, 3)
    scale = 1.0 / math.sqrt(hd + rdim)
    q_off = 0 if offsets is None else offsets
    if s >= _CHUNK_THRESHOLD or sk > 4 * _CHUNK_THRESHOLD:
        o = _sdpa_chunked(q_eff, k_eff, v_t, causal=causal, lens=eff_lens,
                          q_offset=q_off, scale=scale)
    else:
        sc = jnp.einsum("bhqd,bhkd->bhqk", q_eff.astype(jnp.float32),
                        k_eff.astype(jnp.float32)) * scale
        k_idx = jnp.arange(sk)[None, None, None, :]
        neg = jnp.asarray(-1e30, sc.dtype)
        if eff_lens is not None:
            sc = jnp.where(k_idx < eff_lens[:, None, None, None], sc, neg)
        if causal:
            q_idx = _q_positions(s, q_off)[:, None, :, None]
            sc = jnp.where(k_idx <= q_idx, sc, neg)
        prob = jax.nn.softmax(sc, axis=-1)
        o = jnp.einsum("bhqk,bhkd->bhqd", prob,
                       v_t.astype(jnp.float32)).astype(x.dtype)
    out = o.transpose(0, 2, 1, 3).reshape(b, s, h * hd).astype(x.dtype) @ p["wo"]
    return maybe_shard(out, A_BSD), new_cache


def mla_cache_init(cfg: ArchConfig, batch: int, max_len: int) -> Params:
    dt = jnp.bfloat16 if cfg.dtype == "bf16" else jnp.float32
    return {"kv_c": jnp.zeros((batch, max_len, cfg.mla_kv_lora), dt),
            "k_pe": jnp.zeros((batch, max_len, cfg.mla_rope_dim), dt)}


def mla_cache_specs(cfg: ArchConfig) -> Params:
    return {"kv_c": P(("pod", "data"), "model", None),
            "k_pe": P(("pod", "data"), "model", None)}


# ----------------------------------------------------- paged KV blocks --
def paged_gather(pool: jax.Array, tables: jax.Array, *, block_axis: int,
                 seq_axis: int) -> jax.Array:
    """Gather per-row cache rows out of a physical block pool.

    ``pool`` holds the blocks: ``block_axis`` is the block-id axis (size
    ``n_blocks + 1``, id 0 = the null block), ``seq_axis`` the
    within-block token axis (size ``block_size``).  ``tables`` (B, M)
    maps each row's logical block ``j`` to a physical id (null-padded
    with 0).  The result is a dense per-row leaf — block axis replaced by
    the row axis B, seq axis widened to ``M * block_size`` — which is
    exactly the fixed-row layout :func:`attn_apply` / :func:`mla_apply`
    consume, so the attention kernels run unchanged on paged caches.
    """
    bs = pool.shape[seq_axis]
    b, m = tables.shape
    x = jnp.moveaxis(pool, (block_axis, seq_axis), (0, 1))
    flat = x.reshape((x.shape[0] * bs,) + x.shape[2:])
    pos = jnp.arange(m * bs)
    idx = tables[:, pos // bs] * bs + (pos % bs)[None, :]      # (B, M*bs)
    return jnp.moveaxis(flat[idx], (0, 1), (block_axis, seq_axis))


def paged_scatter(pool: jax.Array, dense: jax.Array, tables: jax.Array,
                  keep: jax.Array, *, block_axis: int,
                  seq_axis: int) -> jax.Array:
    """Scatter dense per-row cache leaves back into the block pool.

    Inverse of :func:`paged_gather` restricted to the token positions
    selected by ``keep`` (B, M*block_size) — only freshly written
    positions persist.  Positions with ``keep`` False, and any position
    whose (bucket- or null-) padded table entry is 0, are routed into the
    null block, which absorbs them the way masked writes do on the fixed
    path.
    """
    bs = pool.shape[seq_axis]
    b, m = tables.shape
    x = jnp.moveaxis(pool, (block_axis, seq_axis), (0, 1))
    nb = x.shape[0]
    flat = x.reshape((nb * bs,) + x.shape[2:])
    d = jnp.moveaxis(dense, (block_axis, seq_axis), (0, 1))
    s = m * bs
    pos = jnp.arange(s)
    idx = tables[:, pos // bs] * bs + (pos % bs)[None, :]
    idx = jnp.where(keep, idx, (pos % bs)[None, :])    # null-block sink
    flat = flat.at[idx.reshape(-1)].set(
        d.reshape((b * s,) + d.shape[2:]).astype(flat.dtype))
    out = flat.reshape((nb, bs) + flat.shape[1:])
    return jnp.moveaxis(out, (0, 1), (block_axis, seq_axis))


# ------------------------------------------------------------------ mlp --
def mlp_init(rng, cfg: ArchConfig, d_ff: Optional[int] = None) -> Params:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    dt = jnp.bfloat16 if cfg.dtype == "bf16" else jnp.float32
    ks = jax.random.split(rng, 3)
    p = {"w_in": param_init(ks[0], (d, f), dt),
         "w_out": param_init(ks[1], (f, d), dt)}
    if cfg.act == "silu":
        p["w_gate"] = param_init(ks[2], (d, f), dt)
    return p


def mlp_specs(cfg: ArchConfig) -> Params:
    p = {"w_in": wspec(cfg, "data", "model"),
         "w_out": wspec(cfg, "model", "data")}
    if cfg.act == "silu":
        p["w_gate"] = wspec(cfg, "data", "model")
    return p


def mlp_apply(cfg: ArchConfig, p: Params, x: jax.Array) -> jax.Array:
    with jax.named_scope("mlp"):
        h = x @ p["w_in"]
        if cfg.act == "silu":
            h = jax.nn.silu(x @ p["w_gate"]) * h
        else:
            h = jax.nn.gelu(h)
        h = maybe_shard(h, act_bsf(cfg))
        return maybe_shard(h @ p["w_out"], act_bsd(cfg))


# ------------------------------------------------------------------ moe --
def moe_init(rng, cfg: ArchConfig) -> Params:
    d, e, f = cfg.d_model, cfg.n_experts, cfg.expert_width
    dt = jnp.bfloat16 if cfg.dtype == "bf16" else jnp.float32
    ks = jax.random.split(rng, 5)
    p = {
        "router": param_init(ks[0], (d, e), jnp.float32),
        "w_in": param_init(ks[1], (e, d, f), dt),
        "w_gate": param_init(ks[2], (e, d, f), dt),
        "w_out": param_init(ks[3], (e, f, d), dt),
    }
    if cfg.n_shared_experts:
        sub = jax.random.split(ks[4], 3)
        fs = f * cfg.n_shared_experts
        p["shared"] = {"w_in": param_init(sub[0], (d, fs), dt),
                       "w_gate": param_init(sub[1], (d, fs), dt),
                       "w_out": param_init(sub[2], (fs, d), dt)}
    return p


def moe_specs(cfg: ArchConfig) -> Params:
    p = {"router": P(None, None),
         "w_in": P("model", "data", None),
         "w_gate": P("model", "data", None),
         "w_out": P("model", None, "data")}
    if cfg.n_shared_experts:
        p["shared"] = {"w_in": P("data", "model"),
                       "w_gate": P("data", "model"),
                       "w_out": P("model", "data")}
    return p


def _moe_experts_local(cfg: ArchConfig, w_in, w_gate, w_out, x_tokens,
                       gates, ids, capacity: int):
    """Sort-based capacity dispatch over a *local* expert slice.

    x_tokens (T, D); gates/ids (T, k); experts (E_loc, D, F).  Tokens routed
    to expert e get slots [0, capacity); overflow drops (standard GShard
    token dropping).  No one-hot dispatch einsum — scatter/gather keeps
    compiled FLOPs equal to useful FLOPs (DESIGN §9 beyond-paper note).
    """
    t, dmod = x_tokens.shape
    e_loc = w_in.shape[0]
    k = ids.shape[1]
    flat_e = ids.reshape(-1)                       # (T*k,) expert ids (local)
    flat_g = gates.reshape(-1)
    flat_tok = jnp.repeat(jnp.arange(t), k)
    valid = (flat_e >= 0) & (flat_e < e_loc)
    key = jnp.where(valid, flat_e, e_loc)          # invalid sorts last
    order = jnp.argsort(key)                       # stable
    se, st, sg = key[order], flat_tok[order], flat_g[order]
    # rank within expert: position - start offset of that expert
    counts = jnp.bincount(se, length=e_loc + 1)
    starts = jnp.cumsum(counts) - counts
    pos_in_e = jnp.arange(se.shape[0]) - starts[se]
    keep = (se < e_loc) & (pos_in_e < capacity)
    slot = jnp.where(keep, se * capacity + pos_in_e, e_loc * capacity)
    # gather tokens into padded expert buffers (E_loc*C, D)
    buf = jnp.zeros((e_loc * capacity + 1, dmod), x_tokens.dtype)
    buf = buf.at[slot].add(jnp.where(keep[:, None], x_tokens[st], 0))
    buf = buf[:-1].reshape(e_loc, capacity, dmod)
    h = jnp.einsum("ecd,edf->ecf", buf, w_in)
    g = jnp.einsum("ecd,edf->ecf", buf, w_gate)
    h = jax.nn.silu(g) * h
    out = jnp.einsum("ecf,efd->ecd", h, w_out)     # (E_loc, C, D)
    out_flat = out.reshape(e_loc * capacity, dmod)
    # combine back: weighted scatter-add into tokens
    contrib = jnp.where(keep[:, None],
                        out_flat[jnp.minimum(slot, e_loc * capacity - 1)]
                        * sg[:, None].astype(out_flat.dtype), 0)
    y = jnp.zeros_like(x_tokens).at[st].add(contrib)
    return y


def moe_apply(cfg: ArchConfig, p: Params, x: jax.Array) -> jax.Array:
    """Top-k routed MoE with optional shared experts (dbrx / deepseek-v2).

    Distributed mode (mesh active): expert-parallel over the "model" axis
    via shard_map — tokens are replicated across EP ranks (they already are
    under the activation sharding), each rank runs its expert slice at
    local capacity, partial outputs psum over "model".
    """
    b, s, d = x.shape
    tokens = x.reshape(-1, d)
    t = tokens.shape[0]
    logits = (tokens.astype(jnp.float32) @ p["router"])  # (T, E)
    gates, ids = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), cfg.top_k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    gates = gates.astype(x.dtype)

    mesh = get_mesh()
    e = cfg.n_experts
    if mesh is not None and "model" in mesh.axis_names:
        ep = mesh.shape["model"]
        e_loc = e // ep
        # capacity is per DATA-shard token count — each EP rank sees only
        # its data shard's tokens (replicated across the model axis)
        dp = 1
        for ax in ("pod", "data"):
            if ax in mesh.axis_names:
                dp *= mesh.shape[ax]
        t_loc = max(t // dp, 1)
        cap = int(cfg.capacity_factor * t_loc * cfg.top_k / e)
        cap = max(8, -(-cap // 8) * 8)

        def ep_body(w_in, w_gate, w_out, toks, gat, idd):
            r = jax.lax.axis_index("model")
            local_ids = idd - r * e_loc  # out-of-slice ids become invalid
            y = _moe_experts_local(cfg, w_in, w_gate, w_out,
                                   toks, gat, local_ids, cap)
            # each token's k experts may live on different EP ranks
            return jax.lax.psum(y, "model")

        dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        tok_spec = P(dp_axes if dp_axes else None, None)
        y = jax.shard_map(
            ep_body, mesh=mesh,
            in_specs=(P("model", None, None), P("model", None, None),
                      P("model", None, None),
                      tok_spec, tok_spec, tok_spec),
            out_specs=tok_spec,
            check_vma=False,
        )(p["w_in"], p["w_gate"], p["w_out"], tokens, gates, ids)
    else:
        cap = int(cfg.capacity_factor * t * cfg.top_k / max(e, 1))
        cap = max(4, cap)
        y = _moe_experts_local(cfg, p["w_in"], p["w_gate"], p["w_out"],
                               tokens, gates, ids, cap)

    if cfg.n_shared_experts:
        sh = p["shared"]
        hs = jax.nn.silu(tokens @ sh["w_gate"]) * (tokens @ sh["w_in"])
        y = y + hs @ sh["w_out"]
    return maybe_shard(y.reshape(b, s, d), A_BSD)


# --------------------------------------------------------------- mamba2 --
def mamba2_init(rng, cfg: ArchConfig) -> Params:
    d = cfg.d_model
    d_in = 2 * d
    n, hp = cfg.ssm_state, cfg.ssm_head_dim
    n_heads = d_in // hp
    dt = jnp.bfloat16 if cfg.dtype == "bf16" else jnp.float32
    ks = jax.random.split(rng, 6)
    return {
        "w_x": param_init(ks[0], (d, d_in), dt),
        "w_z": param_init(ks[1], (d, d_in), dt),
        "w_bc": param_init(ks[2], (d, 2 * n), dt),
        "w_dt": param_init(ks[3], (d, n_heads), dt),
        "a_log": jnp.zeros((n_heads,), jnp.float32),
        "w_out": param_init(ks[4], (d_in, d), dt),
        "skip": param_init(ks[5], (n_heads,), jnp.float32, scale=1.0),
    }


def mamba2_specs(cfg: ArchConfig) -> Params:
    return {"w_x": P("data", "model"), "w_z": P("data", "model"),
            "w_bc": P("data", None), "w_dt": P("data", "model"),
            "a_log": P("model"), "w_out": P("model", "data"),
            "skip": P("model")}


def _ssd_chunked(x, a, bmat, cmat, chunk: int):
    """jnp mirror of kernels/mamba2: chunk-parallel SSD scan.

    x (B,H,T,P); a (B,H,T,1); b,c (B,H,T,N) -> (B,H,T,P)."""
    bs, h, t, pdim = x.shape
    n = bmat.shape[-1]
    nc = t // chunk
    xs = x.reshape(bs, h, nc, chunk, pdim)
    as_ = a.reshape(bs, h, nc, chunk, 1)
    bs_ = bmat.reshape(bs, h, nc, chunk, n)
    cs_ = cmat.reshape(bs, h, nc, chunk, n)
    log_a = jnp.log(jnp.maximum(as_, 1e-37))
    cum = jnp.cumsum(log_a, axis=3)                      # (..., chunk, 1)
    g = jnp.exp(cum)
    ratio = jnp.exp(cum - cum.swapaxes(-1, -2))          # (..., chunk, chunk)
    tt = jnp.arange(chunk)
    l_mask = jnp.where(tt[:, None] >= tt[None, :], ratio, 0.0)
    scores = jnp.einsum("bhctn,bhcsn->bhcts", cs_, bs_) * l_mask
    y_intra = jnp.einsum("bhcts,bhcsp->bhctp", scores, xs)
    # inter-chunk state carried with a scan over chunks
    decay_end = jnp.exp(cum[..., -1:, :] - cum)          # (..., chunk, 1)
    b_x = jnp.einsum("bhctn,bhctp->bhcnp", bs_ * decay_end, xs)
    g_last = g[..., -1, 0]                               # (B,H,nc)

    def carry(h_prev, inp):
        bx_c, gl_c = inp
        h_new = gl_c[..., None, None] * h_prev + bx_c
        # §Perf H4 (H1-iter3 lesson transplanted): f32 carry, bf16 stack —
        # the stacked per-chunk states dominate the SSD HBM term
        return h_new, h_prev.astype(jnp.bfloat16)

    h0 = jnp.zeros((bs, h, n, pdim), jnp.float32)
    _, h_prevs = jax.lax.scan(
        carry, h0, (b_x.transpose(2, 0, 1, 3, 4), g_last.transpose(2, 0, 1)))
    h_prevs = h_prevs.transpose(1, 2, 0, 3, 4)           # (B,H,nc,N,P)
    y_inter = g * jnp.einsum("bhctn,bhcnp->bhctp", cs_,
                             h_prevs.astype(jnp.float32))
    return (y_intra + y_inter).reshape(bs, h, t, pdim)


def mamba2_apply(cfg: ArchConfig, p: Params, x: jax.Array, *,
                 cache: Optional[Params] = None):
    """Mamba-2 block; cache mode = single-token state update."""
    b, s, d = x.shape
    d_in = 2 * d
    n, hp = cfg.ssm_state, cfg.ssm_head_dim
    n_heads = d_in // hp
    xz = x @ p["w_x"]
    z = jax.nn.silu(x @ p["w_z"])
    bc = x @ p["w_bc"]
    bmat, cmat = bc[..., :n], bc[..., n:]
    dt_ = jax.nn.softplus((x @ p["w_dt"]).astype(jnp.float32))  # (B,S,H)
    a = jnp.exp(-dt_ * jnp.exp(p["a_log"]))                     # (B,S,H)
    xh = xz.reshape(b, s, n_heads, hp).transpose(0, 2, 1, 3)
    ah = a.transpose(0, 2, 1)[..., None]                        # (B,H,S,1)
    bh = jnp.broadcast_to(bmat[:, None], (b, n_heads, s, n))
    ch = jnp.broadcast_to(cmat[:, None], (b, n_heads, s, n))
    new_cache = None
    if cache is not None:
        h_prev = cache["h"]                                     # (B,H,N,P)
        xt = xh[:, :, 0].astype(jnp.float32)                    # (B,H,P)
        at = ah[:, :, 0]                                        # (B,H,1)
        bt = bh[:, :, 0].astype(jnp.float32)
        ct = ch[:, :, 0].astype(jnp.float32)
        h_new = at[..., None] * h_prev + jnp.einsum("bhn,bhp->bhnp", bt, xt)
        y = jnp.einsum("bhn,bhnp->bhp", ct, h_new)[:, :, None]  # (B,H,1,P)
        new_cache = {"h": h_new}
    else:
        # §Perf H4: chunk 64 (fewer stacked states) when the length allows
        if s % 64 == 0:
            chunk = 64
        elif s % 16 == 0:
            chunk = 16
        elif s % 8 == 0:
            chunk = 8
        else:
            chunk = s
        y = _ssd_chunked(xh.astype(jnp.float32), ah,
                         bh.astype(jnp.float32), ch.astype(jnp.float32),
                         chunk)
    y = y + p["skip"][None, :, None, None] * xh.astype(jnp.float32)
    y = y.transpose(0, 2, 1, 3).reshape(b, s, d_in).astype(x.dtype)
    out = (y * z) @ p["w_out"]
    return maybe_shard(out, A_BSD), new_cache


def mamba2_cache_init(cfg: ArchConfig, batch: int) -> Params:
    d_in = 2 * cfg.d_model
    n_heads = d_in // cfg.ssm_head_dim
    return {"h": jnp.zeros((batch, n_heads, cfg.ssm_state, cfg.ssm_head_dim),
                           jnp.float32)}


def mamba2_cache_specs(cfg: ArchConfig) -> Params:
    return {"h": P(("pod", "data"), "model", None, None)}


# ---------------------------------------------------------------- rwkv6 --
def rwkv6_init(rng, cfg: ArchConfig) -> Params:
    d = cfg.d_model
    hp = cfg.ssm_head_dim
    n_heads = d // hp
    dt = jnp.bfloat16 if cfg.dtype == "bf16" else jnp.float32
    ks = jax.random.split(rng, 8)
    return {
        "w_r": param_init(ks[0], (d, d), dt),
        "w_k": param_init(ks[1], (d, d), dt),
        "w_v": param_init(ks[2], (d, d), dt),
        "w_g": param_init(ks[3], (d, d), dt),
        "w_w": param_init(ks[4], (d, d), dt),      # data-dependent decay proj
        "u": param_init(ks[5], (n_heads, hp), jnp.float32, scale=0.1),
        "w_out": param_init(ks[6], (d, d), dt),
        "mix": param_init(ks[7], (5, d), jnp.float32, scale=0.1),
    }


def rwkv6_specs(cfg: ArchConfig) -> Params:
    return {"w_r": P("data", "model"), "w_k": P("data", "model"),
            "w_v": P("data", "model"), "w_g": P("data", "model"),
            "w_w": P("data", "model"), "u": P("model", None),
            "w_out": P("model", "data"), "mix": P(None, None)}


def _wkv_chunked(r, k, v, w, u, chunk: int = 16, fast_dtype=jnp.bfloat16,
                 w_is_log: bool = False):
    """Chunk-parallel WKV (§Perf hillclimb H1, GLA-style).

    The per-timestep scan materializes O(T) state-sized buffers at HBM
    fusion boundaries; this form materializes O(T/chunk) and turns the
    recurrence into MXU matmuls.  All exponentials are differences of
    *causally ordered* cumulative log-decays, hence ≤ 0 → exp ≤ 1 →
    numerically safe for any data-dependent decay (no k/decay division).

    r,k,w: (B,H,T,K); v: (B,H,T,V); u: (H,K) -> (B,H,T,V)
    """
    b, h, t, dk = r.shape
    dv = v.shape[-1]
    nc = t // chunk
    rs = r.reshape(b, h, nc, chunk, dk)
    ks = k.reshape(b, h, nc, chunk, dk)
    vs = v.reshape(b, h, nc, chunk, dv)
    ws = w.reshape(b, h, nc, chunk, dk)

    # callers may pass LOG decay directly (negative values) to skip the
    # exp→log roundtrip and its (B,T,K) f32 materialization (H1 iter4)
    if w_is_log:
        log_w = ws
    else:
        log_w = jnp.log(jnp.maximum(ws, 1e-37))        # ≤ 0
    cum = jnp.cumsum(log_w, axis=3)                    # inclusive
    cum_excl = cum - log_w                             # exclusive

    # intra-chunk: scores[t,s] = Σ_k r_t k_s exp(cum_excl_t - cum_s), s<t
    d_ts = cum_excl[..., :, None, :] - cum[..., None, :, :]  # (..,C,C,K) ≤0 causal
    tt = jnp.arange(chunk)
    causal = (tt[:, None] > tt[None, :])[None, None, None, :, :, None]
    decay_ts = jnp.where(causal, jnp.exp(jnp.minimum(d_ts, 0.0)), 0.0)
    # §Perf H1 iter2: the (C,C,K) intermediate dominates HBM traffic — carry
    # it in bf16 (all entries ∈ [0,1]) with f32 accumulation in the reduce
    scores = jnp.einsum("bhntk,bhnsk,bhntsk->bhnts",
                        rs.astype(fast_dtype), ks.astype(fast_dtype),
                        decay_ts.astype(fast_dtype),
                        preferred_element_type=jnp.float32)
    diag = jnp.einsum("bhntk,hk,bhntk->bhnt", rs, u, ks)
    y_intra = jnp.einsum("bhnts,bhnsv->bhntv", scores, vs) \
        + diag[..., None] * vs

    # inter-chunk: y_t += (r_t ⊙ exp(cum_excl_t)) @ S_chunk_start
    r_tilde = rs * jnp.exp(cum_excl)                   # ≤ |r|
    # state carry: S_end = diag(exp(cum_last)) S0 + Σ_s (k_s⊙exp(cum_last-cum_s))ᵀ v_s
    k_tilde = ks * jnp.exp(cum[..., -1:, :] - cum)     # exps ≤ 1
    # iter4: per-chunk kv outer products in bf16 (f32 accumulate in carry)
    kv_chunk = jnp.einsum("bhnsk,bhnsv->bhnkv",
                          k_tilde.astype(fast_dtype), vs.astype(fast_dtype),
                          preferred_element_type=jnp.float32)
    g_last = jnp.exp(cum[..., -1, :])                  # (B,H,nc,K)

    def carry(s_prev, inp):
        kv_c, gl_c = inp                               # (B,H,K,V), (B,H,K)
        s_new = gl_c[..., None] * s_prev + kv_c
        # §Perf H1 iter3: carry stays f32; the STACKED per-chunk states
        # (the dominant HBM term) are emitted in bf16
        return s_new, s_prev.astype(fast_dtype)

    s0 = jnp.zeros((b, h, dk, dv), jnp.float32)
    _, s_prevs = jax.lax.scan(
        carry, s0, (kv_chunk.transpose(2, 0, 1, 3, 4),
                    g_last.transpose(2, 0, 1, 3)))
    s_prevs = s_prevs.transpose(1, 2, 0, 3, 4)         # (B,H,nc,K,V)
    y_inter = jnp.einsum("bhntk,bhnkv->bhntv",
                         r_tilde.astype(fast_dtype), s_prevs,
                         preferred_element_type=jnp.float32)
    return (y_intra + y_inter).reshape(b, h, t, dv)


def _wkv_scan(r, k, v, w, u):
    """jnp sequential oracle form: r,k,w (B,H,T,K); v (B,H,T,V); u (H,K)."""
    dk, dv = r.shape[-1], v.shape[-1]

    def step(s, inp):
        rt, kt, vt, wt = inp                        # (B,H,K/V)
        kv = jnp.einsum("bhk,bhv->bhkv", kt, vt)
        yt = jnp.einsum("bhk,bhkv->bhv", rt, s + u[None, :, :, None] * kv)
        s = wt[..., None] * s + kv
        return s, yt

    b, h = r.shape[0], r.shape[1]
    s0 = jnp.zeros((b, h, dk, dv), jnp.float32)
    xs = (r.transpose(2, 0, 1, 3), k.transpose(2, 0, 1, 3),
          v.transpose(2, 0, 1, 3), w.transpose(2, 0, 1, 3))
    s_fin, ys = jax.lax.scan(step, s0, xs)
    return ys.transpose(1, 2, 0, 3), s_fin


def rwkv6_apply(cfg: ArchConfig, p: Params, x: jax.Array, *,
                cache: Optional[Params] = None):
    """RWKV-6 time-mix block (token-shift simplified to previous-x mix)."""
    b, s, d = x.shape
    hp = cfg.ssm_head_dim
    n_heads = d // hp
    if cache is not None:
        x_prev = cache["x_prev"][:, None]           # (B,1,D)
    else:
        x_prev = jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1]
    # H1 iter6: token-shift mix arithmetic in the activation dtype — the
    # f32 mix params otherwise promote 5 (B,T,D) chains to f32 (dominant
    # residual HBM term after iter3)
    mix = jax.nn.sigmoid(p["mix"]).astype(x.dtype)  # (5, D)

    def mixed(i):
        return x * mix[i] + x_prev * (1 - mix[i])

    r = (mixed(0) @ p["w_r"]).reshape(b, s, n_heads, hp).transpose(0, 2, 1, 3)
    k = (mixed(1) @ p["w_k"]).reshape(b, s, n_heads, hp).transpose(0, 2, 1, 3)
    v = (mixed(2) @ p["w_v"]).reshape(b, s, n_heads, hp).transpose(0, 2, 1, 3)
    g = jax.nn.silu(mixed(3) @ p["w_g"])
    # log-decay computed directly (H1 iter4: skip exp→log roundtrip)
    log_dec = -jnp.exp((mixed(4) @ p["w_w"]).astype(jnp.float32).clip(-8, 4))
    log_dec = log_dec.reshape(b, s, n_heads, hp).transpose(0, 2, 1, 3)
    # H1 iter5: no blanket f32 casts — precision is chosen per-einsum
    # inside the chunked path; decode/scan paths cast locally
    rf, kf, vf = r, k, v
    new_cache = None
    if cache is not None:
        s_prev = cache["s"]                          # (B,H,K,V)
        kv = jnp.einsum("bhk,bhv->bhkv", kf[:, :, 0], vf[:, :, 0])
        y = jnp.einsum("bhk,bhkv->bhv", rf[:, :, 0],
                       s_prev + p["u"][None, :, :, None] * kv)[:, :, None]
        s_new = jnp.exp(log_dec[:, :, 0, :, None]) * s_prev + kv
        new_cache = {"s": s_new, "x_prev": x[:, -1]}
    elif s % 16 == 0:
        # §Perf H1: chunk-parallel WKV — O(T/chunk) state materializations;
        # iter3: chunk 64 balances state-stack vs intra-score traffic
        chunk = 64 if s % 64 == 0 else 16
        y = _wkv_chunked(rf, kf, vf, log_dec, p["u"], chunk=chunk,
                         w_is_log=True)
    else:
        y, _ = _wkv_scan(rf.astype(jnp.float32), kf.astype(jnp.float32),
                         vf.astype(jnp.float32), jnp.exp(log_dec), p["u"])
    y = y.transpose(0, 2, 1, 3).reshape(b, s, d).astype(x.dtype)
    out = (y * g) @ p["w_out"]
    return maybe_shard(out, A_BSD), new_cache


def rwkv6_cache_init(cfg: ArchConfig, batch: int) -> Params:
    hp = cfg.ssm_head_dim
    n_heads = cfg.d_model // hp
    dt = jnp.bfloat16 if cfg.dtype == "bf16" else jnp.float32
    return {"s": jnp.zeros((batch, n_heads, hp, hp), jnp.float32),
            "x_prev": jnp.zeros((batch, cfg.d_model), dt)}


def rwkv6_cache_specs(cfg: ArchConfig) -> Params:
    return {"s": P(("pod", "data"), "model", None, None),
            "x_prev": P(("pod", "data"), None)}
