"""Attention flavors: GQA (RoPE, optional bias, sliding window), MLA
(DeepSeek-V2 latent attention), cross-attention, with decode KV caches.

The full-sequence path is *blockwise* over query chunks so 32k-prefill
never materializes an (S, S) score matrix.  On the TPU, MLA's causal
train/prefill attention runs the fused flash kernel
``repro.kernels.flash_attention.splash`` instead (:func:`attention_path`
decides), which keeps each block's scores on chip; the blockwise routine
is that kernel's numerical oracle and serves every other call.
"""
from __future__ import annotations

import collections
import contextlib
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro import scopes
from repro.configs.base import ArchConfig
from repro.kernels.flash_attention import splash
from repro.models import modules as nn

NEG_INF = -1e30

FLASH, BLOCKWISE = "flash", "blockwise"
# precisions at which a float32 matmul takes one bfloat16 pass, the
# arithmetic of the flash kernel
_ONE_PASS = (None, "default", "bfloat16", "BF16_BF16_F32")
_path_counts: list = []          # the Counters of open count_attention_paths


def attention_path(backend: str, mode: str, seq_len: int,
                   precision: Optional[str] = None) -> str:
    """``FLASH`` where the fused kernel serves a causal self-attention
    call: on the TPU, in train or prefill mode, at a sequence length
    that is a multiple of the kernel's block, at a matmul precision
    (``jax_default_matmul_precision``) of one bfloat16 pass; else
    ``BLOCKWISE``."""
    if (backend == "tpu" and mode in ("train", "prefill")
            and seq_len % splash.BLOCK == 0 and precision in _ONE_PASS):
        return FLASH
    return BLOCKWISE


@contextlib.contextmanager
def count_attention_paths():
    """Yields a ``Counter`` of the paths (``FLASH`` / ``BLOCKWISE``)
    taken by the full-sequence self-attention calls (train, prefill,
    encode) traced inside the block; GQA's always read ``BLOCKWISE``.  A
    count of traced calls: a program that is already compiled adds
    nothing, and a layer scan traces its body once."""
    counts = collections.Counter()
    _path_counts.append(counts)
    try:
        yield counts
    finally:
        _path_counts.remove(counts)


def _count_path(path: str):
    for counts in _path_counts:
        counts[path] += 1


# ----------------------------------------------------------------- core --
def blockwise_attention(q, k, v, *, causal: bool, window: Optional[int],
                        q_offset=0, kv_len: Optional[jnp.ndarray] = None,
                        q_block: int = 1024, scale: Optional[float] = None,
                        segments: Optional[jnp.ndarray] = None):
    """Memory-bounded attention.

    q: (B, Sq, H, hd);  k/v: (B, Sk, KV, hd) — GQA via head repeat.
    ``q_offset``: absolute position of q[0] (decode / chunked prefill).
    ``window``: sliding-window size (None = full).
    ``kv_len``: optional dynamic valid length of k/v (decode).
    ``scale``: softmax scale (default ``hd ** -0.5``).
    ``segments``: optional (B, S) document ids of a packed self-attention
    sequence (Sq == Sk): a query attends only within its document.
    """
    B, Sq, H, hd = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    rep = H // KV
    scale = hd ** -0.5 if scale is None else scale
    kT = k.transpose(0, 2, 3, 1)                      # (B, KV, hd, Sk)
    vT = v.transpose(0, 2, 1, 3)                      # (B, KV, Sk, hd)
    kv_pos = jnp.arange(Sk)
    kv_len_vec = (kv_len is not None
                  and getattr(kv_len, "ndim", 0) == 1)  # per-row lengths

    nb = max(1, (Sq + q_block - 1) // q_block)
    pad = nb * q_block - Sq
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else q
    qp = qp.reshape(B, nb, q_block, H, hd)
    if segments is not None:
        seg_q = (jnp.pad(segments, ((0, 0), (0, pad)), constant_values=-1)
                 if pad else segments).reshape(B, nb, q_block)

    def one_block(args):
        qb, block_idx = args                          # (B, q_block, H, hd)
        q_pos = q_offset + block_idx * q_block + jnp.arange(q_block)
        qg = qb.reshape(B, q_block, KV, rep, hd).transpose(0, 2, 3, 1, 4)
        s = jnp.einsum("bgrqd,bgdk->bgrqk", qg.astype(jnp.float32),
                       kT.astype(jnp.float32)) * scale   # (B,KV,rep,qb,Sk)
        mask = jnp.ones((q_block, Sk), bool)
        if causal:
            mask &= kv_pos[None, :] <= q_pos[:, None]
        if window is not None:
            mask &= kv_pos[None, :] > q_pos[:, None] - window
        if kv_len is not None and not kv_len_vec:
            mask &= kv_pos[None, :] < kv_len
        mask = mask[None, None, None]
        if kv_len_vec:                                # (B,) per-slot lengths
            mask = mask & (kv_pos[None, :] <
                           kv_len[:, None])[:, None, None, None]
        if segments is not None:                      # same document only
            sq = jax.lax.dynamic_index_in_dim(seg_q, block_idx, 1, False)
            mask = mask & (sq[:, :, None] == segments[:, None, :]
                           )[:, None, None]
        s = jnp.where(mask, s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bgrqk,bgkd->bgrqd", p, vT.astype(jnp.float32))
        return o.transpose(0, 3, 1, 2, 4).reshape(B, q_block, H, v.shape[-1])

    if nb == 1:
        out = one_block((qp[:, 0], jnp.int32(0)))
    else:
        out = jax.lax.map(one_block, (qp.transpose(1, 0, 2, 3, 4),
                                      jnp.arange(nb, dtype=jnp.int32)))
        out = out.transpose(1, 0, 2, 3, 4).reshape(B, nb * q_block, H,
                                                   v.shape[-1])
    return out[:, :Sq].astype(q.dtype)


# ------------------------------------------------------------------ GQA --
def gqa_init(rng, cfg: ArchConfig, *, cross: bool = False):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    H, KV = cfg.num_heads, cfg.num_kv_heads
    r = jax.random.split(rng, 4)
    dt = cfg.param_dtype
    return {"wq": nn.dense_init(r[0], d, H * hd, bias=cfg.qkv_bias, dtype=dt),
            "wk": nn.dense_init(r[1], d, KV * hd, bias=cfg.qkv_bias, dtype=dt),
            "wv": nn.dense_init(r[2], d, KV * hd, bias=cfg.qkv_bias, dtype=dt),
            "wo": nn.dense_init(r[3], H * hd, d, dtype=dt)}


def gqa_cache_init(cfg: ArchConfig, batch: int, max_len: int,
                   dtype=jnp.bfloat16, quantized: bool = False):
    """KV cache.  ``quantized=True`` stores int8 values + one bf16 scale
    per (token, head) — ~2x less HBM read per decode step, which is the
    dominant roofline term for decode shapes (EXPERIMENTS.md §Perf)."""
    hd = cfg.resolved_head_dim
    KV = cfg.num_kv_heads
    if quantized:
        return {"k": jnp.zeros((batch, max_len, KV, hd), jnp.int8),
                "v": jnp.zeros((batch, max_len, KV, hd), jnp.int8),
                "k_scale": jnp.zeros((batch, max_len, KV), jnp.bfloat16),
                "v_scale": jnp.zeros((batch, max_len, KV), jnp.bfloat16)}
    return {"k": jnp.zeros((batch, max_len, KV, hd), dtype),
            "v": jnp.zeros((batch, max_len, KV, hd), dtype)}


def _quantize_kv(x):
    """x: (B, S, KV, hd) -> (int8 values, bf16 per-(token,head) scale)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax / 127.0, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.bfloat16)


def _dequantize_kv(q, scale, dtype=jnp.bfloat16):
    return (q.astype(jnp.float32)
            * scale[..., None].astype(jnp.float32)).astype(dtype)


def _write_at(buf, val, pos):
    """Write val (B,1,...) into buf (B,S,...) at seq position ``pos`` —
    scalar, or (B,) for per-slot positions (continuous batching)."""
    val = val.astype(buf.dtype)
    if getattr(pos, "ndim", 0) == 1:
        return jax.vmap(
            lambda b, v, p: jax.lax.dynamic_update_slice(
                b, v, (p,) + (0,) * (b.ndim - 1)))(buf, val, pos)
    zeros = (0,) * (buf.ndim - 2)
    return jax.lax.dynamic_update_slice(buf, val, (0, pos) + zeros)


def _slice_at(buf, start, length):
    """Read a (B, length, ...) window starting at ``start`` (scalar or
    (B,) per-slot)."""
    if getattr(start, "ndim", 0) == 1:
        return jax.vmap(
            lambda b, s: jax.lax.dynamic_slice(
                b, (s,) + (0,) * (b.ndim - 1), (length,) + b.shape[1:])
        )(buf, start)
    return jax.lax.dynamic_slice_in_dim(buf, start, length, 1)


def gqa_apply(p, x, *, cfg: ArchConfig, mode: str, positions,
              cache=None, cache_pos=None, kv_source=None,
              window: Optional[int] = None, cross: bool = False,
              segments=None):
    """Returns (y, new_cache).  kv_source: encoder output for cross-attn
    (may be None during decode when the cross K/V cache is prefilled).
    ``segments``: (B, S) document ids of packed training sequences."""
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    H, KV = cfg.num_heads, cfg.num_kv_heads
    q = nn.dense_apply(nn.tp_weight(p["wq"], None, "model"),
                       x).reshape(B, S, H, hd)
    cross = cross or kv_source is not None
    use_cached_cross = (cross and mode == "decode" and cache is not None
                        and "ck" in cache)
    if use_cached_cross:
        k = v = None                   # never recomputed during decode
    else:
        src = x if kv_source is None else kv_source
        k = nn.dense_apply(nn.tp_weight(p["wk"], None, "model"),
                           src).reshape(B, src.shape[1], KV, hd)
        v = nn.dense_apply(nn.tp_weight(p["wv"], None, "model"),
                           src).reshape(B, src.shape[1], KV, hd)

    if cfg.pos_emb == "rope" and not cross:
        q = nn.apply_rope(q, positions, cfg.rope_theta)
        k = nn.apply_rope(k, positions, cfg.rope_theta)

    new_cache = cache
    if mode == "decode" and not cross:
        quantized = cache is not None and "k_scale" in cache
        # write this step's k/v at cache_pos, attend over valid prefix
        if quantized:
            kq, ks = _quantize_kv(k)
            vq, vs = _quantize_kv(v)
            new_cache = {
                "k": _write_at(cache["k"], kq, cache_pos),
                "v": _write_at(cache["v"], vq, cache_pos),
                "k_scale": _write_at(cache["k_scale"], ks, cache_pos),
                "v_scale": _write_at(cache["v_scale"], vs, cache_pos),
            }
        else:
            new_cache = {"k": _write_at(cache["k"], k, cache_pos),
                         "v": _write_at(cache["v"], v, cache_pos)}
        kv_len = cache_pos + 1

        def read(name, start=None, length=None):
            buf = new_cache[name]
            if start is not None:
                buf = _slice_at(buf, start, length)
            if not quantized:
                return buf
            sc = new_cache[name + "_scale"]
            if start is not None:
                sc = _slice_at(sc, start, length)
            return _dequantize_kv(buf, sc, k.dtype)

        if window is not None:
            # only read the last `window` positions (sliding window decode)
            win = min(window, new_cache["k"].shape[1])   # short caches
            start = jnp.maximum(kv_len - win, 0)
            out = blockwise_attention(
                q, read("k", start, win), read("v", start, win),
                causal=False, window=None,
                kv_len=jnp.minimum(kv_len, win), q_block=8)
        else:
            out = blockwise_attention(q, read("k"), read("v"), causal=False,
                                      window=None, kv_len=kv_len, q_block=8)
    elif cross:
        if use_cached_cross:
            # cross K/V were computed once at prefill — reuse
            out = blockwise_attention(q, cache["ck"].astype(q.dtype),
                                      cache["cv"].astype(q.dtype),
                                      causal=False, window=None, q_block=8)
        else:
            out = blockwise_attention(q, k, v, causal=False, window=None,
                                      q_block=min(1024, max(8, S)))
            if mode == "prefill" and cache is not None and "ck" in cache:
                new_cache = {"ck": k.astype(cache["ck"].dtype),
                             "cv": v.astype(cache["cv"].dtype)}
    else:  # train / prefill: full causal; encoder: bidirectional
        _count_path(BLOCKWISE)
        out = blockwise_attention(q, k, v, causal=(mode != "encode"),
                                  window=window,
                                  q_block=min(1024, max(8, S)),
                                  segments=segments)
        if mode == "prefill" and cache is not None:
            if "k_scale" in cache:
                kq, ks = _quantize_kv(k)
                vq, vs = _quantize_kv(v)
                new_cache = {
                    "k": jax.lax.dynamic_update_slice(cache["k"], kq,
                                                      (0, 0, 0, 0)),
                    "v": jax.lax.dynamic_update_slice(cache["v"], vq,
                                                      (0, 0, 0, 0)),
                    "k_scale": jax.lax.dynamic_update_slice(
                        cache["k_scale"], ks, (0, 0, 0)),
                    "v_scale": jax.lax.dynamic_update_slice(
                        cache["v_scale"], vs, (0, 0, 0))}
            else:
                new_cache = {
                    "k": jax.lax.dynamic_update_slice(
                        cache["k"], k.astype(cache["k"].dtype), (0, 0, 0, 0)),
                    "v": jax.lax.dynamic_update_slice(
                        cache["v"], v.astype(cache["v"].dtype), (0, 0, 0, 0))}
    y = nn.dense_apply(nn.tp_weight(p["wo"], "model", None),
                       out.reshape(B, S, H * hd))
    return y, new_cache


# ------------------------------------------------------------------ MLA --
def mla_init(rng, cfg: ArchConfig):
    """MLA weights; with ``q_lora_rank`` None the query comes straight
    from the hidden state through ``wq`` (DeepSeek-V2-Lite)."""
    m = cfg.mla
    d, H = cfg.d_model, cfg.num_heads
    r = jax.random.split(rng, 6)
    dt = cfg.param_dtype
    qk_dim = m.qk_nope_head_dim + m.qk_rope_head_dim
    if m.q_lora_rank is None:
        q = {"wq": nn.dense_init(r[0], d, H * qk_dim, dtype=dt)}
    else:
        q = {"wq_a": nn.dense_init(r[0], d, m.q_lora_rank, dtype=dt),
             "q_norm": nn.norm_init("rmsnorm", m.q_lora_rank, dt),
             "wq_b": nn.dense_init(r[1], m.q_lora_rank, H * qk_dim,
                                   dtype=dt)}
    return {
        **q,
        "wkv_a": nn.dense_init(r[2], d, m.kv_lora_rank + m.qk_rope_head_dim, dtype=dt),
        "kv_norm": nn.norm_init("rmsnorm", m.kv_lora_rank, dt),
        "wkv_b": nn.dense_init(r[3], m.kv_lora_rank,
                               H * (m.qk_nope_head_dim + m.v_head_dim), dtype=dt),
        "wo": nn.dense_init(r[4], H * m.v_head_dim, d, dtype=dt),
    }


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_inv_freq(cfg: ArchConfig, dim: int):
    """Inverse rotary frequencies of a ``dim``-wide rope; under YaRN
    (``cfg.rope_scaling``) the blend of interpolated and original
    frequencies of ``DeepseekV2YarnRotaryEmbedding`` in DeepSeek-V2's
    reference modelling code (``modeling_deepseek.py``)."""
    base = cfg.rope_theta
    extra = nn.rope_freqs(dim, base)
    y = cfg.rope_scaling
    if y is None:
        return extra

    def correction_dim(rotations):
        return (dim * math.log(y.original_max_position_embeddings
                               / (rotations * 2 * math.pi))
                / (2 * math.log(base)))
    low = max(math.floor(correction_dim(y.beta_fast)), 0)
    high = min(math.ceil(correction_dim(y.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp                   # 1: the original frequency
    return extra / y.factor * (1.0 - keep) + extra * keep


def mla_softmax_scale(cfg: ArchConfig) -> float:
    """``(qk_nope + qk_rope) ** -0.5``, times YaRN's ``mscale ** 2`` with
    ``mscale = 0.1 * mscale_all_dim * ln(factor) + 1`` where the config
    sets ``mscale_all_dim`` (DeepSeek-V2's ``DeepseekV2Attention``)."""
    m = cfg.mla
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    y = cfg.rope_scaling
    if y is not None and y.mscale_all_dim:
        scale *= _yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return scale


def _rope(x, positions, cfg: ArchConfig):
    """Rotary embedding of x (..., S, H, r) at ``positions``, with YaRN's
    cos/sin factor ``mscale(mscale) / mscale(mscale_all_dim)``."""
    out = nn.apply_rope(x, positions, cfg.rope_theta,
                        freqs=rope_inv_freq(cfg, x.shape[-1]))
    y = cfg.rope_scaling
    if y is not None:
        factor = (_yarn_mscale(y.factor, y.mscale)
                  / _yarn_mscale(y.factor, y.mscale_all_dim or 1.0))
        if factor != 1.0:
            out = out * jnp.asarray(factor, out.dtype)
    return out


def mla_cache_init(cfg: ArchConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    m = cfg.mla
    return {"c_kv": jnp.zeros((batch, max_len, m.kv_lora_rank), dtype),
            "k_rope": jnp.zeros((batch, max_len, m.qk_rope_head_dim), dtype)}


def _mla_qkr(p, x, cfg, positions):
    """Shared q / latent / rope-key computation."""
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.num_heads
    if "wq" in p:
        q = nn.dense_apply(p["wq"], x)
    else:
        q = nn.dense_apply(p["wq_b"], nn.norm_apply(
            "rmsnorm", p["q_norm"], nn.dense_apply(p["wq_a"], x),
            eps=cfg.norm_eps))
    q = q.reshape(B, S, H, m.qk_nope_head_dim + m.qk_rope_head_dim)
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    q_rope = _rope(q_rope, positions, cfg)
    kv_a = nn.dense_apply(p["wkv_a"], x)
    c_kv, k_rope = jnp.split(kv_a, [m.kv_lora_rank], axis=-1)
    c_kv = nn.norm_apply("rmsnorm", p["kv_norm"], c_kv, eps=cfg.norm_eps)
    k_rope = _rope(k_rope[:, :, None, :], positions, cfg)[:, :, 0, :]
    return q_nope, q_rope, c_kv, k_rope


def mla_apply(p, x, *, cfg: ArchConfig, mode: str, positions,
              cache=None, cache_pos=None, absorb: bool = True,
              segments=None, **_):
    """DeepSeek-V2 MLA.  Decode uses the *absorbed* formulation (attend in
    latent space; W_uk folded into q, W_uv applied post-attention) so the
    cache stays (kv_lora + rope) per position — the paper's memory win.
    ``segments``: (B, S) document ids of packed training sequences."""
    with jax.named_scope(scopes.MLA):
        return _mla(p, x, cfg=cfg, mode=mode, positions=positions,
                    cache=cache, cache_pos=cache_pos, absorb=absorb,
                    segments=segments)


def _mla(p, x, *, cfg, mode, positions, cache, cache_pos, absorb, segments):
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.num_heads
    q_nope, q_rope, c_kv, k_rope = _mla_qkr(p, x, cfg, positions)
    wkv_b = p["wkv_b"]["w"].reshape(m.kv_lora_rank, H,
                                    m.qk_nope_head_dim + m.v_head_dim)
    w_uk = wkv_b[..., :m.qk_nope_head_dim]            # (L, H, nope)
    w_uv = wkv_b[..., m.qk_nope_head_dim:]            # (L, H, v)

    new_cache = cache
    if mode == "decode":
        c_cache = jax.lax.dynamic_update_slice(
            cache["c_kv"], c_kv.astype(cache["c_kv"].dtype), (0, cache_pos, 0))
        r_cache = jax.lax.dynamic_update_slice(
            cache["k_rope"], k_rope.astype(cache["k_rope"].dtype), (0, cache_pos, 0))
        new_cache = {"c_kv": c_cache, "k_rope": r_cache}
        kv_len = cache_pos + 1
        if absorb:
            # q_lat: (B,S,H,L) = q_nope absorbed through W_uk
            q_lat = jnp.einsum("bshn,lhn->bshl", q_nope.astype(jnp.float32),
                               w_uk.astype(jnp.float32))
            scale = mla_softmax_scale(cfg)
            s = (jnp.einsum("bshl,btl->bhst", q_lat,
                            c_cache.astype(jnp.float32))
                 + jnp.einsum("bshr,btr->bhst", q_rope.astype(jnp.float32),
                              r_cache.astype(jnp.float32))) * scale
            mask = jnp.arange(c_cache.shape[1])[None, None, None, :] < kv_len
            s = jnp.where(mask, s, NEG_INF)
            pr = jax.nn.softmax(s, axis=-1)
            o_lat = jnp.einsum("bhst,btl->bshl", pr,
                               c_cache.astype(jnp.float32))  # (B,S,H,L)
            out = jnp.einsum("bshl,lhv->bshv", o_lat, w_uv.astype(jnp.float32))
        else:
            k_nope = jnp.einsum("btl,lhn->bthn", c_cache.astype(jnp.float32),
                                w_uk.astype(jnp.float32))
            v_full = jnp.einsum("btl,lhv->bthv", c_cache.astype(jnp.float32),
                                w_uv.astype(jnp.float32))
            k_full = jnp.concatenate(
                [k_nope, jnp.broadcast_to(r_cache[:, :, None, :].astype(jnp.float32),
                                          (*r_cache.shape[:2], H, m.qk_rope_head_dim))], -1)
            q_full = jnp.concatenate([q_nope, q_rope], -1)
            out = blockwise_attention(q_full, k_full.astype(q_full.dtype),
                                      v_full.astype(q_full.dtype),
                                      causal=False, window=None,
                                      kv_len=kv_len, q_block=8,
                                      scale=mla_softmax_scale(cfg))
    else:
        # train / prefill: materialize per-head K/V (naive, paper-faithful)
        k_nope = jnp.einsum("btl,lhn->bthn", c_kv.astype(jnp.float32),
                            w_uk.astype(jnp.float32)).astype(x.dtype)
        v_full = jnp.einsum("btl,lhv->bthv", c_kv.astype(jnp.float32),
                            w_uv.astype(jnp.float32)).astype(x.dtype)
        k_full = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                      (*k_rope.shape[:2], H, m.qk_rope_head_dim))], -1)
        q_full = jnp.concatenate([q_nope, q_rope], -1)
        path = attention_path(jax.default_backend(), mode, S,
                              jax.config.jax_default_matmul_precision)
        _count_path(path)
        if path == FLASH:
            out = splash.causal_flash_attention(
                q_full, k_full, v_full, segments,
                scale=mla_softmax_scale(cfg))
        else:
            out = blockwise_attention(q_full, k_full, v_full, causal=True,
                                      window=None,
                                      q_block=min(1024, max(8, S)),
                                      scale=mla_softmax_scale(cfg),
                                      segments=segments)
        if mode == "prefill" and cache is not None:
            new_cache = {
                "c_kv": jax.lax.dynamic_update_slice(
                    cache["c_kv"], c_kv.astype(cache["c_kv"].dtype), (0, 0, 0)),
                "k_rope": jax.lax.dynamic_update_slice(
                    cache["k_rope"], k_rope.astype(cache["k_rope"].dtype), (0, 0, 0))}
    y = nn.dense_apply(p["wo"], out.reshape(B, S, H * m.v_head_dim).astype(x.dtype))
    return y, new_cache
