"""Minimal param-pytree module helpers (no flax dependency).

Every "module" is a pair of pure functions: ``*_init(rng, ...) -> params``
and ``*_apply(params, x, ...) -> y``.  Params are plain dicts of jnp
arrays so they stack cleanly under ``vmap`` (scan-over-layers) and shard
under pjit.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def _normal(rng, shape, scale, dtype):
    return (jax.random.normal(rng, shape, jnp.float32) * scale).astype(dtype)


# ---------------------------------------------------------------- dense --
def dense_init(rng, in_dim: int, out_dim: int, *, bias: bool = False,
               dtype=jnp.bfloat16, scale: Optional[float] = None):
    scale = scale if scale is not None else in_dim ** -0.5
    p = {"w": _normal(rng, (in_dim, out_dim), scale, dtype)}
    if bias:
        p["b"] = jnp.zeros((out_dim,), dtype)
    return p


def dense_apply(p, x):
    y = x @ p["w"]
    if "b" in p:
        y = y + p["b"]
    return y


# ---------------------------------------------------------------- norms --
def norm_init(kind: str, dim: int, dtype=jnp.bfloat16):
    if kind == "rmsnorm":
        return {"scale": jnp.ones((dim,), dtype)}
    if kind == "layernorm":
        return {"scale": jnp.ones((dim,), dtype), "bias": jnp.zeros((dim,), dtype)}
    if kind == "layernorm_np":          # OLMo: non-parametric LN
        return {}
    raise ValueError(kind)


def norm_apply(kind: str, p, x, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
        return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)
    mean = jnp.mean(xf, -1, keepdims=True)
    var = jnp.var(xf, -1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    if kind == "layernorm":
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


# ------------------------------------------------------------ embedding --
def embedding_init(rng, vocab: int, dim: int, dtype=jnp.bfloat16):
    return {"table": _normal(rng, (vocab, dim), 1.0, dtype)}


def embedding_apply(p, ids):
    return jnp.take(p["table"], ids, axis=0)


def embedding_attend(p, x):
    """Tied-embedding logits."""
    return x @ p["table"].T


# ----------------------------------------------------------------- rope --
def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, theta: float, freqs=None):
    """x: (..., S, H, hd); positions: (..., S); ``freqs`` (hd/2,) the
    inverse frequencies (default: ``rope_freqs(hd, theta)``)."""
    hd = x.shape[-1]
    if freqs is None:
        freqs = rope_freqs(hd, theta)                   # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    angles = angles[..., None, :]                       # (..., S, 1, hd/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


# ------------------------------------------------------------------ ffn --
def ffn_init(rng, kind: str, d_model: int, d_ff: int, dtype=jnp.bfloat16):
    r = jax.random.split(rng, 3)
    if kind == "swiglu":
        return {"wi": dense_init(r[0], d_model, d_ff, dtype=dtype),
                "wg": dense_init(r[1], d_model, d_ff, dtype=dtype),
                "wo": dense_init(r[2], d_ff, d_model, dtype=dtype)}
    if kind == "gelu":
        return {"wi": dense_init(r[0], d_model, d_ff, dtype=dtype),
                "wo": dense_init(r[1], d_ff, d_model, dtype=dtype)}
    raise ValueError(kind)


def tp_weight(p, *axes):
    """FSDP -> TP reshard of a weight before use.

    Storage sharding is ZeRO-3 (both dims sharded); computing directly
    from that makes XLA all-gather *activations* (B,S,d_ff f32 — orders
    of magnitude worse).  Constraining the weight to its Megatron layout
    (contracting dim replicated, output dim on `model`) turns that into
    a per-layer weight all-gather over `data` — the FSDP schedule.
    See EXPERIMENTS.md §Perf iteration 1.
    """
    from repro.sharding import constrain  # local import: avoid cycle
    w = constrain(p["w"], *axes)
    out = dict(p)
    out["w"] = w
    return out


def ffn_apply(kind: str, p, x):
    if kind == "swiglu":
        h = (jax.nn.silu(dense_apply(tp_weight(p["wg"], None, "model"), x))
             * dense_apply(tp_weight(p["wi"], None, "model"), x))
    else:
        h = jax.nn.gelu(dense_apply(tp_weight(p["wi"], None, "model"), x))
    return dense_apply(tp_weight(p["wo"], "model", None), h)


# ------------------------------------------------------------ conv (cnn) --
def conv2d_init(rng, kh: int, kw: int, cin: int, cout: int, dtype=jnp.float32):
    scale = (kh * kw * cin) ** -0.5
    return {"w": _normal(rng, (kh, kw, cin, cout), scale, dtype),
            "b": jnp.zeros((cout,), dtype)}


def conv2d_apply(p, x, *, padding="SAME"):
    """x: (B, H, W, C)."""
    y = jax.lax.conv_general_dilated(
        x, p["w"], window_strides=(1, 1), padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + p["b"]


def maxpool2(x):
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                 (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
