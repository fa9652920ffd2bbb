"""Plain reference of a tiny decoder-only language model: the test
fixture of the token path.

Token embedding, ``n_layers`` pre-norm blocks (RMS norm, causal
multi-head self-attention within each document of a packed sequence,
RMS norm, ReLU MLP), a final RMS norm and the unembedding.  The loss is
next-token cross-entropy over the positions whose target lies in the
same document as its input.  Initial weights: one key per tensor,
N(0, 1/fan_in) matrices, N(0, 1) embeddings, unit norm gains.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

MATRICES = ("w1", "w2", "wk", "wo", "wq", "wv")


def init(key, cfg, dtype=jnp.float32):
    d, f, v = cfg["d_model"], cfg["d_ff"], cfg["vocab_size"]
    shapes = {"w1": (d, f), "w2": (f, d), "wk": (d, d), "wo": (d, d),
              "wq": (d, d), "wv": (d, d)}
    k_embed, k_head, *k_blocks = jax.random.split(key, 2 + cfg["n_layers"])

    def dense(k, shape):
        w = jax.random.normal(k, shape, jnp.float32) * shape[0] ** -0.5
        return w.astype(dtype)

    blocks = []
    for kb in k_blocks:
        ks = jax.random.split(kb, len(MATRICES))
        block = {name: dense(k, shapes[name]) for name, k in zip(MATRICES, ks)}
        block.update(ln1=jnp.ones((d,), dtype), ln2=jnp.ones((d,), dtype))
        blocks.append(block)
    return {"blocks": blocks,
            "embed": jax.random.normal(k_embed, (v, d), jnp.float32)
            .astype(dtype),
            "head": dense(k_head, (d, v)),
            "ln_f": jnp.ones((d,), dtype)}


def _rms(x, gain):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * gain


def logits(params, tokens, segments, cfg):
    """tokens, segments (B, T) -> logits (B, T, vocab)."""
    b, t = tokens.shape
    heads = cfg["n_heads"]
    dh = cfg["d_model"] // heads
    causal = jnp.tril(jnp.ones((t, t), bool))
    mask = causal & (segments[:, :, None] == segments[:, None, :])
    h = params["embed"][tokens]
    for blk in params["blocks"]:
        x = _rms(h, blk["ln1"])
        q, k, v = [(x @ blk[w]).reshape(b, t, heads, dh)
                   for w in ("wq", "wk", "wv")]
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * dh ** -0.5
        s = jnp.where(mask[:, None], s, -1e9)
        a = jax.nn.softmax(s, -1)
        o = jnp.einsum("bhqk,bkhd->bqhd", a, v).reshape(b, t, -1)
        h = h + o @ blk["wo"]
        h = h + jax.nn.relu(_rms(h, blk["ln2"]) @ blk["w1"]) @ blk["w2"]
    return _rms(h, params["ln_f"]) @ params["head"]


def _in_document(segments):
    return segments[:, 1:] == segments[:, :-1]


def count(batch, cfg):
    """The targets ``loss`` averages over: those in their input's
    document."""
    return _in_document(batch["segments"]).sum()


def loss(params, batch, cfg, dropout_key=None):
    """Mean next-token loss and accuracy over in-document targets of a
    batch of packed sequences ``{"tokens", "segments"}`` (B, T + 1)."""
    tokens, segments = batch["tokens"], batch["segments"]
    out = logits(params, tokens[:, :-1], segments[:, :-1], cfg)
    target = tokens[:, 1:]
    same = _in_document(segments).astype(out.dtype)
    n = jnp.maximum(same.sum(), 1)
    logp = jax.nn.log_softmax(out)
    nll = -jnp.take_along_axis(logp, target[..., None], -1)[..., 0]
    hit = (out.argmax(-1) == target).astype(out.dtype)
    return (nll * same).sum() / n, (hit * same).sum() / n


def forward_macs(cfg) -> int:
    """Multiply-accumulates of one sequence's forward pass (``seq_len``
    positions; attention over the whole square, as computed)."""
    t, d, f, v = cfg["seq_len"], cfg["d_model"], cfg["d_ff"], cfg["vocab_size"]
    per_token = cfg["n_layers"] * (4 * d * d + 2 * d * f) + d * v
    return t * per_token + cfg["n_layers"] * 2 * t * t * d
