"""Plain reference of DeepSeek-V2-Lite at one chip's share (the
configuration file beside this module; the published model is
https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite).

Token embedding, then ``num_hidden_layers`` pre-norm layers (RMS norm,
multi-head latent attention, RMS norm, FFN), a final RMS norm and the
unembedding.  The first ``first_k_dense_replace`` layers have a SwiGLU
FFN of width ``intermediate_size``; each other layer has a router over
all ``router_outputs`` experts (softmax, greedy top-``num_experts_per_tok``,
the gates not renormalised and scaled by ``routed_scaling_factor``) and
the weights of the ``n_routed_experts`` experts held here, experts 0 to
``n_routed_experts - 1``: a token's output is the gate-weighted sum of
the held experts it is routed to (the absent experts add nothing), plus
the ``n_shared_experts`` shared experts as one SwiGLU of their summed
width.

Latent attention without a query low-rank: ``q = x wq`` split into a
no-rope and a rope part; ``x wkv_a`` gives the latent (RMS-normed) and
one rope key shared by the heads; ``latent wkv_b`` gives each head's
no-rope key and value.  Rotary frequencies follow YaRN as DeepSeek-V2's
modelling code computes them (``yarn_find_correction_range``, a linear
ramp between the interpolated and the original frequencies, cos and sin
times ``mscale(mscale) / mscale(mscale_all_dim)``), rotating the two
halves of the rope dimensions; the softmax scale is ``(qk_nope +
qk_rope) ** -0.5 * mscale(mscale_all_dim) ** 2``.  Attention is causal
within each document of a packed sequence, positions restart at each
document.  The loss is next-token cross-entropy over the positions whose
target lies in the same document as its input; no routing loss.

Each layer, and within it each head's attention, is rematerialised in
the backward pass (``jax.checkpoint``), heads run one at a time and a
batch one sequence at a time, so that a 4,096-token sequence fits one
chip; none of this changes the arithmetic.

Initial weights follow the program's key schedule and layout (its
``tree_flatten`` order, layers of a kind stacked on a leading axis):
``split(key, 8)`` gives the embedding (N(0, 1)), the head, the expert
layers' and the dense layers' keys (indices 0, 1, 3, 6); each layer's
key splits in 5 (mixer 0, FFN 2); the mixer's in 6 (wq 0, wkv_a 2,
wkv_b 3, wo 4); the expert FFN's in 6 (router 0, wi 1, wg 2, wo 3,
shared experts 4, which split in 3: wi, wg, wo), a dense FFN's in 3
(wi, wg, wo).  A matrix is N(0, 1/fan_in), the router's and each
expert's too; norm gains are 1.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NEG = -1e30


# ----------------------------------------------------------- weights --
def _normal(key, shape, fan_in, dtype):
    w = jax.random.normal(key, shape, jnp.float32) * fan_in ** -0.5
    return w.astype(dtype)


def _dense(key, n_in, n_out, dtype):
    return {"w": _normal(key, (n_in, n_out), n_in, dtype)}


def _swiglu(key, d, f, dtype):
    k = jax.random.split(key, 3)
    return {"wg": _dense(k[1], d, f, dtype), "wi": _dense(k[0], d, f, dtype),
            "wo": _dense(k[2], f, d, dtype)}


def _mla(key, cfg, dtype):
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    lat, v = cfg["kv_lora_rank"], cfg["v_head_dim"]
    k = jax.random.split(key, 6)
    return {"kv_norm": {"scale": jnp.ones((lat,), dtype)},
            "wkv_a": _dense(k[2], d, lat + rope, dtype),
            "wkv_b": _dense(k[3], lat, h * (nope + v), dtype),
            "wo": _dense(k[4], h * v, d, dtype),
            "wq": _dense(k[0], d, h * (nope + rope), dtype)}


def _experts(key, cfg, dtype):
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    held = cfg["n_routed_experts"]
    k = jax.random.split(key, 6)
    return {"router": {"w": _normal(k[0], (d, cfg["router_outputs"]), d,
                                    dtype)},
            "shared": _swiglu(k[4], d, f * cfg["n_shared_experts"], dtype),
            "wg": _normal(k[2], (held, d, f), d, dtype),
            "wi": _normal(k[1], (held, d, f), d, dtype),
            "wo": _normal(k[3], (held, f, d), f, dtype)}


def _layer(key, cfg, dense: bool, dtype):
    d = cfg["hidden_size"]
    k = jax.random.split(key, 5)
    out = {"mixer": _mla(k[0], cfg, dtype),
           "norm1": {"scale": jnp.ones((d,), dtype)},
           "norm2": {"scale": jnp.ones((d,), dtype)}}
    if dense:
        out["ffn"] = _swiglu(k[2], d, cfg["intermediate_size"], dtype)
    else:
        out["moe"] = _experts(k[2], cfg, dtype)
    return out


def _stack(layers):
    return jax.tree.map(lambda *a: jnp.stack(a), *layers)


def init(key, cfg, dtype=jnp.float32):
    d, vocab = cfg["hidden_size"], cfg["vocab_size"]
    n_dense = cfg["first_k_dense_replace"]
    n_moe = cfg["num_hidden_layers"] - n_dense
    k = jax.random.split(key, 8)
    moe_keys = jax.random.split(k[3], n_moe)
    dense_keys = jax.random.split(k[6], n_dense)
    # each expert layer is the one sublayer of its group
    moe = [_layer(jax.random.split(g, 1)[0], cfg, False, dtype)
           for g in moe_keys]
    return {"dense": _stack([_layer(g, cfg, True, dtype)
                             for g in dense_keys]),
            "embed": {"table": (jax.random.normal(k[0], (vocab, d),
                                                  jnp.float32)
                                .astype(dtype))},
            "final_norm": {"scale": jnp.ones((d,), dtype)},
            "groups": {"sub0": _stack(moe)},
            "lm_head": _dense(k[1], d, vocab, dtype)}


# ------------------------------------------------------------- model --
def _rms(x, p, cfg):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True)
                           + cfg["rms_norm_eps"])
    return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)


def _mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def rope_tables(cfg, positions):
    """YaRN inverse frequencies at ``positions`` (T,): (cos, sin), each
    (T, rope / 2)."""
    dim, base = cfg["qk_rope_head_dim"], cfg["rope_theta"]
    y = cfg["rope_scaling"]
    extra = 1.0 / base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)

    def correction(rotations):
        return (dim * math.log(y["original_max_position_embeddings"]
                               / (rotations * 2 * math.pi))
                / (2 * math.log(base)))
    low = max(math.floor(correction(y["beta_fast"])), 0)
    high = min(math.ceil(correction(y["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0, 1)
    inter = extra / y["factor"]
    inv = inter * ramp + extra * (1 - ramp)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    m = (_mscale(y["factor"], y["mscale"])
         / _mscale(y["factor"], y["mscale_all_dim"]))
    return jnp.cos(ang) * m, jnp.sin(ang) * m


def softmax_scale(cfg):
    y = cfg["rope_scaling"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return qk ** -0.5 * _mscale(y["factor"], y["mscale_all_dim"]) ** 2


def _rotate(x, cos, sin):
    """x (T, heads, r): the halves rotated by the tables (T, r/2)."""
    x = x.astype(jnp.float32)
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)


def attention(p, x, tables, mask, cfg):
    """Latent attention of one sequence: x (T, d), mask (T, T)."""
    t = x.shape[0]
    h = cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    lat, vd = cfg["kv_lora_rank"], cfg["v_head_dim"]
    cos, sin = tables
    q = (x @ p["wq"]["w"]).reshape(t, h, nope + rope)
    q = jnp.concatenate([q[..., :nope],
                         _rotate(q[..., nope:], cos, sin).astype(q.dtype)],
                        -1)
    kv = x @ p["wkv_a"]["w"]
    latent = _rms(kv[:, :lat], p["kv_norm"], cfg)
    k_rope = _rotate(kv[:, None, lat:], cos, sin).astype(x.dtype)
    kv = (latent @ p["wkv_b"]["w"]).reshape(t, h, nope + vd)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_rope, (t, h, rope))], -1)
    v = kv[..., nope:]

    @jax.checkpoint
    def head(qkv):
        qh, kh, vh = (a.astype(jnp.float32) for a in qkv)
        s = (qh @ kh.T) * softmax_scale(cfg)
        return jax.nn.softmax(jnp.where(mask, s, NEG), -1) @ vh

    o = jax.lax.map(head, tuple(a.transpose(1, 0, 2) for a in (q, k, v)))
    return o.transpose(1, 0, 2).reshape(t, h * vd).astype(x.dtype) \
        @ p["wo"]["w"]


def swiglu(p, x):
    return (jax.nn.silu(x @ p["wg"]["w"]) * (x @ p["wi"]["w"])) @ p["wo"]["w"]


def gates(p, x, cfg):
    """(T, held) weight of each held expert for each token: its top-k
    gate where the token is routed to it, else 0."""
    logits = x.astype(jnp.float32) @ p["router"]["w"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, -1)
    gate, idx = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        gate = gate / gate.sum(-1, keepdims=True)
    gate = gate * cfg["routed_scaling_factor"]
    held = jnp.arange(cfg["n_routed_experts"])
    return (gate[:, :, None] * (idx[:, :, None] == held)).sum(1)


def routed(p, x, cfg):
    """The held experts' part: every held expert on every token, each
    weighted by its gate (0 where the token is not routed to it)."""
    w = gates(p, x, cfg).astype(x.dtype)
    hid = (jax.nn.silu(jnp.einsum("td,edf->etf", x, p["wg"]))
           * jnp.einsum("td,edf->etf", x, p["wi"]))
    out = jnp.einsum("etf,efd->etd", hid, p["wo"])
    return jnp.einsum("te,etd->td", w, out)


def experts(p, x, cfg):
    return routed(p, x, cfg) + swiglu(p["shared"], x)


def positions_of(segments):
    """Position of each token within its document (T,)."""
    idx = jnp.arange(segments.shape[0])
    start = jnp.concatenate([jnp.ones((1,), bool),
                             segments[1:] != segments[:-1]])
    return idx - jax.lax.cummax(jnp.where(start, idx, 0))


def hidden(params, tokens, segments, cfg):
    """One sequence (T,) -> final-normed hidden states (T, d)."""
    t = tokens.shape[0]
    tables = rope_tables(cfg, positions_of(segments))
    mask = (jnp.tril(jnp.ones((t, t), bool))
            & (segments[:, None] == segments[None, :]))

    def block(ffn):
        @jax.checkpoint
        def apply(x, p):
            x = x + attention(p["mixer"], _rms(x, p["norm1"], cfg), tables,
                              mask, cfg)
            return x + ffn(p, _rms(x, p["norm2"], cfg)), None
        return apply

    x = params["embed"]["table"][tokens]
    x, _ = jax.lax.scan(block(lambda p, h: swiglu(p["ffn"], h)), x,
                        params["dense"])
    x, _ = jax.lax.scan(block(lambda p, h: experts(p["moe"], h, cfg)), x,
                        params["groups"]["sub0"])
    return _rms(x, params["final_norm"], cfg)


def _in_document(segments):
    return segments[..., 1:] == segments[..., :-1]


def count(batch, cfg):
    """The targets ``loss`` averages over: those in their input's
    document."""
    return _in_document(batch["segments"]).sum()


def loss(params, batch, cfg, dropout_key=None):
    """Mean next-token loss and accuracy over in-document targets of a
    batch of packed sequences ``{"tokens", "segments"}`` (B, T + 1)."""
    def one(seq):
        tokens, segments = seq
        out = hidden(params, tokens[:-1], segments[:-1], cfg) \
            @ params["lm_head"]["w"]
        target = tokens[1:]
        same = _in_document(segments).astype(jnp.float32)
        logp = jax.nn.log_softmax(out.astype(jnp.float32))
        nll = -jnp.take_along_axis(logp, target[:, None], -1)[:, 0]
        hit = (out.argmax(-1) == target).astype(jnp.float32)
        return (nll * same).sum(), (hit * same).sum()

    nll, hit = jax.lax.map(one, (batch["tokens"], batch["segments"]))
    n = jnp.maximum(count(batch, cfg), 1).astype(jnp.float32)
    return nll.sum() / n, hit.sum() / n


def forward_macs(cfg) -> int:
    """Multiply-accumulates of one sequence's forward pass (``seq_len``
    positions): attention over the whole square, as computed; per token
    the routed experts it is expected to reach here (top-k times the
    held share of the router's outputs) and both shared experts."""
    t, d, vocab = cfg["seq_len"], cfg["hidden_size"], cfg["vocab_size"]
    h = cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    lat, vd = cfg["kv_lora_rank"], cfg["v_head_dim"]
    layers = cfg["num_hidden_layers"]
    n_dense = cfg["first_k_dense_replace"]
    mla = (d * h * (nope + rope) + d * (lat + rope) + lat * h * (nope + vd)
           + h * vd * d)
    square = t * t * h * (nope + rope + vd)
    f = cfg["moe_intermediate_size"]
    reach = (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
             / cfg["router_outputs"])
    moe = (d * cfg["router_outputs"]
           + (reach + cfg["n_shared_experts"]) * 3 * d * f)
    per_token = (layers * mla + n_dense * 3 * d * cfg["intermediate_size"]
                 + (layers - n_dense) * moe + d * vocab)
    return int(t * per_token + layers * square)
