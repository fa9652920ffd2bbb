"""Composable decoder / encoder-decoder stack covering all assigned
architectures.

Layers are grouped by the arch's repeating ``block_pattern`` and the
group params are *stacked* along a leading axis so the stack runs under
``jax.lax.scan`` — an 80-layer config compiles as one group body.  Each
sublayer kind (attn / mamba / mlstm / slstm) exposes
``init / cache_init / apply`` and the group body dispatches statically.
Leading dense layers (``first_k_dense``) are a stack of their own,
``params["dense"]``, scanned before the groups.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro import scopes
from repro.configs.base import ArchConfig
from repro.models import attention as attn
from repro.models import modules as nn
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models import xlstm as xlstm_lib
from repro.sharding import batch_axes, constrain


def _has_moe(cfg: ArchConfig, sub_idx: int) -> bool:
    if cfg.moe is None:
        return False
    kind = cfg.block_pattern[sub_idx]
    if kind not in ("attn", "mamba"):
        return False
    return sub_idx % cfg.moe.every_n_layers == (cfg.moe.every_n_layers - 1) \
        if cfg.moe.every_n_layers > 1 else True


def _leading(cfg: ArchConfig) -> ArchConfig:
    """The config of a leading dense layer: the arch's mixer and its
    dense FFN of width ``d_ff``."""
    return dataclasses.replace(cfg, moe=None, block_pattern=("attn",))


def _mixer_fns(cfg: ArchConfig, kind: str):
    if kind == "attn":
        if cfg.mla is not None:
            return attn.mla_init, attn.mla_apply
        return functools.partial(attn.gqa_init), attn.gqa_apply
    if kind == "mamba":
        return ssm_lib.mamba_init, ssm_lib.mamba_apply
    if kind == "mlstm":
        return xlstm_lib.mlstm_init, xlstm_lib.mlstm_apply
    if kind == "slstm":
        return xlstm_lib.slstm_init, xlstm_lib.slstm_apply
    raise ValueError(kind)


# ---------------------------------------------------------------- init --
def _init_sublayer(rng, cfg: ArchConfig, sub_idx: int) -> Dict[str, Any]:
    kind = cfg.block_pattern[sub_idx]
    r = jax.random.split(rng, 5)
    init_fn, _ = _mixer_fns(cfg, kind)
    p: Dict[str, Any] = {
        "norm1": nn.norm_init(cfg.norm, cfg.d_model, cfg.param_dtype),
        "mixer": init_fn(r[0], cfg),
    }
    if cfg.cross_attention and kind == "attn":
        p["norm_x"] = nn.norm_init(cfg.norm, cfg.d_model, cfg.param_dtype)
        p["cross"] = attn.gqa_init(r[1], cfg, cross=True)
    if kind in ("attn", "mamba"):
        if _has_moe(cfg, sub_idx):
            p["norm2"] = nn.norm_init(cfg.norm, cfg.d_model, cfg.param_dtype)
            p["moe"] = moe_lib.moe_init(r[2], cfg)
        elif cfg.ffn != "none":
            p["norm2"] = nn.norm_init(cfg.norm, cfg.d_model, cfg.param_dtype)
            p["ffn"] = nn.ffn_init(r[2], cfg.ffn, cfg.d_model, cfg.d_ff,
                                   cfg.param_dtype)
    return p


def _cache_sublayer(cfg: ArchConfig, sub_idx: int, batch: int, max_len: int,
                    quantized: bool = False):
    kind = cfg.block_pattern[sub_idx]
    if kind == "attn":
        if cfg.mla is not None:
            # MLA's latent cache is already 4-9x smaller than full KV;
            # int8 is applied to GQA caches only
            return attn.mla_cache_init(cfg, batch, max_len)
        self_cache = attn.gqa_cache_init(cfg, batch, max_len,
                                         quantized=quantized)
        if cfg.cross_attention:
            hd = cfg.resolved_head_dim
            # cross K/V computed once at prefill, reused every decode step
            cross = {"ck": jnp.zeros((batch, cfg.encoder_seq,
                                      cfg.num_kv_heads, hd), jnp.bfloat16),
                     "cv": jnp.zeros((batch, cfg.encoder_seq,
                                      cfg.num_kv_heads, hd), jnp.bfloat16)}
            return {"self": self_cache, "cross": cross}
        return self_cache
    if kind == "mamba":
        return ssm_lib.mamba_state_init(cfg, batch)
    if kind == "mlstm":
        return xlstm_lib.mlstm_state_init(cfg, batch)
    if kind == "slstm":
        return xlstm_lib.slstm_state_init(cfg, batch)
    raise ValueError(kind)


def _no_slots() -> jnp.ndarray:
    return jnp.zeros((0,), jnp.int32)


def _slots_of(cfg: ArchConfig) -> jnp.ndarray:
    """A layer's zero slot counts: one per held expert and one for the
    absent experts where the layer holds a share, else none."""
    m = cfg.moe
    if m is None or m.experts_held is None:
        return _no_slots()
    return jnp.zeros((m.experts_held + 1,), jnp.int32)


def _apply_sublayer(p, x, *, cfg: ArchConfig, sub_idx: int, mode: str,
                    positions, cache_entry, cache_pos, enc_out, window,
                    segments=None):
    """-> (x, new_cache, aux_loss, expert slots)."""
    kind = cfg.block_pattern[sub_idx]
    _, apply_fn = _mixer_fns(cfg, kind)
    has_cross = "cross" in p
    nested = has_cross and isinstance(cache_entry, dict) \
        and "self" in cache_entry
    self_entry = cache_entry["self"] if nested else cache_entry
    h = nn.norm_apply(cfg.norm, p["norm1"], x, eps=cfg.norm_eps)
    if kind == "attn":
        docs = {} if segments is None else {"segments": segments}
        y, new_self = apply_fn(p["mixer"], h, cfg=cfg, mode=mode,
                               positions=positions, cache=self_entry,
                               cache_pos=cache_pos, window=window, **docs)
    else:
        y, new_self = apply_fn(p["mixer"], h, cfg=cfg, mode=mode,
                               state=self_entry)
        if mode == "decode" and new_self is None:
            new_self = self_entry
    x = x + y
    new_cache = new_self
    if has_cross:
        h = nn.norm_apply(cfg.norm, p["norm_x"], x, eps=cfg.norm_eps)
        cross_entry = cache_entry["cross"] if nested else None
        y, new_cross = attn.gqa_apply(p["cross"], h, cfg=cfg, mode=mode,
                                      positions=positions,
                                      kv_source=enc_out, cache=cross_entry,
                                      cross=True)
        x = x + y
        if nested:
            new_cache = {"self": new_self,
                         "cross": new_cross if new_cross is not None
                         else cross_entry}
    aux = jnp.zeros((), jnp.float32)
    slots = _no_slots()
    if "moe" in p:
        h = nn.norm_apply(cfg.norm, p["norm2"], x, eps=cfg.norm_eps)
        if cfg.moe.experts_held is None:
            y, aux = moe_lib.moe_apply(p["moe"], h, cfg)
        else:
            y, slots = moe_lib.share_apply(p["moe"], h, cfg)
        x = x + y
    elif "ffn" in p:
        h = nn.norm_apply(cfg.norm, p["norm2"], x, eps=cfg.norm_eps)
        with jax.named_scope(scopes.DENSE_FFN):
            y = nn.ffn_apply(cfg.ffn, p["ffn"], h)
        y = constrain(y, batch_axes(), None, None)
        x = x + y
    if mode == "decode" and new_cache is None:
        new_cache = cache_entry
    return x, new_cache, aux, slots


# ---------------------------------------------------------------- model --
@dataclasses.dataclass
class Model:
    cfg: ArchConfig
    max_seq: int

    # ---------------- params ----------------
    def init(self, rng) -> Dict[str, Any]:
        cfg = self.cfg
        r = jax.random.split(rng, 8)
        params: Dict[str, Any] = {
            "embed": nn.embedding_init(r[0], cfg.vocab_size, cfg.d_model,
                                       cfg.param_dtype),
            "final_norm": nn.norm_init(cfg.norm, cfg.d_model, cfg.param_dtype),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = nn.dense_init(r[1], cfg.d_model,
                                              cfg.vocab_size,
                                              dtype=cfg.param_dtype)
        if cfg.pos_emb == "learned":
            params["pos_embed"] = nn.embedding_init(
                r[2], self.max_seq, cfg.d_model, cfg.param_dtype)

        def init_group(rng_g):
            rs = jax.random.split(rng_g, cfg.group_size)
            return {f"sub{i}": _init_sublayer(rs[i], cfg, i)
                    for i in range(cfg.group_size)}

        params["groups"] = jax.vmap(init_group)(
            jax.random.split(r[3], cfg.num_groups))
        if cfg.first_k_dense:
            lead = _leading(cfg)
            params["dense"] = jax.vmap(
                lambda k: _init_sublayer(k, lead, 0))(
                jax.random.split(r[6], cfg.first_k_dense))

        if cfg.encoder_layers:
            enc_cfg = dataclasses.replace(cfg, block_pattern=("attn",),
                                          cross_attention=False, moe=None,
                                          mla=None)

            def init_enc_layer(rng_l):
                return _init_sublayer(rng_l, enc_cfg, 0)

            params["encoder"] = jax.vmap(init_enc_layer)(
                jax.random.split(r[4], cfg.encoder_layers))
            params["enc_pos"] = nn.embedding_init(
                r[5], max(cfg.encoder_seq, 8), cfg.d_model, cfg.param_dtype)
            params["enc_norm"] = nn.norm_init(cfg.norm, cfg.d_model,
                                              cfg.param_dtype)
        return params

    # ---------------- cache ----------------
    def cache_init(self, batch: int, max_len: int,
                   quantized: bool = False) -> Dict[str, Any]:
        """The groups' stacked caches; with leading dense layers,
        ``{"dense": their stacked caches, "groups": ...}``."""
        cfg = self.cfg
        one_group = {f"sub{i}": _cache_sublayer(cfg, i, batch, max_len,
                                                quantized=quantized)
                     for i in range(cfg.group_size)}
        groups = jax.tree.map(
            lambda a: jnp.zeros((cfg.num_groups, *a.shape), a.dtype),
            one_group)
        if not cfg.first_k_dense:
            return groups
        one = {"sub0": _cache_sublayer(_leading(cfg), 0, batch, max_len,
                                       quantized=quantized)}
        dense = jax.tree.map(
            lambda a: jnp.zeros((cfg.first_k_dense, *a.shape), a.dtype), one)
        return {"dense": dense, "groups": groups}

    # ---------------- encoder ----------------
    def _encode(self, params, enc_embeds):
        """enc_embeds: (B, enc_S, d) stubbed modality-frontend output."""
        cfg = self.cfg
        enc_cfg = dataclasses.replace(cfg, block_pattern=("attn",),
                                      cross_attention=False, moe=None,
                                      mla=None)
        S = enc_embeds.shape[1]
        x = enc_embeds + nn.embedding_apply(
            params["enc_pos"], jnp.arange(S))[None]
        positions = jnp.arange(S)[None]

        def body(x, lparams):
            h = nn.norm_apply(cfg.norm, lparams["norm1"], x, eps=cfg.norm_eps)
            y, _ = attn.gqa_apply(lparams["mixer"], h, cfg=enc_cfg,
                                  mode="encode", positions=positions)
            x = x + y
            h = nn.norm_apply(cfg.norm, lparams["norm2"], x, eps=cfg.norm_eps)
            x = x + nn.ffn_apply(cfg.ffn, lparams["ffn"], h)
            return x, None

        x, _ = jax.lax.scan(body, x, params["encoder"])
        return nn.norm_apply(cfg.norm, params["enc_norm"], x,
                             eps=cfg.norm_eps)

    # ---------------- main apply ----------------
    def apply(self, params, batch: Dict[str, Any], *, mode: str,
              cache=None, cache_pos=None, window: Optional[int] = None,
              with_slots: bool = False):
        """Returns (logits, new_cache, aux_loss), and with ``with_slots``
        the expert-slot counts (num_groups, ...) of each group's share
        layers last.

        batch keys: tokens (B,S) int32; optional segments (B,S) int32,
        the document id of each position of packed sequences (positions
        restart at each document, and no query attends outside its
        document); optional encoder_embeds (B,enc_S,d); optional
        image_embeds (B,V,d); decode also needs enc_out precomputed in
        batch (enc-dec serving).
        """
        cfg = self.cfg
        tokens = batch["tokens"]
        B, S = tokens.shape
        x = nn.embedding_apply(params["embed"], tokens)

        n_prefix = 0
        if cfg.vision_tokens and mode != "decode":
            img = batch["image_embeds"].astype(x.dtype)       # (B, V, d)
            n_prefix = img.shape[1]
            x = jnp.concatenate([img, x], axis=1)
        Sx = x.shape[1]

        segments = batch.get("segments")
        if mode == "decode":
            positions = jnp.broadcast_to(cache_pos, (B,))[:, None]
        elif segments is not None:
            idx = jnp.arange(Sx)
            start = jnp.concatenate(
                [jnp.ones_like(segments[:, :1], bool),
                 segments[:, 1:] != segments[:, :-1]], axis=1)
            positions = idx - jax.lax.cummax(jnp.where(start, idx, 0),
                                             axis=1)
        else:
            positions = jnp.arange(Sx)[None]
        if cfg.pos_emb == "learned":
            x = x + nn.embedding_apply(params["pos_embed"],
                                       positions.astype(jnp.int32))
        x = x.astype(cfg.param_dtype)
        x = constrain(x, batch_axes(), None, None)

        enc_out = None
        if cfg.encoder_layers:
            if mode == "decode":
                # cross K/V live in the cache after prefill; enc_out is
                # only needed when a caller decodes without prefilling
                enc_out = batch.get("enc_out")
            else:
                enc_out = self._encode(params, batch["encoder_embeds"])

        x, new_cache, aux, slots = self._layers(
            params, x, mode=mode, positions=positions, cache=cache,
            cache_pos=cache_pos, enc_out=enc_out, window=window,
            segments=segments)
        x = nn.norm_apply(cfg.norm, params["final_norm"], x,
                          eps=cfg.norm_eps)
        with jax.named_scope(scopes.LM_HEAD):
            if cfg.tie_embeddings:
                logits = nn.embedding_attend(params["embed"], x)
            else:
                logits = nn.dense_apply(
                    nn.tp_weight(params["lm_head"], None, "model"), x)
        if n_prefix:
            logits = logits[:, n_prefix:]
        logits = constrain(logits, batch_axes(), None, "model")
        out = (logits.astype(jnp.float32), new_cache, aux)
        return out + (slots,) if with_slots else out

    # ---------------- layers ----------------
    def _layers(self, params, x, *, mode: str, positions, cache=None,
                cache_pos=None, enc_out=None, window=None, segments=None):
        """The leading dense layers, then the groups, each stack under
        ``lax.scan`` (rematerialised per layer in training) ->
        ``(x, new_cache, aux_loss, slots)``; ``slots`` (num_groups, ...)
        are the expert-slot counts of each group's share layers."""
        cfg = self.cfg

        def stack(lcfg, x, aux, stacked, caches):
            def body(carry, xs):
                x, aux = carry
                gparams, gcache = xs
                new_cache = {}
                slots = _slots_of(lcfg)
                for i in range(lcfg.group_size):
                    entry = None if gcache is None else gcache[f"sub{i}"]
                    x, nc, a, s = _apply_sublayer(
                        gparams[f"sub{i}"], x, cfg=lcfg, sub_idx=i,
                        mode=mode, positions=positions, cache_entry=entry,
                        cache_pos=cache_pos, enc_out=enc_out, window=window,
                        segments=segments)
                    x = constrain(x, batch_axes(), None, None)
                    new_cache[f"sub{i}"] = nc
                    aux = aux + a
                    if s.shape[0]:
                        slots = slots + s
                return (x, aux), (new_cache, slots)

            if mode == "train":
                body = jax.checkpoint(body)
            if caches is None:
                (x, aux), (_, slots) = jax.lax.scan(
                    lambda c, gp: body(c, (gp, None)), (x, aux), stacked)
                return x, aux, None, slots
            (x, aux), (new_cache, slots) = jax.lax.scan(
                body, (x, aux), (stacked, caches))
            return x, aux, new_cache, slots

        aux = jnp.zeros((), jnp.float32)
        dense_cache, group_cache = cache, cache
        if cfg.first_k_dense:
            if cache is not None:
                dense_cache, group_cache = cache["dense"], cache["groups"]
            x, aux, dense_cache, _ = stack(_leading(cfg), x, aux,
                                           {"sub0": params["dense"]},
                                           dense_cache)
        x, aux, group_cache, slots = stack(cfg, x, aux, params["groups"],
                                           group_cache)
        new_cache = group_cache
        if cfg.first_k_dense and cache is not None:
            new_cache = {"dense": dense_cache, "groups": group_cache}
        return x, new_cache, aux, slots


def build_model(cfg: ArchConfig, max_seq: int = 4096) -> Model:
    return Model(cfg=cfg, max_seq=max_seq)
