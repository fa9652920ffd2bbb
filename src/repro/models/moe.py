"""Mixture-of-Experts layer with capacity-based, *locality-preserving*
dispatch.

TPU-native design (MaxText/GShard lineage, not a CUDA grouped-GEMM
port): tokens never leave their data shard during routing — position-in-
expert is a per-batch-row cumsum (no global argsort), and the dispatch
buffer is (B, E, C, d) with B sharded over ``data`` and E sharded over
``model`` (expert parallelism).  The only cross-device movement is the
expert-dim reshard around the expert einsums, which XLA lowers to an
all-to-all/all-gather over the ``model`` axis.

The first implementation used a *global* argsort over all (token, slot)
pairs; SPMD could not shard it and materialized (T*K, d) slot tensors
with ~1e14 link bytes per step on deepseek-v2 — see EXPERIMENTS.md §Perf
for the before/after.

Supports DeepSeek-V2 shared experts and Arctic's parallel dense residual.

A config whose ``MoEConfig.experts_held`` is set runs :func:`share_apply`
instead: the chip's share of an expert-parallel layer (the first
``experts_held`` of ``num_experts`` experts), dropless, with no capacity.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro import scopes
from repro.configs.base import ArchConfig
from repro.models import modules as nn
from repro.sharding import batch_axes, constrain
from repro.sharding.context import current_mesh


def moe_init(rng, cfg: ArchConfig):
    """Router over all ``num_experts``; expert weights of the experts
    held (``experts_held``, default all)."""
    m = cfg.moe
    d = cfg.d_model
    dff = m.expert_d_ff or cfg.d_ff
    held = m.experts_held or m.num_experts
    r = jax.random.split(rng, 6)
    dt = cfg.param_dtype
    scale = d ** -0.5

    def stack(key, shape):
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dt)

    p = {
        "router": {"w": (jax.random.normal(r[0], (d, m.num_experts),
                                           jnp.float32) * scale)},
        "wi": stack(r[1], (held, d, dff)),
        "wg": stack(r[2], (held, d, dff)),
        "wo": (jax.random.normal(r[3], (held, dff, d), jnp.float32)
               * dff ** -0.5).astype(dt),
    }
    if m.num_shared_experts:
        p["shared"] = nn.ffn_init(r[4], "swiglu", d,
                                  dff * m.num_shared_experts, dtype=dt)
    if m.dense_residual:
        p["dense"] = nn.ffn_init(r[5], "swiglu", d, cfg.d_ff, dtype=dt)
    return p


def _scatter_local(contrib, e_flat, pos_c, *, E, C):
    """(B?,SK,d) slot contributions -> (B?,E,C,d) dispatch buffer."""
    Bl, SK, d = contrib.shape
    bidx = jnp.broadcast_to(jnp.arange(Bl, dtype=jnp.int32)[:, None],
                            (Bl, SK))
    return jnp.zeros((Bl, E, C, d), contrib.dtype) \
        .at[bidx, e_flat, pos_c].add(contrib)


def _gather_local(yb, e_flat, pos_c):
    Bl, SK = e_flat.shape
    bidx = jnp.broadcast_to(jnp.arange(Bl, dtype=jnp.int32)[:, None],
                            (Bl, SK))
    return yb[bidx, e_flat, pos_c]


def _scatter_masked(contrib, e_flat, pos_c, *, E_loc, C):
    """Per-model-rank dispatch: scatter only the slots owned by local
    experts, producing an (B, E_loc, C, d) buffer that is *born* sharded
    over `model` — the replicate-then-slice version paid a (B,E,C,d)
    all-reduce in backward (EXPERIMENTS.md §Perf deepseek iteration 4)."""
    me = jax.lax.axis_index("model")
    lo = me * E_loc
    local = (e_flat >= lo) & (e_flat < lo + E_loc)
    e_loc = jnp.clip(e_flat - lo, 0, E_loc - 1)
    Bl, SK, d = contrib.shape
    bidx = jnp.broadcast_to(jnp.arange(Bl, dtype=jnp.int32)[:, None],
                            (Bl, SK))
    masked = contrib * local[..., None].astype(contrib.dtype)
    return jnp.zeros((Bl, E_loc, C, d), contrib.dtype) \
        .at[bidx, e_loc, pos_c].add(masked)


def _local_dispatch_fns(B: int, E: int, C: int):
    """shard_map-wrapped scatter/gather when a mesh is active and the
    batch divides the data axes; plain local ops otherwise (smoke tests,
    B=1 decode).  The combine all-gathers the dispatch buffer: a psum of
    each model shard's own slots was measured on deepseek-v2 train_4k
    and moved 28% more link bytes (2 x (B,SK,d) a pass against the
    all-gather's (E,C,d), within ~1.5x of each other at K=6, cf=1.25)."""
    import functools
    scatter = functools.partial(_scatter_local, E=E, C=C)
    mesh = current_mesh()
    if mesh is None:
        return scatter, _gather_local
    baxes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    nb = 1
    for a in baxes:
        nb *= mesh.shape[a]
    if not baxes or B % nb != 0:
        return scatter, _gather_local
    bs = P(baxes, None)
    if "model" in mesh.axis_names and E % mesh.shape["model"] == 0:
        E_loc = E // mesh.shape["model"]
        scatter_sm = shard_map(
            functools.partial(_scatter_masked, E_loc=E_loc, C=C), mesh=mesh,
            in_specs=(P(baxes, None, None), bs, bs),
            out_specs=P(baxes, "model", None, None), check_vma=False)
    else:
        scatter_sm = shard_map(
            scatter, mesh=mesh,
            in_specs=(P(baxes, None, None), bs, bs),
            out_specs=P(baxes, None, None, None), check_vma=False)
    gather_sm = shard_map(
        _gather_local, mesh=mesh,
        in_specs=(P(baxes, None, None, None), bs, bs),
        out_specs=P(baxes, None, None), check_vma=False)
    return scatter_sm, gather_sm


def moe_apply(p, x, cfg: ArchConfig, *, capacity_factor: float = 1.25
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, d) -> (y, aux_loss).  B stays sharded over `data`."""
    m = cfg.moe
    B, S, d = x.shape
    E, K = m.num_experts, m.top_k
    SK = S * K

    logits = x.astype(jnp.float32) @ p["router"]["w"]             # (B,S,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, eidx = jax.lax.top_k(probs, K)                          # (B,S,K)
    if m.renormalize:
        gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    # ---- load-balance auxiliary loss (Switch-style) ----
    me = probs.mean((0, 1))                                       # (E,)
    ce = jax.nn.one_hot(eidx, E, dtype=jnp.float32).sum((0, 1, 2)) \
        / (B * SK)
    aux = E * jnp.sum(me * ce) * m.router_aux_loss

    # ---- per-row position-in-expert (cumsum, no sort, fully local) ----
    e_flat = eidx.reshape(B, SK)                                  # (B,SK)
    onehot = jax.nn.one_hot(e_flat, E, dtype=jnp.int32)           # (B,SK,E)
    pos = jnp.take_along_axis(jnp.cumsum(onehot, axis=1) - 1,
                              e_flat[..., None], axis=-1)[..., 0]  # (B,SK)

    C = max(8, int(capacity_factor * SK / E + 0.999))
    C = -(-C // 8) * 8
    keep = pos < C
    pos_c = jnp.minimum(pos, C - 1)

    token_of_slot = jnp.repeat(jnp.arange(S, dtype=jnp.int32), K)  # (SK,)
    x_slot = jnp.take(x, token_of_slot, axis=1)                    # (B,SK,d)
    contrib = x_slot * keep[..., None].astype(x.dtype)

    # SPMD cannot shard batched scatters/gathers on their batch dim (it
    # replicates them — catastrophic at deepseek scale), so dispatch and
    # combine run under shard_map where they are *provably local*.
    scatter_fn, gather_fn = _local_dispatch_fns(B, E, C)
    xb = scatter_fn(contrib, e_flat, pos_c)                        # (B,E,C,d)
    xb = constrain(xb, batch_axes(), "model", None, None)

    # ---- expert FFN (swiglu); expert dim sharded over `model` ----
    wg = constrain(p["wg"], "model", None, None)
    wi = constrain(p["wi"], "model", None, None)
    wo = constrain(p["wo"], "model", None, None)
    h = (jax.nn.silu(jnp.einsum("becd,edf->becf", xb, wg))
         * jnp.einsum("becd,edf->becf", xb, wi))
    yb = jnp.einsum("becf,efd->becd", h, wo)
    yb = constrain(yb, batch_axes(), None, None, None)

    # ---- gather back & combine top-k (local again) ----
    y_slot = gather_fn(yb, e_flat, pos_c) * keep[..., None].astype(yb.dtype)
    y = (y_slot.reshape(B, S, K, d)
         * gate.astype(yb.dtype)[..., None]).sum(2)               # (B,S,d)

    if m.num_shared_experts:
        y = y + nn.ffn_apply("swiglu", p["shared"], x)
    if m.dense_residual:
        y = y + nn.ffn_apply("swiglu", p["dense"], x)
    return y, aux


# ------------------------------------------------------- the chip's share --
def route(p, x, cfg: ArchConfig):
    """Softmax over all ``num_experts`` router logits in float32, greedy
    top-k; the gates are renormalised only where the config says so
    (DeepSeek-V2's ``norm_topk_prob``).  x (N, d) -> gates, experts
    (N, K)."""
    logits = x.astype(jnp.float32) @ p["router"]["w"].astype(jnp.float32)
    gate, eidx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1),
                               cfg.moe.top_k)
    if cfg.moe.renormalize:
        gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    return gate, eidx


@jax.custom_vjp
def _permute(a, order, inverse):
    """The rows of ``a`` in the order ``order``, whose inverse permutation
    is ``inverse``.  Its transpose is the inverse gather, where autodiff
    of a plain ``take`` would scatter-add."""
    return jnp.take(a, order, axis=0)


def _permute_fwd(a, order, inverse):
    return _permute(a, order, inverse), (order, inverse)


def _permute_bwd(res, g):
    order, inverse = res
    return jnp.take(g, inverse, axis=0), None, None


_permute.defvjp(_permute_fwd, _permute_bwd)


def share_routed(p, x, cfg: ArchConfig, first_expert: int = 0):
    """The held experts' part of an expert layer, dropless.

    x (N, d).  Every token is routed over all ``num_experts``; of its
    top-k slots, those that land on the held experts ``first_expert ..
    first_expert + H - 1`` (H = the leading axis of ``p["wi"]``) are
    sorted by expert and run as one grouped SwiGLU product over exactly
    those slots (``jax.lax.ragged_dot``: no capacity, no slot dropped);
    slots on absent experts add nothing.  Returns ``(y, slots)``: the
    gate-weighted sum of the held experts' outputs (N, d), and the
    slot count of each held expert with the absent experts' count last
    (H + 1,) int32.

    The products run over at least N rows: where fewer slots are held,
    the last held group is stretched over zeroed absent slots, so the
    grouped product does the same work for every routing that holds
    fewer than N slots (an even router holds N * K * H / num_experts).
    The slot permutation and its inverse are gathers both ways, and the
    counts a comparison, so the layer's gradient scatters no slot row
    to where the routing points."""
    n, d = x.shape
    held = p["wi"].shape[0]
    K = cfg.moe.top_k
    with jax.named_scope(scopes.MOE_ROUTE):
        gate, eidx = route(p, x, cfg)
        local = eidx.reshape(-1) - first_expert                 # (N*K,)
        mine = (local >= 0) & (local < held)
        group = jnp.where(mine, local, held).astype(jnp.int32)
        slots = (group[:, None] == jnp.arange(held + 1)).sum(
            0, dtype=jnp.int32)
        order = jnp.argsort(group, stable=True)
        back = jnp.argsort(order)
        is_held = (group[order] < held)[:, None]
        xs = jnp.where(is_held, _permute(jnp.repeat(x, K, axis=0), order,
                                         back), 0)
    with jax.named_scope(scopes.MOE_EXPERTS):
        sizes = slots[:held]
        sizes = sizes.at[held - 1].add(jnp.maximum(n - sizes.sum(), 0))
        # rows past the held groups belong to no group: ragged_dot
        # leaves them unspecified, so each product's rows are masked
        h = (jax.nn.silu(jax.lax.ragged_dot(xs, p["wg"], sizes))
             * jax.lax.ragged_dot(xs, p["wi"], sizes))
        h = jnp.where(is_held, h, 0)
        ys = jnp.where(is_held, jax.lax.ragged_dot(h, p["wo"], sizes), 0)
    with jax.named_scope(scopes.MOE_ROUTE):
        y_slot = _permute(ys, back, order).reshape(n, K, d)
        y = (y_slot * gate.astype(ys.dtype)[..., None]).sum(1)
    return y.astype(x.dtype), slots


def share_apply(p, x, cfg: ArchConfig, first_expert: int = 0):
    """The chip's share of an expert layer: x (B, S, d) -> (y, slots),
    the held experts' routed part (:func:`share_routed`) plus the shared
    experts, which every chip computes for every token.  No auxiliary
    loss."""
    B, S, d = x.shape
    y, slots = share_routed(p, x.reshape(B * S, d), cfg, first_expert)
    y = y.reshape(B, S, d)
    if cfg.moe.num_shared_experts:
        with jax.named_scope(scopes.DENSE_FFN):
            y = y + nn.ffn_apply("swiglu", p["shared"], x)
    return y, slots
