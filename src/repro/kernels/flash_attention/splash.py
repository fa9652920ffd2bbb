"""Causal flash attention over packed documents, on the TPU.

A thin wrapper around JAX's splash attention kernel
(``jax.experimental.pallas.ops.tpu.splash_attention``), which has a
forward and a backward kernel, document ids (``SegmentIds``) and
separate query/key and value head widths.  The scores of a block stay
in the kernel's fast memory: nothing of the (S, S) square is written to
HBM, and key blocks that lie wholly above the causal diagonal are
skipped.

Blocks are skipped by the causal mask alone, never by documents: which
blocks hold no pair of one document depends on the data, so a skip by
documents would make the work follow the data.  The documents enter
only as the elementwise mask inside the blocks the kernel computes.

Operands enter the kernel in bfloat16; the softmax statistics and the
accumulators are float32 inside it.  This is the arithmetic of a
float32 einsum at XLA's default precision on the TPU, one bfloat16 pass
with float32 accumulation, which the unfused path
(``repro.models.attention.blockwise_attention``, this kernel's oracle)
runs.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu import splash_attention as splash

BLOCK = 512
"""Query and key block of every kernel, forward and backward, and the
keys of each inner step.  Chosen by a sweep on one TPU v5e at the token
cell's 4,096 tokens (PERF.md)."""


def causal_flash_attention(q, k, v, segments, *, scale: float,
                           block: int = BLOCK,
                           interpret: Optional[bool] = None):
    """q, k: (B, S, H, dqk); v: (B, S, H, dv); segments: (B, S) document
    ids, or None for one document a row -> (B, S, H, dv) float32.  A
    query attends to the keys at or before it in its own document, with
    softmax scale ``scale``.  S must be a multiple of ``block`` (itself
    a multiple of 128)."""
    B, S, H, _ = q.shape
    if S % block or block % 128:
        raise ValueError(f"sequence {S} is not a multiple of the block "
                         f"{block}, or the block not of 128")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    mask = splash.MultiHeadMask([splash.CausalMask((S, S))] * H)
    sizes = splash.BlockSizes(
        block_q=block, block_kv=block, block_kv_compute=block,
        block_q_dkv=block, block_kv_dkv=block, block_kv_dkv_compute=block,
        use_fused_bwd_kernel=True)
    kernel = splash.make_splash_mha_single_device(
        mask, block_sizes=sizes, interpret=interpret)

    def heads_first(t):                               # (B, H, S, d) bf16
        return t.astype(jnp.bfloat16).transpose(0, 2, 1, 3)

    args = [heads_first(q * scale), heads_first(k), heads_first(v)]
    if segments is not None:
        ids = segments.astype(jnp.int32)
        args.append(splash.SegmentIds(q=ids, kv=ids))
    out = jax.vmap(kernel)(*args)
    return out.transpose(0, 2, 1, 3).astype(jnp.float32)
