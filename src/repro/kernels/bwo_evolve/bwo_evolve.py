"""Pallas TPU kernel: fused BWO mutation + procreation.

One pass over VMEM produces a child row block from two *dynamically
indexed* parent row blocks (scalar-prefetched ``p1_idx``/``p2_idx`` drive
the BlockSpec index maps — TPU's analogue of the gather the GPU version
does through shared memory), plus on-the-fly RNG decode from prefetched
random bits.  Fusing mutate+crossover avoids materializing the mutated
population and three (P, D) temporaries in HBM: HBM traffic drops from
~7 x P x D x 4B (separate HLO ops) to ~4 x P x D x 4B (read p1, p2,
bits1, bits2; write child).

Block layout: each length-D row is laid out as ``(rows, 128)`` lanes, so
one grid step reads a ``(block_rows, 128)`` tile of one child row (the
population axis is squeezed out of the block, since each child gathers
different parents).  ``block_rows`` is a multiple of 8 and D is padded
up to a whole number of tiles, which keeps every block's last two
dimensions at the TPU's (8, 128) f32 tiling.  The per-row mutation gate
is a scalar-prefetched SMEM value.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8          # f32 sublane tile
BLOCK_ROWS = 512      # 512 x 128 f32 = 256 KiB per operand block


def tile_rows(d: int) -> int:
    """Sublane rows per block for a length-``d`` row: ``BLOCK_ROWS``,
    shrunk to the row's own (8-aligned) height when the row is shorter."""
    rows = -(-d // LANES)
    return min(BLOCK_ROWS, -(-rows // SUBLANES) * SUBLANES)


def padded_dim(d: int) -> int:
    """``d`` rounded up to a whole number of ``(tile_rows, 128)`` tiles."""
    tile = tile_rows(d) * LANES
    return -(-d // tile) * tile


def _kernel(p1_idx_ref, p2_idx_ref, gate_ref, p1_ref, p2_ref, bits1_ref,
            bits2_ref, out_ref, *, pm_gene: float, mut_scale: float):
    # The random bits arrive bitcast to int32: Mosaic has no uint32 ->
    # float32 cast.  Every field below is masked to its width first, so
    # the arithmetic shift of int32 reads the same bits as uint32's.
    p1 = p1_ref[...]
    p2 = p2_ref[...]
    bits1 = bits1_ref[...]
    bits2 = bits2_ref[...]
    gate = gate_ref[pl.program_id(0)].astype(jnp.float32)

    mask = ((bits2 & 0xFF) < int(pm_gene * 256)).astype(p1.dtype)
    u_noise = (((bits2 >> 8) & 0xFFFFFF).astype(jnp.float32)
               * (1.0 / float(1 << 24)))
    noise = (2.0 * u_noise - 1.0) * mut_scale * (jnp.abs(p1) + 1e-3)
    p1m = p1 + noise.astype(p1.dtype) * mask * gate
    # float32(uint32 bits1) as one rounded sum of two exact 16-bit halves,
    # which is the same round-to-nearest the direct conversion does
    hi = ((bits1 >> 16) & 0xFFFF).astype(jnp.float32)
    lo = (bits1 & 0xFFFF).astype(jnp.float32)
    alpha = ((hi * 65536.0 + lo) * (1.0 / 4294967296.0)).astype(p1.dtype)
    out_ref[...] = alpha * p1m + (1.0 - alpha) * p2


def bwo_evolve_pallas(pop, p1_idx, p2_idx, bits1, bits2, row_gate, *,
                      pm_gene: float, mut_scale: float,
                      interpret: bool = False):
    """pop (P, Dp) f32 and bits1, bits2 (P, Dp) uint32, with ``Dp ==
    padded_dim(Dp)`` (the caller pads); p1_idx, p2_idx, row_gate (P,)
    int32."""
    P, Dp = pop.shape
    br = tile_rows(Dp)
    if Dp % (br * LANES):
        raise ValueError(f"row length {Dp} is not padded to whole "
                         f"({br}, {LANES}) tiles; pad with padded_dim()")
    rows = Dp // LANES
    grid = (P, rows // br)

    kernel = functools.partial(_kernel, pm_gene=pm_gene,
                               mut_scale=mut_scale)
    tile = (pl.Squeezed(), br, LANES)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=[
            pl.BlockSpec(tile, lambda i, j, i1, i2, g: (i1[i], j, 0)),
            pl.BlockSpec(tile, lambda i, j, i1, i2, g: (i2[i], j, 0)),
            pl.BlockSpec(tile, lambda i, j, i1, i2, g: (i, j, 0)),
            pl.BlockSpec(tile, lambda i, j, i1, i2, g: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec(tile, lambda i, j, i1, i2, g: (i, j, 0)),
    )

    def rows3(a):
        return a.reshape(P, rows, LANES)

    def int_rows3(bits):
        return rows3(jax.lax.bitcast_convert_type(bits, jnp.int32))

    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((P, rows, LANES), pop.dtype),
        interpret=interpret,
        name="bwo_evolve",
    )(p1_idx, p2_idx, row_gate, rows3(pop), rows3(pop), int_rows3(bits1),
      int_rows3(bits2))
    return out.reshape(P, Dp)
