"""jit'd wrapper: full BWO generation step = rank parents, draw RNG,
call the fused Pallas kernel (padding D to the kernel's tile)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.bwo_evolve.bwo_evolve import bwo_evolve_pallas, padded_dim
from repro.kernels.bwo_evolve import ref as ref_lib


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _sample(pop, fit, rng, pm: float, procreate_frac: float):
    """Parent indices, random bits and row gates of one generation —
    shared by the kernel path and the reference so both see one draw."""
    P, D = pop.shape
    r_sel1, r_sel2, r_b1, r_b2, r_gate = jax.random.split(rng, 5)
    n_par = max(2, int(P * procreate_frac))
    order = jnp.argsort(fit)
    p1_idx = order[jax.random.randint(r_sel1, (P,), 0, n_par)].astype(jnp.int32)
    p2_idx = order[jax.random.randint(r_sel2, (P,), 0, n_par)].astype(jnp.int32)
    bits1 = jax.random.bits(r_b1, (P, D), jnp.uint32)
    bits2 = jax.random.bits(r_b2, (P, D), jnp.uint32)
    gate = jax.random.bernoulli(r_gate, pm, (P,))
    return p1_idx, p2_idx, bits1, bits2, gate


@functools.partial(jax.jit, static_argnames=("pm", "pm_gene", "mut_scale",
                                             "procreate_frac", "interpret"))
def bwo_evolve(pop, fit, rng, *, pm: float = 0.4, pm_gene: float = 0.1,
               mut_scale: float = 0.05, procreate_frac: float = 0.6,
               interpret: bool | None = None):
    """One BWO generation: (P, D) population -> (P, D) children.

    Selection/cannibalism is done by the caller on child fitness.
    """
    P, D = pop.shape
    if interpret is None:
        interpret = not _on_tpu()
    p1_idx, p2_idx, bits1, bits2, gate = _sample(pop, fit, rng, pm,
                                                 procreate_frac)
    pad = ((0, 0), (0, padded_dim(D) - D))
    children = bwo_evolve_pallas(
        jnp.pad(pop.astype(jnp.float32), pad), p1_idx, p2_idx,
        jnp.pad(bits1, pad), jnp.pad(bits2, pad), gate.astype(jnp.int32),
        pm_gene=pm_gene, mut_scale=mut_scale, interpret=interpret)
    return children[:, :D].astype(pop.dtype)


def bwo_evolve_reference(pop, fit, rng, *, pm: float = 0.4,
                         pm_gene: float = 0.1, mut_scale: float = 0.05,
                         procreate_frac: float = 0.6):
    """Same sampling path, pure-jnp math — the oracle for kernel tests."""
    p1_idx, p2_idx, bits1, bits2, gate = _sample(pop, fit, rng, pm,
                                                 procreate_frac)
    children = ref_lib.bwo_evolve_ref(
        pop.astype(jnp.float32), p1_idx, p2_idx, bits1, bits2,
        gate.astype(jnp.float32)[:, None], pm_gene=pm_gene,
        mut_scale=mut_scale)
    return children.astype(pop.dtype)
