"""Black Widow Optimization (Hayyolalam & Kazem 2020), FedBWO variant.

The paper (§III-C) *reorders* the canonical BWO for FL: each generation
runs **mutation -> procreation -> cannibalism** (instead of mating first),
then clients report only the best fitness.  We implement that order.

Continuous adaptation for NN weights (recorded in DESIGN.md): the
original BWO mutates by swapping two genes; for weight vectors we use a
sparse Gaussian perturbation (per-gene prob ``pm_gene``) whose scale is
relative to the gene magnitude — the TPU-friendly equivalent.  The fused
generation update is also available as a Pallas kernel
(``repro.kernels.bwo_evolve``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.metaheuristics.base import (Metaheuristic, draw_rows,
                                       init_population, rows_drawable)


def _pick(rows, idx):
    """``rows[idx]`` for a few rows, as a chain of selects.  Under
    ``vmap`` a row gather lowers on the TPU to chunked gathers, each
    chunk copied twice more into place; the selects read the rows in
    one fused pass."""
    out = jnp.broadcast_to(rows[-1], (idx.shape[0],) + rows.shape[1:])
    for k in range(rows.shape[0] - 2, -1, -1):
        out = jnp.where((idx == k)[:, None], rows[k][None], out)
    return out


def bwo(pm: float = 0.4, pc: float = 0.44, pm_gene: float = 0.1,
        mut_scale: float = 0.05, procreate_frac: float = 0.6,
        use_pallas: bool = False) -> Metaheuristic:
    """pm: per-individual mutation prob; pc: cannibalism rate (fraction of
    offspring eliminated); procreate_frac: fraction of pop used as parents.

    A generation computes only what it keeps.  Mutation is drawn and
    applied for the ``n_par`` fittest rows alone, the only rows
    procreation reads (rows of the same ``(P, D)`` draws,
    :func:`~repro.metaheuristics.base.draw_rows`); children select from
    the mutated parents directly; and cannibalism composes its two
    selections on the fitness vectors, then gathers each surviving row
    once from the population and the children.  The result equals the
    plain formulation (mutate all ``P``, rank, cross over, select the
    best ``n_surv`` children, then the best ``P`` of parents +
    survivors) bit for bit.
    """

    def n_parents(P):
        return max(2, int(P * procreate_frac))

    def init(rng, x0, pop, fit_fn):
        return init_population(rng, x0, pop, fit_fn)

    def step(rng, state, fit_fn):
        pop, fit = state["pop"], state["fit"]
        P, D = pop.shape
        r_mut, r_sel, r_sel2, r_alpha, r_mask, r_noise = jax.random.split(rng, 6)

        if use_pallas:
            from repro.kernels.bwo_evolve import ops as bwo_ops
            children = bwo_ops.bwo_evolve(
                pop, fit, rng, pm=pm, pm_gene=pm_gene, mut_scale=mut_scale,
                procreate_frac=procreate_frac)
        else:
            # ---- 1. mutation of the parents, the fittest n_par rows
            #         (sparse Gaussian, per-individual gated) ----
            n_par = n_parents(P)
            par = jnp.argsort(fit)[:n_par]
            parents = pop[par]
            mut_ind = jax.random.bernoulli(r_mut, pm, (P, 1))[par]
            mut_gene = draw_rows(r_mask, par, (P, D), "bernoulli",
                                 p=pm_gene)
            noise = draw_rows(r_noise, par, (P, D), "normal",
                              pop.dtype) * mut_scale
            noise = noise * (jnp.abs(parents) + 1e-3)
            mutated = parents + noise * (mut_ind & mut_gene)

            # ---- 2. procreation: alpha-crossover among the parents ----
            p1 = _pick(mutated, jax.random.randint(r_sel, (P,), 0, n_par))
            p2 = _pick(mutated, jax.random.randint(r_sel2, (P,), 0, n_par))
            alpha = jax.random.uniform(r_alpha, (P, D), pop.dtype)
            children = alpha * p1 + (1 - alpha) * p2

        # fitness and cannibalism both read the children: written once,
        # or XLA redoes the crossover and its alpha draw for each reader
        children = jax.lax.optimization_barrier(children)
        child_fit = fit_fn(children)

        # ---- 3. cannibalism: drop the worst pc of offspring, then keep
        #         the best P of (parents + survivors); row k < P of that
        #         union is pop[k], row P + i is children[keep[i]] ----
        n_surv = max(1, int(P * (1 - pc)))
        keep = jnp.argsort(child_fit)[:n_surv]
        all_fit = jnp.concatenate([fit, child_fit[keep]])
        sel = jnp.argsort(all_fit)[:P]
        src = jnp.where(sel < P, sel, P + keep[jnp.maximum(sel - P, 0)])
        new_pop = jnp.concatenate([pop, children])[src]
        return {"pop": new_pop, "fit": all_fit[sel], "t": state["t"] + 1}

    def mutation_rows(P, D):
        drawn = (n_parents(P) if not use_pallas and rows_drawable((P, D))
                 else P)
        return drawn, P

    return Metaheuristic("bwo", init, step, mutation_rows)
