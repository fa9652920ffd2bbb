"""FL client: local SGD epochs + (FedX) meta-heuristic weight refinement.

The whole local update is one jit'd function per (task, strategy):
``lax.fori_loop`` over epochs, ``lax.scan`` over the client's batches,
then G generations of the meta-heuristic on the flattened weights with
fitness = loss on the client's own data (paper Algorithm 3,
UpdateClient).

The loops are rolled or unrolled by the target backend
(:func:`unrolls`): XLA:CPU executes convolutions inside while loops
(``lax.scan`` / ``fori_loop``) ~20x slower than unrolled (no fast conv
thunk in loop bodies), so on the CPU the client loops are unrolled in
Python.  Everywhere else they stay rolled, so the compiled program's
size does not grow with batches, epochs, generations or population.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree

from repro.core import tracing
from repro.metaheuristics import Metaheuristic
from repro.metaheuristics.base import best_member


class Task(NamedTuple):
    """A trainable task: loss_fn(params, batch) -> (loss, acc).

    ``slot_loss(params, batch) -> (loss, slots)``, where given, is the
    training step's loss together with the int32 expert-slot counts of
    its forward pass (a model whose expert layers hold a share of the
    experts): local SGD then sums them over its steps and the client
    update returns them last (``CommMeter.expert_slots``)."""
    init_params: Callable[[jax.Array], Any]
    loss_fn: Callable[[Any, Any], Tuple[jnp.ndarray, jnp.ndarray]]
    slot_loss: Optional[Callable[[Any, Any], Tuple[jnp.ndarray,
                                                   jnp.ndarray]]] = None


@dataclasses.dataclass(frozen=True)
class ClientHP:
    local_epochs: int = 5
    lr: float = 0.0025                  # paper §IV-A
    momentum: float = 0.9
    mh_pop: int = 8
    mh_generations: int = 5
    fitness_batches: int = 2
    # Beyond-paper (DESIGN.md §3): evolve a low-dimensional subspace
    # instead of the raw weight vector.  The genome is one multiplicative
    # gain per parameter tensor (dim = #leaves, not #params), so BWO on a
    # 100M+ model needs O(P x leaves) memory instead of O(P x params).
    # The protocol (score uplink, winner fetch) is unchanged.
    subspace: bool = False
    subspace_scale: float = 0.05
    # FedProx proximal term (Li et al. 2020, paper's related work [18]):
    # local objective += (mu/2) * ||w - w_global||^2.  0 disables.
    prox_mu: float = 0.0
    # How the round engine (repro.core.engine) traverses the client
    # axis: "vmap" | "scan" | "auto" (scan on CPU, vmap elsewhere).  "scan:k" chunks the scan (unroll=k) so compile
    # time stays flat in n_clients while dispatch overhead amortizes.
    # See repro.core.knobs, engine.resolve_vectorize and DESIGN.md §4-5.
    vectorize: str = "auto"


def unrolls(backend: Optional[str] = None) -> bool:
    """Whether the client loops are Python-unrolled on ``backend``
    (default: ``jax.default_backend()``): on the CPU only."""
    return (backend or jax.default_backend()) == "cpu"


def make_local_sgd(task: Task, hp: ClientHP, masked: bool = False,
                   unroll: bool = True):
    """data: dict of arrays with leading (n_batches, batch, ...) dims.

    With ``masked=True`` the returned ``local_sgd`` takes an extra
    ``(n_batches,)`` bool mask marking valid (non-padded) batches; the
    update of a padded batch is discarded with ``jnp.where`` and —
    crucially for parity with the same client's unpadded run — the PRNG
    carry only advances past valid batches, so the per-batch dropout
    keys match the unpadded run's bit for bit.

    Where the task counts expert slots (``task.slot_loss``),
    ``local_sgd`` returns ``(params, slots)``: the counts summed over
    the valid steps.
    """
    counted = task.slot_loss is not None

    def one_step(params, batch, dkey, anchor=None):
        def obj(p):
            keyed = {**batch, "rng": dkey}
            loss, slots = (task.slot_loss(p, keyed) if counted
                           else (task.loss_fn(p, keyed)[0], None))
            if hp.prox_mu > 0 and anchor is not None:   # FedProx
                sq = sum(jnp.sum(jnp.square(a.astype(jnp.float32)
                                            - b.astype(jnp.float32)))
                         for a, b in zip(jax.tree.leaves(p),
                                         jax.tree.leaves(anchor)))
                loss = loss + 0.5 * hp.prox_mu * sq
            return loss, slots

        grads, slots = jax.grad(obj, has_aux=True)(params)
        return jax.tree.map(
            lambda p, g: p - hp.lr * g.astype(p.dtype), params,
            grads), slots

    def sgd_epoch(params, data, rng, anchor, mask, total):
        def one_batch(carry, xs):
            params, rng, total = carry
            batch, valid = xs if masked else (xs, None)
            rng2, dkey = jax.random.split(rng)
            new_params, slots = one_step(params, batch, dkey, anchor)
            if masked:
                new_params = jax.tree.map(
                    lambda n, p: jnp.where(valid, n, p), new_params, params)
                rng2 = jnp.where(valid, rng2, rng)
                if counted:
                    slots = jnp.where(valid, slots, 0)
            if counted:
                total = total + slots
            return (new_params, rng2, total), None

        n_batches = jax.tree.leaves(data)[0].shape[0]
        (params, _, total), _ = jax.lax.scan(
            one_batch, (params, rng, total),
            (data, mask) if masked else data,
            unroll=n_batches if unroll else 1)
        return params, total

    def local_sgd(params, data, rng, mask=None):
        with jax.named_scope(tracing.LOCAL_SGD):
            anchor = params if hp.prox_mu > 0 else None  # w_global (FedProx)
            total = None
            if counted:
                total = jnp.zeros_like(jax.eval_shape(
                    task.slot_loss, params,
                    {**jax.tree.map(lambda a: a[0], data),
                     "rng": rng})[1])
            if unroll:
                for _ in range(hp.local_epochs):
                    rng, ekey = jax.random.split(rng)
                    params, total = sgd_epoch(params, data, ekey, anchor,
                                              mask, total)
            else:
                def body(_, carry):
                    params, rng, total = carry
                    rng, ekey = jax.random.split(rng)
                    params, total = sgd_epoch(params, data, ekey, anchor,
                                              mask, total)
                    return params, rng, total
                params, _, total = jax.lax.fori_loop(
                    0, hp.local_epochs, body, (params, rng, total))
            return (params, total) if counted else params

    return local_sgd


def _fitness_slice(data, n_batches: int, n_valid=None):
    """First ``n_batches`` batches of a client dataset.

    For padded datasets (``n_valid`` given, the count of valid leading
    batches) this replicates the unpadded ``a[:n_batches][i]`` clamp
    semantics with a gather at ``min(i, n_valid - 1)``: a client with
    fewer than ``n_batches`` valid batches scores the same duplicated
    trailing batch as its unpadded run does, never a padded zero
    batch.
    """
    if n_valid is None:
        return jax.tree.map(lambda a: a[:n_batches], data)
    idx = jnp.minimum(jnp.arange(n_batches), jnp.maximum(n_valid - 1, 0))
    return jax.tree.map(lambda a: jnp.take(a, idx, axis=0), data)


def make_fitness_fn(task: Task, data, unravel, n_batches: int,
                    unroll: bool = True, n_valid=None):
    """Batched population fitness: mean loss over the first n_batches.

    ``unravel`` decodes one population row (a flat weight vector or a
    subspace genome) into params.  Sequential map (not vmap) over the
    population: vmapping over *conv weights* lowers to grouped
    convolutions that are pathologically slow on CPU; population members
    are independent, so a map keeps each on the fast conv path.
    Python-unrolled with ``unroll`` (see :func:`unrolls`), else a
    ``lax.map``; traced inside the ``fl.bwo_fitness`` scope.
    ``n_valid`` marks the valid-batch count of a padded dataset (see
    :func:`_fitness_slice`).
    """
    sub = _fitness_slice(data, n_batches, n_valid)

    def one(flat):
        params = unravel(flat)
        batches = [jax.tree.map(lambda a: a[i], sub)
                   for i in range(n_batches)]
        losses = [task.loss_fn(params, b)[0] for b in batches]
        return jnp.stack(losses).mean()

    def fit_fn(pops):
        with jax.named_scope(tracing.BWO_FITNESS):
            if unroll:
                return jnp.stack([one(pops[i])
                                  for i in range(pops.shape[0])])
            return jax.lax.map(one, pops)
    return fit_fn


def make_subspace_map(params, scale: float):
    """Genome z (one gain per tensor) -> params * (1 + scale * (z - 1)).

    The genome is centered at 1.0 (identity map) so the meta-heuristics'
    *relative* move scales — tuned for refining non-zero weights — apply
    directly to z."""
    leaves, treedef = jax.tree_util.tree_flatten(params)

    def apply_z(z):
        scaled = [leaf * (1.0 + scale * (z[i] - 1.0)).astype(leaf.dtype)
                  for i, leaf in enumerate(leaves)]
        return jax.tree_util.tree_unflatten(treedef, scaled)

    return len(leaves), apply_z


def _evolve(mh: Metaheuristic, hp: ClientHP, rng, state, fit_fn,
            unroll: bool):
    """``hp.mh_generations`` meta-heuristic steps, one key split each."""
    if unroll:
        for _ in range(hp.mh_generations):
            rng, k = jax.random.split(rng)
            state = mh.step(k, state, fit_fn)
        return state

    def gen(i, carry):
        state, rng = carry
        rng, k = jax.random.split(rng)
        return mh.step(k, state, fit_fn), rng

    state, _ = jax.lax.fori_loop(0, hp.mh_generations, gen, (state, rng))
    return state


def make_client_update(task: Task, hp: ClientHP,
                       mh: Optional[Metaheuristic] = None,
                       masked: bool = False,
                       backend: Optional[str] = None):
    """Returns jit-able ``client_update(params, data, rng) ->
    (score, params)``.  With ``mh`` (FedX): SGD then meta-heuristic
    refinement; without (FedAvg): plain SGD, score = post-training loss.

    With ``masked=True`` the signature becomes ``client_update(params,
    data, mask, rng)``: ``data`` is one client's row of a pad+mask stack
    (:func:`repro.core.engine.stack_clients`) and ``mask`` its
    ``(n_batches,)`` bool validity row.  Padded batches contribute no
    SGD step and no fitness term, so scores and weights match the same
    client's unpadded run.

    ``backend`` is the platform the update is compiled for (default:
    ``jax.default_backend()``); it decides :func:`unrolls`.

    Where the task counts expert slots (``task.slot_loss``) the update
    returns ``(score, params, slots)``, the slots of its local SGD.
    """
    unroll = unrolls(backend)
    local_sgd = make_local_sgd(task, hp, masked=masked, unroll=unroll)
    counted = task.slot_loss is not None

    def client_update(global_params, data, rng, mask=None):
        r_sgd, r_mh = jax.random.split(rng)
        params = local_sgd(global_params, data, r_sgd, mask)
        tail = ()
        if counted:
            params, slots = params
            tail = (slots,)
        n_valid = None if mask is None else jnp.sum(mask.astype(jnp.int32))

        if hp.subspace and mh is not None:
            n_genes, decode = make_subspace_map(params, hp.subspace_scale)
            x0 = jnp.ones((n_genes,))
        else:
            x0, decode = ravel_pytree(params)
        fit_fn = make_fitness_fn(task, data, decode, hp.fitness_batches,
                                 unroll=unroll, n_valid=n_valid)
        if mh is None:
            score = fit_fn(x0[None])[0]
            return (score, params) + tail
        with jax.named_scope(tracing.BWO_EVOLVE):
            state = mh.init(r_mh, x0, hp.mh_pop, fit_fn)
            state = _evolve(mh, hp, r_mh, state, fit_fn, unroll)
            best, best_fit = best_member(state)
            return (best_fit, decode(best)) + tail

    if masked:
        def masked_update(global_params, data, mask, rng):
            return client_update(global_params, data, rng, mask)
        return masked_update

    def plain_update(global_params, data, rng):
        return client_update(global_params, data, rng)

    return plain_update
