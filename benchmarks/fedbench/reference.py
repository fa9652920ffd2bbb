"""Plain reference of one federated run, written from the protocol.

It imports nothing of the program.  From the server seed it draws the
initial weights and every random choice of the run on the key schedule
below.  No paper publishes one: it is the program's own, written down
here once, and ``correct`` holds the program to it.  A change to how the
program splits or uses its keys changes the yardstick, not only the
program.

* ``rng, init = split(PRNGKey(server_seed))``; ``init`` draws the
  initial weights;
* each round ``keys = split(rng, n_clients + 2)``: ``keys[0]`` is the
  next round's ``rng``, ``keys[1]`` picks FedAvg's participants,
  ``keys[2 + k]`` is client ``k``'s key;
* a client splits its key into ``(sgd, bwo)``; each local epoch takes
  ``sgd, epoch = split(sgd)``, and each valid batch ``epoch, dropout =
  split(epoch)`` (a padded batch leaves ``epoch`` as it was);
* BWO draws its initial spread from ``bwo``; each generation takes
  ``bwo, g = split(bwo)`` and ``split(g, 6)`` gives the mutation gate,
  the first and second parents, the crossover weights, the gene mask
  and the mutation noise, in that order.

Each client's round then runs in straightforward ``jax.numpy``:

* local SGD: ``local_epochs`` passes over the client's batches, one
  plain SGD step per valid batch, dropout keyed per batch;
* FedBWO: Black Widow Optimization on the flattened weights, fitness =
  mean loss over the first ``fitness_batches`` batches, in the paper's
  order mutation -> procreation -> cannibalism; the client returns its
  best member and that member's fitness;
* FedAvg: the client returns its trained weights and their fitness;
* the server adopts the winner (the first lowest score) or the mean of
  the participants, and evaluates on the whole test set.

In float32 it runs every matmul and convolution at ``highest``
precision.  With ``dtype=bfloat16`` and no precision it is the control:
the same run one precision step below what the configuration states.
Clients run in chunks under ``vmap`` and the test set in blocks, so the
reference fits beside nothing else on one chip.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree

EVAL_BLOCK = 1000


@dataclasses.dataclass(frozen=True)
class Protocol:
    strategy: str                 # "fedbwo" | "fedavg"
    local_epochs: int
    lr: float
    mh_pop: int
    mh_generations: int
    fitness_batches: int
    client_ratio: float = 1.0
    bwo: Dict[str, float] = dataclasses.field(default_factory=dict)

    @property
    def is_fedx(self) -> bool:
        return self.strategy != "fedavg"


@dataclasses.dataclass
class RunRecord:
    """What a run produced: initial weights, weights after the kept
    rounds, and one log per round (scores, winner or participants,
    test loss and accuracy)."""
    w0: Any
    snapshots: Dict[int, Any]
    logs: List[dict]


def _host(tree):
    return jax.tree.map(lambda a: np.asarray(a), jax.device_get(tree))


class Reference:
    """``fault`` plants a fault for the control tests: ``"half_batch"``
    trains on the first half of every batch."""

    def __init__(self, model, cfg: dict, proto: Protocol, clients: List[dict],
                 test: dict, server_seed: int, dtype=jnp.float32,
                 precision: Optional[str] = "highest",
                 fault: Optional[str] = None, chunk_bytes: float = 4e9):
        self.model, self.cfg, self.proto = model, cfg, proto
        self.dtype, self.precision, self.fault = dtype, precision, fault
        self.server_seed = int(server_seed)
        self.n = len(clients)
        nb = [c["labels"].shape[0] for c in clients]
        self.nb_max = max(nb)
        self.n_valid = jnp.asarray(nb, jnp.int32)

        def pad(a):
            out = np.zeros((self.nb_max,) + a.shape[1:], a.dtype)
            out[:a.shape[0]] = a
            return out
        self.images = jnp.asarray(
            np.stack([pad(c["images"]) for c in clients]), dtype)
        self.labels = jnp.asarray(np.stack([pad(c["labels"])
                                            for c in clients]))
        self.test_images = jnp.asarray(test["images"], dtype)
        self.test_labels = jnp.asarray(test["labels"])
        self.eval_block = math.gcd(int(self.test_labels.shape[0]), EVAL_BLOCK)
        d = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(
            jax.eval_shape(lambda k: model.init(k, cfg), jax.random.PRNGKey(0))))
        # a client's BWO step holds about a dozen (pop, D) float32 arrays
        per_client = max(proto.mh_pop, 1) * d * 4 * 12
        self.chunk = max(1, min(self.n, int(chunk_bytes // per_client)))
        self._chunk_fn = jax.jit(self._chunk_update)
        self._eval_block = jax.jit(self._eval_block_fn)
        self._fit_one = jax.jit(self._fitness_of)

    # ----------------------------------------------------------- model --
    def _ctx(self):
        if self.precision is None:
            return contextlib.nullcontext()
        return jax.default_matmul_precision(self.precision)

    def loss(self, params, images, labels, dropout_key=None):
        logits = self.model.logits(params, images, self.cfg, dropout_key)
        logp = jax.nn.log_softmax(logits)
        nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1).mean()
        acc = (logits.argmax(-1) == labels).mean()
        return nll, acc

    def init_params(self):
        _, pkey = jax.random.split(jax.random.PRNGKey(self.server_seed))
        with self._ctx():
            return self.model.init(pkey, self.cfg, self.dtype)

    # ---------------------------------------------------------- client --
    def _local_sgd(self, params, images, labels, n_valid, key):
        lr = self.proto.lr
        valid = jnp.arange(images.shape[0]) < n_valid
        half = self.fault == "half_batch"

        def step(carry, xs):
            p, r = carry
            x, y, v = xs
            r2, dkey = jax.random.split(r)
            if half:
                x, y = x[:x.shape[0] // 2], y[:y.shape[0] // 2]
            g = jax.grad(lambda q: self.loss(q, x, y, dkey)[0])(p)
            new = jax.tree.map(lambda a, b: a - lr * b.astype(a.dtype), p, g)
            p = jax.tree.map(lambda n, o: jnp.where(v, n, o), new, p)
            return (p, jnp.where(v, r2, r)), None

        rng = key
        for _ in range(self.proto.local_epochs):
            rng, ekey = jax.random.split(rng)
            (params, _), _ = jax.lax.scan(step, (params, ekey),
                                          (images, labels, valid))
        return params

    def _fitness_rows(self, images, labels, n_valid):
        f = self.proto.fitness_batches
        idx = jnp.minimum(jnp.arange(f), jnp.maximum(n_valid - 1, 0))
        return images[idx], labels[idx]

    def _fitness_of(self, params, images, labels, n_valid):
        xs, ys = self._fitness_rows(images, labels, n_valid)
        return jnp.stack([self.loss(params, xs[i], ys[i])[0]
                          for i in range(xs.shape[0])]).mean()

    def _bwo(self, x0, fit_fn, key):
        b = self.proto.bwo
        pop_n, dt = self.proto.mh_pop, x0.dtype
        noise = jax.random.normal(key, (pop_n, x0.shape[0]), dt)
        noise = noise * b["init_spread"] * (jnp.abs(x0)[None, :] + 1e-3)
        pop = x0[None, :] + noise.at[0].set(0.0)
        fit = fit_fn(pop)
        n_par = max(2, int(pop_n * b["procreate_frac"]))
        n_surv = max(1, int(pop_n * (1 - b["pc"])))

        def generation(_, carry):
            pop, fit, rng = carry
            rng, k = jax.random.split(rng)
            r_mut, r_sel, r_sel2, r_alpha, r_mask, r_noise = \
                jax.random.split(k, 6)
            d = pop.shape[1]
            gate = (jax.random.bernoulli(r_mut, b["pm"], (pop_n, 1))
                    & jax.random.bernoulli(r_mask, b["pm_gene"], (pop_n, d)))
            step = jax.random.normal(r_noise, (pop_n, d), dt) * b["mut_scale"]
            mutated = pop + step * (jnp.abs(pop) + 1e-3) * gate
            ranked = mutated[jnp.argsort(fit)]
            p1 = ranked[jax.random.randint(r_sel, (pop_n,), 0, n_par)]
            p2 = ranked[jax.random.randint(r_sel2, (pop_n,), 0, n_par)]
            alpha = jax.random.uniform(r_alpha, (pop_n, d), dt)
            children = alpha * p1 + (1 - alpha) * p2
            child_fit = fit_fn(children)
            keep = jnp.argsort(child_fit)[:n_surv]
            all_pop = jnp.concatenate([pop, children[keep]], 0)
            all_fit = jnp.concatenate([fit, child_fit[keep]], 0)
            order = jnp.argsort(all_fit)[:pop_n]
            return all_pop[order], all_fit[order], rng

        pop, fit, _ = jax.lax.fori_loop(0, self.proto.mh_generations,
                                        generation, (pop, fit, key))
        best = jnp.argmin(fit)
        return fit[best], pop[best]

    def _client(self, params, images, labels, n_valid, key):
        r_sgd, r_mh = jax.random.split(key)
        trained = self._local_sgd(params, images, labels, n_valid, r_sgd)
        x0, unravel = ravel_pytree(trained)
        xs, ys = self._fitness_rows(images, labels, n_valid)

        def fit_fn(pop):
            def one(flat):
                p = unravel(flat)
                return jnp.stack([self.loss(p, xs[i], ys[i])[0]
                                  for i in range(xs.shape[0])]).mean()
            return jax.lax.map(one, pop)

        if not self.proto.is_fedx:
            return fit_fn(x0[None])[0], trained
        score, best = self._bwo(x0, fit_fn, r_mh)
        return score, unravel(best)

    def _chunk_update(self, params, images, labels, n_valid, ids, keys):
        """The clients ``ids`` of the stacked data, with their keys."""
        images = jnp.take(images, ids, axis=0)
        labels = jnp.take(labels, ids, axis=0)
        n_valid = jnp.take(n_valid, ids, axis=0)
        return jax.vmap(self._client, in_axes=(None, 0, 0, 0, 0))(
            params, images, labels, n_valid, keys)

    def _run_clients(self, params, ids, keys):
        """Runs clients ``ids`` in equal chunks (the last chunk repeats
        its last client); returns per-client scores and the list of
        (chunk output params, row) per client."""
        ids = np.asarray(ids)
        c = min(self.chunk, len(ids))
        n_chunks = math.ceil(len(ids) / c)
        c = math.ceil(len(ids) / n_chunks)
        scores, where = [], []
        for j in range(n_chunks):
            part = ids[j * c:(j + 1) * c]
            take = np.concatenate([part, np.repeat(part[-1:], c - len(part))])
            with self._ctx():
                take = jnp.asarray(take, jnp.int32)
                s, p = self._chunk_fn(params, self.images, self.labels,
                                      self.n_valid, take,
                                      jnp.take(keys, take, axis=0))
            s = np.asarray(jax.device_get(s), np.float64)[:len(part)]
            scores.extend(s.tolist())
            where.extend((p, i) for i in range(len(part)))
        return np.asarray(scores), where

    # ---------------------------------------------------------- server --
    def run(self, n_rounds: int, keep_after: Optional[List[int]] = None
            ) -> RunRecord:
        """Follows ``n_rounds`` rounds from the seed; keeps the weights
        after each round index (0-based count of rounds done) in
        ``keep_after``."""
        keep_after = set(keep_after or [])
        rng, _ = jax.random.split(jax.random.PRNGKey(self.server_seed))
        params = self.init_params()
        rec = RunRecord(w0=_host(params), snapshots={}, logs=[])
        m = max(int(self.proto.client_ratio * self.n), 1)
        for r in range(n_rounds):
            keys = jax.random.split(rng, self.n + 2)
            rng, sel_key, ckeys = keys[0], keys[1], keys[2:]
            log: Dict[str, Any] = {}
            if self.proto.is_fedx:
                scores, where = self._run_clients(params, np.arange(self.n),
                                                  ckeys)
                best = int(np.argmin(scores))
                p, i = where[best]
                params = jax.tree.map(lambda a: a[i], p)
                log.update(scores=scores, best=best)
            else:
                sel = np.asarray(jax.random.choice(sel_key, self.n, (m,),
                                                   replace=False))
                scores, where = self._run_clients(params, sel, ckeys)
                total = None
                for p, i in where:
                    leaf = jax.tree.map(lambda a: a[i], p)
                    total = leaf if total is None else jax.tree.map(
                        jnp.add, total, leaf)
                params = jax.tree.map(lambda a: a / m, total)
                log.update(scores=scores, participants=sel.tolist())
            log["eval_loss"], log["eval_acc"] = self.evaluate(params)
            rec.logs.append(log)
            if r + 1 in keep_after:
                rec.snapshots[r + 1] = _host(params)
        return rec

    # ----------------------------------------------------- evaluation --
    def _eval_block_fn(self, params, images, labels, start):
        x = jax.lax.dynamic_slice_in_dim(images, start, self.eval_block)
        y = jax.lax.dynamic_slice_in_dim(labels, start, self.eval_block)
        nll, acc = self.loss(params, x, y)
        return nll.astype(jnp.float32) * x.shape[0], \
            acc.astype(jnp.float32) * x.shape[0]

    def evaluate(self, params):
        """Mean test loss and accuracy of ``params`` (host or device
        weights, cast to the reference's dtype)."""
        params = jax.tree.map(lambda a: jnp.asarray(a, self.dtype), params)
        n = self.test_labels.shape[0]
        nll = acc = 0.0
        with self._ctx():
            for s in range(0, n, self.eval_block):
                a, b = self._eval_block(params, self.test_images,
                                        self.test_labels, jnp.int32(s))
                nll, acc = nll + float(a), acc + float(b)
        return nll / n, acc / n

    def fitness(self, params, client: int) -> float:
        """The fitness (score) of ``params`` on client ``client``."""
        params = jax.tree.map(lambda a: jnp.asarray(a, self.dtype), params)
        with self._ctx():
            return float(self._fit_one(params, self.images[client],
                                       self.labels[client],
                                       self.n_valid[client]))
