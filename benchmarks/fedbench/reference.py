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
* FedBWO: Black Widow Optimization on a genome of the trained weights,
  fitness = mean loss over the first ``fitness_batches`` batches, in the
  paper's order mutation -> procreation -> cannibalism; the client
  returns its best member and that member's fitness.  The protocol's
  ``genome`` is ``"flat"``, the flattened weights (the paper's), or
  ``"tensor"``, one gain ``z`` per weight tensor starting at 1, decoded
  as ``w * (1 + genome_scale * (z - 1))`` over the tensors in
  ``jax.tree_util.tree_flatten`` order.  The genome is part of the
  yardstick: a configuration's reference module must build its weights
  as a tree whose leaves are the program's tensors in the program's
  order;
* FedAvg: the client returns its trained weights and their fitness;
* the server adopts the winner (the first lowest score) or the mean of
  the participants, and evaluates on the whole test set.

A client's data is a dict of arrays with leading ``(n_batches,
batch)`` axes (the test set's with a leading example axis), whatever
its keys.  The loss is the configuration module's ``loss(params, batch,
cfg, dropout_key) -> (loss, accuracy)``, a mean, where it defines one,
with ``count(batch, cfg)``, the number of terms that mean is over; else
the class cross-entropy of its ``logits`` on ``images`` against
``labels``, a mean over the batch's examples.  The test loss is the
mean over the whole test set, as one call of the loss would give it,
taken in blocks each weighted by its count.

In float32 it runs every matmul and convolution at ``highest``
precision.  With ``dtype=bfloat16`` and no precision it is the control:
the same run one precision step below what the configuration states,
with the weights and every floating input in bfloat16 (token ids and
labels stay integers).  Clients run in chunks under ``vmap`` and the
test set in blocks, so the reference fits beside nothing else on one
chip: on the flat genome by a fixed formula, on the tensor genome by the
compiled programs' own memory analysis against the device's free memory.
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
GENOMES = ("flat", "tensor")


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
    genome: str = "flat"
    genome_scale: float = 0.05

    def __post_init__(self):
        if self.genome not in GENOMES:
            raise ValueError(f"genome {self.genome!r} is not one of "
                             f"{GENOMES}")

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


def _rows(data: dict) -> int:
    return data[min(data)].shape[0]


class Reference:
    """``fault`` plants a fault for the control tests: ``"half_batch"``
    trains on the first half of every batch (the leading batch axis of
    every array)."""

    def __init__(self, model, cfg: dict, proto: Protocol, clients: List[dict],
                 test: dict, server_seed: int, dtype=jnp.float32,
                 precision: Optional[str] = "highest",
                 fault: Optional[str] = None, chunk_bytes: float = 4e9):
        self.model, self.cfg, self.proto = model, cfg, proto
        self.dtype, self.precision, self.fault = dtype, precision, fault
        self.server_seed = int(server_seed)
        self.n = len(clients)
        nb = [_rows(c) for c in clients]
        self.nb_max = max(nb)
        self.n_valid = jnp.asarray(nb, jnp.int32)

        def pad(a):
            out = np.zeros((self.nb_max,) + a.shape[1:], a.dtype)
            out[:a.shape[0]] = a
            return out
        self.data = {k: self._cast(np.stack([pad(c[k]) for c in clients]))
                     for k in clients[0]}
        self.test = {k: self._cast(v) for k, v in test.items()}
        self.n_test = _rows(self.test)
        self._chunk_fn = jax.jit(self._chunk_update)
        self._eval_block = jax.jit(self._eval_block_fn,
                                   static_argnames="block")
        self._fit_one = jax.jit(self._fitness_of)
        if proto.genome == "flat":
            self.eval_block = math.gcd(self.n_test, EVAL_BLOCK)
            d = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(
                self._param_shapes()))
            # a client's BWO step holds about a dozen (pop, D) float32
            # arrays
            per_client = max(proto.mh_pop, 1) * d * 4 * 12
            self.chunk = max(1, min(self.n, int(chunk_bytes // per_client)))
        else:
            # a round holds the global weights and the adopted ones
            # beside its programs
            budget = self._free_bytes(chunk_bytes) - 2 * sum(
                l.size * l.dtype.itemsize
                for l in jax.tree.leaves(self._param_shapes()))
            self.chunk = self._fit_count(self._lower_chunk, budget,
                                         range(1, self.n + 1))
            self.eval_block = self._fit_count(
                self._lower_eval, budget,
                [b for b in range(1, self.n_test + 1) if self.n_test % b == 0])

    def _cast(self, a):
        """Floating inputs in the reference's dtype; ids and labels as
        they are."""
        if np.issubdtype(np.asarray(a).dtype, np.floating):
            return jnp.asarray(a, self.dtype)
        return jnp.asarray(a)

    # ------------------------------------------------------- sizing --
    def _param_shapes(self):
        return jax.eval_shape(lambda k: self.model.init(k, self.cfg,
                                                        self.dtype),
                              jax.random.PRNGKey(0))

    @staticmethod
    def _free_bytes(fallback: float) -> float:
        """Nine tenths of the device's free memory, where the backend
        reports it; else ``fallback``."""
        stats = jax.devices()[0].memory_stats() or {}
        if "bytes_limit" not in stats:
            return fallback
        return 0.9 * (stats["bytes_limit"] - stats.get("bytes_in_use", 0))

    def _fit_count(self, lower, budget: float, counts) -> int:
        """The largest of ``counts`` (ascending, from 1) whose program
        fits ``budget``, estimated from the program at count 1: its
        arguments once, its outputs and scratch once per count.  At
        least 1."""
        with self._ctx():
            m = lower(1).compile().memory_analysis()
        per = m.output_size_in_bytes + m.temp_size_in_bytes
        fits = int((budget - m.argument_size_in_bytes) // max(per, 1))
        return max([c for c in counts if c <= fits] or [1])

    def _lower_chunk(self, count: int):
        ids = jax.ShapeDtypeStruct((count,), jnp.int32)
        key = jax.random.PRNGKey(0)
        keys = jax.ShapeDtypeStruct((count,) + key.shape, key.dtype)
        return self._chunk_fn.lower(self._param_shapes(), self.data,
                                    self.n_valid, ids, keys)

    def _lower_eval(self, block: int):
        return self._eval_block.lower(self._param_shapes(), self.test,
                                      jnp.int32(0), block=block)

    # ----------------------------------------------------------- model --
    def _ctx(self):
        if self.precision is None:
            return contextlib.nullcontext()
        return jax.default_matmul_precision(self.precision)

    def loss(self, params, batch, dropout_key=None):
        if hasattr(self.model, "loss"):
            return self.model.loss(params, batch, self.cfg, dropout_key)
        labels = batch["labels"]
        logits = self.model.logits(params, batch["images"], self.cfg,
                                   dropout_key)
        logp = jax.nn.log_softmax(logits)
        nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1).mean()
        acc = (logits.argmax(-1) == labels).mean()
        return nll, acc

    def init_params(self):
        _, pkey = jax.random.split(jax.random.PRNGKey(self.server_seed))
        with self._ctx():
            return self.model.init(pkey, self.cfg, self.dtype)

    # ---------------------------------------------------------- client --
    def _local_sgd(self, params, data, n_valid, key):
        lr = self.proto.lr
        valid = jnp.arange(_rows(data)) < n_valid
        half = self.fault == "half_batch"

        def step(carry, xs):
            p, r = carry
            batch, v = xs
            r2, dkey = jax.random.split(r)
            if half:
                batch = jax.tree.map(lambda a: a[:a.shape[0] // 2], batch)
            g = jax.grad(lambda q: self.loss(q, batch, dkey)[0])(p)
            new = jax.tree.map(lambda a, b: a - lr * b.astype(a.dtype), p, g)
            p = jax.tree.map(lambda n, o: jnp.where(v, n, o), new, p)
            return (p, jnp.where(v, r2, r)), None

        rng = key
        for _ in range(self.proto.local_epochs):
            rng, ekey = jax.random.split(rng)
            (params, _), _ = jax.lax.scan(step, (params, ekey),
                                          (data, valid))
        return params

    def _fitness_rows(self, data, n_valid):
        f = self.proto.fitness_batches
        idx = jnp.minimum(jnp.arange(f), jnp.maximum(n_valid - 1, 0))
        return jax.tree.map(lambda a: a[idx], data)

    def _mean_loss(self, params, rows):
        """Mean loss over the fitness batches ``rows``."""
        return jnp.stack([
            self.loss(params, jax.tree.map(lambda a: a[i], rows))[0]
            for i in range(_rows(rows))]).mean()

    def _fitness_of(self, params, data, n_valid):
        return self._mean_loss(params, self._fitness_rows(data, n_valid))

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

    def _genome(self, trained):
        """``(x0, decode)``: the protocol's genome of the trained
        weights and its map back to weights."""
        if self.proto.genome == "flat":
            return ravel_pytree(trained)
        leaves, treedef = jax.tree_util.tree_flatten(trained)
        scale = self.proto.genome_scale

        def decode(z):
            return jax.tree_util.tree_unflatten(
                treedef, [leaf * (1 + scale * (z[i] - 1))
                          for i, leaf in enumerate(leaves)])
        return jnp.ones((len(leaves),), self.dtype), decode

    def _client(self, params, data, n_valid, key):
        r_sgd, r_mh = jax.random.split(key)
        trained = self._local_sgd(params, data, n_valid, r_sgd)
        x0, decode = self._genome(trained)
        rows = self._fitness_rows(data, n_valid)

        def fit_fn(pop):
            return jax.lax.map(lambda x: self._mean_loss(decode(x), rows),
                               pop)

        if not self.proto.is_fedx:
            return fit_fn(x0[None])[0], trained
        score, best = self._bwo(x0, fit_fn, r_mh)
        return score, decode(best)

    def _chunk_update(self, params, data, n_valid, ids, keys):
        """The clients ``ids`` of the stacked data, with their keys."""
        data = jax.tree.map(lambda a: jnp.take(a, ids, axis=0), data)
        n_valid = jnp.take(n_valid, ids, axis=0)
        return jax.vmap(self._client, in_axes=(None, 0, 0, 0))(
            params, data, n_valid, keys)

    def _width(self, n_ids: int) -> int:
        """Clients a chunk runs when ``n_ids`` clients are run in equal
        chunks of at most ``self.chunk``."""
        c = min(self.chunk, n_ids)
        return math.ceil(n_ids / math.ceil(n_ids / c))

    def _run_clients(self, params, ids, keys):
        """Runs clients ``ids`` in equal chunks (the last chunk repeats
        its last client); returns per-client scores and, reduced as the
        chunks come, the weights the server adopts from them: under FedX
        the first lowest score's, under FedAvg the sum over ``ids``.  So
        no more than one chunk's weights are held at a time."""
        ids = np.asarray(ids)
        c = self._width(len(ids))
        scores, adopted = [], None
        for j in range(math.ceil(len(ids) / c)):
            part = ids[j * c:(j + 1) * c]
            take = np.concatenate([part, np.repeat(part[-1:], c - len(part))])
            with self._ctx():
                take = jnp.asarray(take, jnp.int32)
                s, p = self._chunk_fn(params, self.data, self.n_valid, take,
                                      jnp.take(keys, take, axis=0))
            s = np.asarray(jax.device_get(s), np.float64)[:len(part)]
            first = len(scores)
            scores.extend(s.tolist())
            if self.proto.is_fedx:
                best = int(np.argmin(scores))
                if best >= first:
                    adopted = jax.tree.map(lambda a: a[best - first], p)
                continue
            for i in range(len(part)):
                leaf = jax.tree.map(lambda a: a[i], p)
                adopted = leaf if adopted is None else jax.tree.map(
                    jnp.add, adopted, leaf)
        return np.asarray(scores), adopted

    def _round_keys(self, rng):
        """``(next rng, FedAvg's selection key, the clients' keys)``."""
        keys = jax.random.split(rng, self.n + 2)
        return keys[0], keys[1], keys[2:]

    def first_round(self, client: int):
        """Client ``client``'s weights after the first round, from the
        initial weights: local SGD and, under FedX, the best member of
        its BWO, on the chunk program the round runs."""
        rng, _ = jax.random.split(jax.random.PRNGKey(self.server_seed))
        _, _, ckeys = self._round_keys(rng)
        ids = np.full(self._width(self.n), client)
        return _host(self._run_clients(self.init_params(), ids, ckeys)[1])

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
            rng, sel_key, ckeys = self._round_keys(rng)
            log: Dict[str, Any] = {}
            if self.proto.is_fedx:
                scores, params = self._run_clients(params, np.arange(self.n),
                                                   ckeys)
                log.update(scores=scores, best=int(np.argmin(scores)))
            else:
                sel = np.asarray(jax.random.choice(sel_key, self.n, (m,),
                                                   replace=False))
                scores, total = self._run_clients(params, sel, ckeys)
                params = jax.tree.map(lambda a: a / m, total)
                log.update(scores=scores, participants=sel.tolist())
            log["eval_loss"], log["eval_acc"] = self.evaluate(params)
            rec.logs.append(log)
            if r + 1 in keep_after:
                rec.snapshots[r + 1] = _host(params)
        return rec

    # ----------------------------------------------------- evaluation --
    def _eval_block_fn(self, params, data, start, block):
        x = jax.tree.map(
            lambda a: jax.lax.dynamic_slice_in_dim(a, start, block), data)
        nll, acc = self.loss(params, x)
        w = (self.model.count(x, self.cfg) if hasattr(self.model, "count")
             else block)
        return nll.astype(jnp.float32) * w, acc.astype(jnp.float32) * w, w

    def evaluate(self, params):
        """Mean test loss and accuracy of ``params`` (host or device
        weights, cast to the reference's dtype)."""
        params = jax.tree.map(lambda a: jnp.asarray(a, self.dtype), params)
        nll = acc = n = 0.0
        with self._ctx():
            for s in range(0, self.n_test, self.eval_block):
                a, b, w = self._eval_block(params, self.test, jnp.int32(s),
                                           block=self.eval_block)
                nll, acc, n = nll + float(a), acc + float(b), n + float(w)
        return nll / n, acc / n

    def fitness(self, params, client: int) -> float:
        """The fitness (score) of ``params`` on client ``client``."""
        params = jax.tree.map(lambda a: jnp.asarray(a, self.dtype), params)
        with self._ctx():
            return float(self._fit_one(
                params, jax.tree.map(lambda a: a[client], self.data),
                self.n_valid[client]))
