import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.client import Task


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)


def make_toy_task(d: int = 8, classes: int = 3) -> Task:
    """Fast logistic-regression task for FL behaviour tests."""
    def init_params(rng):
        k1, _ = jax.random.split(rng)
        return {"w": jax.random.normal(k1, (d, classes)) * 0.1,
                "b": jnp.zeros((classes,))}

    def loss_fn(params, batch):
        logits = batch["x"] @ params["w"] + params["b"]
        lp = jax.nn.log_softmax(logits)
        nll = -jnp.take_along_axis(lp, batch["y"][:, None], -1).mean()
        acc = (logits.argmax(-1) == batch["y"]).mean()
        return nll, acc

    return Task(init_params, loss_fn)


def make_toy_data(rng, n: int, d: int = 8, classes: int = 3,
                  w_seed: int = 123):
    """Linearly separable synthetic classification data.  The labelling
    weights come from ``w_seed`` (not ``rng``) so separately drawn
    train/test splits share the same ground truth."""
    w_true = jax.random.normal(jax.random.PRNGKey(w_seed), (d, classes))
    x = jax.random.normal(rng, (n, d))
    y = (x @ w_true).argmax(-1).astype(jnp.int32)
    return {"x": x, "y": y}


class ReferenceRounds:
    """Test-only reference for ``Server.run_round``: one round as a loop
    over the clients, one jitted client update each, on the unstacked
    client data.  It starts from the server's initial weights and key
    and splits the key as the server's block does, ``split(rng,
    n_clients + 2) -> (rng, sel_key, client_keys)``; FedAvg draws its
    participants with the same ``jax.random.choice`` on ``sel_key`` and
    averages their weights with ``jnp.mean``.  The byte ledger is a
    ``CommMeter`` of its own."""

    def __init__(self, task, strategy, hp, client_data, rng):
        from repro.core.client import make_client_update
        from repro.core.comm import CommMeter
        self.strategy = strategy
        self.client_data = list(client_data)
        self.n_clients = len(self.client_data)
        self.rng, pkey = jax.random.split(rng)
        self.global_params = task.init_params(pkey)
        self.meter = CommMeter(
            model_bytes=sum(l.size * l.dtype.itemsize
                            for l in jax.tree.leaves(self.global_params)),
            n_clients=self.n_clients)
        self._update = jax.jit(make_client_update(task, hp, strategy.mh))

    def run_round(self) -> dict:
        n = self.n_clients
        keys = jax.random.split(self.rng, n + 2)
        self.rng, sel_key, ckeys = keys[0], keys[1], keys[2:]
        if self.strategy.is_fedx:
            sel = list(range(n))
        else:
            m = max(int(self.strategy.client_ratio * n), 1)
            sel = jax.random.choice(sel_key, n, (m,), replace=False).tolist()
        outs = [self._update(self.global_params, self.client_data[k],
                             ckeys[k]) for k in sel]
        scores, slots = jax.device_get(
            (jnp.stack([o[0] for o in outs]), [s for o in outs
                                               for s in o[2:]]))
        scores = np.asarray(scores)
        if slots:
            self.meter.record_expert_slots(sum(slots))
        if self.strategy.is_fedx:
            # GetBestModel: the winner's weights only
            best = int(scores.argmin())
            self.global_params = outs[best][1]
            self.meter.record_fedx_round(fetched_model=True)
            return {"best_client": best, "score": float(scores[best]),
                    "scores": [float(s) for s in scores]}
        self.global_params = jax.tree.map(
            lambda *xs: jnp.mean(jnp.stack(xs), 0), *[o[1] for o in outs])
        self.meter.record_fedavg_round(len(sel))
        return {"participants": sel, "scores": [float(s) for s in scores]}
