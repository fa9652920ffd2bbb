"""Pad+mask batching on Dirichlet (ragged) partitions and the FedAvg
participant-count compile-cache policy (DESIGN.md §5).

Parity with the unpadded per-client loop (``conftest.ReferenceRounds``)
holds because the masked client update skips padded batches' SGD steps
AND holds the PRNG carry, so the per-batch key sequence matches the
unpadded run, and the fitness slice replicates the unpadded
clamp-indexing semantics for short clients.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ClientHP, Server, Task, get_strategy
from repro.data.loader import batch_dataset
from repro.data.partition import partition_dirichlet

from conftest import ReferenceRounds, make_toy_data

N_CLIENTS = 4
CLASSES = 3


def _labeled_toy_task(d: int = 8) -> Task:
    """conftest's toy task, with the label key partition_dirichlet
    expects ("labels", not "y")."""
    def init_params(rng):
        k1, _ = jax.random.split(rng)
        return {"w": jax.random.normal(k1, (d, CLASSES)) * 0.1,
                "b": jnp.zeros((CLASSES,))}

    def loss_fn(params, batch):
        logits = batch["x"] @ params["w"] + params["b"]
        lp = jax.nn.log_softmax(logits)
        nll = -jnp.take_along_axis(lp, batch["labels"][:, None], -1).mean()
        acc = (logits.argmax(-1) == batch["labels"]).mean()
        return nll, acc

    return Task(init_params, loss_fn)


def _dirichlet_clients(n: int = 480, batch: int = 8):
    raw = make_toy_data(jax.random.PRNGKey(0), n, classes=CLASSES)
    data = {"x": raw["x"], "labels": raw["y"]}
    parts = partition_dirichlet(jax.random.PRNGKey(5), data, N_CLIENTS,
                                alpha=0.5, num_classes=CLASSES)
    return [batch_dataset(p, batch) for p in parts]


def _servers(strategy, clients, **kw):
    hp = ClientHP(local_epochs=1, mh_pop=4, mh_generations=2, lr=0.05,
                  fitness_batches=2)
    strat = get_strategy(strategy, **kw)
    return {"reference": ReferenceRounds(_labeled_toy_task(), strat, hp,
                                         clients, jax.random.PRNGKey(3)),
            "batched": Server(_labeled_toy_task(), strat, hp, clients,
                              jax.random.PRNGKey(3))}


@pytest.mark.parametrize("strategy,kw", [("fedbwo", {}),
                                         ("fedavg", {}),
                                         ("fedavg", {"client_ratio": 0.5})])
def test_dirichlet_parity(strategy, kw):
    """Identical winners/scores/participants, CommMeter bytes, and
    global weights between the masked round program and the per-client
    reference loop on a label-skewed (ragged) partition."""
    clients = _dirichlet_clients()
    lens = [jax.tree.leaves(c)[0].shape[0] for c in clients]
    assert len(set(lens)) > 1, f"partition not ragged: {lens}"
    servers = _servers(strategy, clients, **kw)
    assert servers["batched"].engine == "batched"
    assert servers["batched"]._engine.padded
    infos = {e: [s.run_round() for _ in range(2)]
             for e, s in servers.items()}
    ref, bat = servers["reference"], servers["batched"]
    assert ref.meter.uplink == bat.meter.uplink
    assert ref.meter.downlink == bat.meter.downlink
    assert ref.meter.summary() == bat.meter.summary()
    for a, b in zip(infos["reference"], infos["batched"]):
        if strategy == "fedbwo":
            assert a["best_client"] == b["best_client"]
            np.testing.assert_allclose(a["scores"], b["scores"], rtol=1e-4)
        else:
            assert a["participants"] == b["participants"]
    for x, y in zip(jax.tree.leaves(ref.global_params),
                    jax.tree.leaves(bat.global_params)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=1e-4, atol=1e-5)


def test_fedavg_sample_then_stack_compiles_for_m():
    """FedAvg at C=0.5 must trace/compile the round program exactly once,
    for the participant count m — never for the full n_clients."""
    raw = make_toy_data(jax.random.PRNGKey(0), 480, classes=CLASSES)
    # uniform IID shards so the only shape in play is the client axis
    per = 480 // 6
    clients = [batch_dataset(
        {"x": raw["x"][k * per:(k + 1) * per],
         "labels": raw["y"][k * per:(k + 1) * per]}, 8) for k in range(6)]
    hp = ClientHP(local_epochs=1, mh_pop=2, mh_generations=1, lr=0.05)
    server = Server(_labeled_toy_task(), get_strategy(
        "fedavg", client_ratio=0.5), hp, clients,
        jax.random.PRNGKey(3))
    eng = server._engine
    assert eng.n_participants == 3 and eng.n_clients == 6
    for _ in range(3):
        server.run_round()
    # one cached executable, shaped (m, ...), reused across rounds
    assert eng.traced_participant_counts == [3]


def test_zero_pad_rows_never_change_scores():
    """Padding one client far beyond its data must not perturb its
    score: mask out everything past the real batches."""
    from repro.core.client import make_client_update

    task = _labeled_toy_task()
    raw = make_toy_data(jax.random.PRNGKey(0), 64, classes=CLASSES)
    data = batch_dataset({"x": raw["x"], "labels": raw["y"]}, 8)  # 8 batches
    hp = ClientHP(local_epochs=2, mh_pop=3, mh_generations=2, lr=0.05,
                  fitness_batches=2)
    params = task.init_params(jax.random.PRNGKey(9))
    rng = jax.random.PRNGKey(3)

    from repro.metaheuristics import bwo
    plain = jax.jit(make_client_update(task, hp, bwo()))
    masked = jax.jit(make_client_update(task, hp, bwo(), masked=True))

    score0, params0 = plain(params, data, rng)
    padded = jax.tree.map(
        lambda a: jnp.concatenate([a, jnp.zeros((5,) + a.shape[1:],
                                                a.dtype)]), data)
    mask = jnp.arange(13) < 8
    score1, params1 = masked(params, padded, mask, rng)
    np.testing.assert_allclose(float(score0), float(score1), rtol=1e-6)
    for x, y in zip(jax.tree.leaves(params0), jax.tree.leaves(params1)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-6)
