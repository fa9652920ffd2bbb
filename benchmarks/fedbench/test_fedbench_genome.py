"""The reference's tensor genome is the program's ``ClientHP(subspace=
True)``: the same key schedule, the same leaves in the same order, the
same decode.  The 2NN at reduced widths (8x8x3 input, 16 hidden), 3
clients, 2 rounds, on the CPU."""
import jax
import numpy as np
import pytest

from fedbench import check, gen, harness, spec
from fedbench.reference import Protocol, Reference
from repro.core.api import FLConfig, build_experiment
from repro.core.client import ClientHP
from repro.core.protocol import StopConditions, run_federated
from repro.data.synthetic import mlp_task

CFG = {"hidden": 16, "image_size": 8, "channels": 3, "num_classes": 10}
CLIENTS, ROUNDS, SCALE = 3, 2, 0.05
HP = dict(local_epochs=1, lr=0.05, mh_pop=4, mh_generations=2)
# Both sides compute in float32 on the CPU, where a matmul at the default
# precision is a float32 matmul; they differ only in how XLA orders and
# fuses the loss's sums, 1e-7 of a score (9.1e-8 read).  A wrong key, leaf
# order or decode moves a score by BWO's own spread, 1e-3 or more.
RTOL = 1e-5


def proto(**kw) -> Protocol:
    bwo = spec.workload("mlp2nn_fedbwo_noniid")["protocol"]["bwo"]
    return Protocol("fedbwo", fitness_batches=2, bwo=bwo,
                    **dict(dict(HP, genome="tensor", genome_scale=SCALE),
                           **kw))


@pytest.fixture(scope="module")
def runs():
    data = gen.make_dataset(gen.Traffic(
        n_train=150, n_test=50, n_clients=CLIENTS, batch_size=10,
        image_size=8, partition="iid"), 2**33 + 3)
    cfg = FLConfig(strategy="fedbwo", task="mlp", n_clients=CLIENTS,
                   batch_size=10, server_seed=data.server_seed, tau=2.0,
                   **HP)
    exp = build_experiment(
        cfg, task=mlp_task(hidden=CFG["hidden"],
                           image_size=CFG["image_size"]),
        client_data=[jax.device_put(c) for c in data.clients],
        eval_data=jax.device_put(data.test),
        hp=ClientHP(fitness_batches=2, subspace=True, subspace_scale=SCALE,
                    **HP))
    server = exp.server
    logs, weights = [], []
    for _ in range(ROUNDS):
        out = run_federated(server, exp.eval_data, StopConditions(
            max_rounds=1, patience=2, tau=2.0), eval_every=1)
        logs += [harness.round_log(rl) for rl in out]
        weights.append(harness.host(server.global_params))

    def reference(**kw):
        return Reference(spec.model("fedavg_2nn"), CFG, proto(**kw),
                         data.clients, data.test, data.server_seed).run(
            ROUNDS, keep_after=list(range(1, ROUNDS + 1)))
    return server.meter, logs, weights, reference


def test_same_winner_and_bytes(runs):
    meter, logs, _, reference = runs
    ref = reference()
    assert [l["best"] for l in logs] == [l["best"] for l in ref.logs]
    assert check.bytes_rounds_off(meter.uplink, meter.downlink, True,
                                  CLIENTS, CLIENTS, meter.model_bytes,
                                  ROUNDS) == 0


def test_scores_and_adopted_weights_agree(runs):
    _, logs, weights, reference = runs
    ref = reference()
    for got, want in zip(logs, ref.logs):
        np.testing.assert_allclose(got["scores"], want["scores"], rtol=RTOL)
    for r, w in enumerate(weights, start=1):
        for a, b in zip(jax.tree.leaves(w),
                        jax.tree.leaves(ref.snapshots[r])):
            np.testing.assert_allclose(a, b, rtol=RTOL, atol=1e-7)


@pytest.mark.parametrize("other", [dict(genome_scale=2 * SCALE),
                                   dict(genome="flat")])
def test_another_genome_reads_apart(runs, other):
    """The winners' genomes moved: the reference with another decode
    adopts other weights."""
    _, _, weights, reference = runs
    ref = reference(**other)
    gaps = [np.max(np.abs(a - b)) / np.max(np.abs(b)) for a, b in zip(
        jax.tree.leaves(weights[-1]),
        jax.tree.leaves(ref.snapshots[ROUNDS]))]
    assert max(gaps) > 100 * RTOL
