"""The model-FLOP functions: MACs per image from the configurations'
sizes, parameter counts of the reference models, and the per-round
count."""
import math

import jax
import numpy as np
import pytest

from fedbench import flops, spec
from fedbench.reference import Protocol


@pytest.mark.parametrize("name,macs", [("paper_cnn", 36_803_584),
                                       ("fedavg_2nn", 656_400)])
def test_forward_macs(name, macs):
    assert spec.model(name).forward_macs(spec.config(name)) == macs


@pytest.mark.parametrize("name", ["paper_cnn", "fedavg_2nn"])
def test_reference_parameter_count_matches_config(name):
    cfg = spec.config(name)
    shapes = jax.eval_shape(lambda k: spec.model(name).init(k, cfg),
                            jax.random.PRNGKey(0))
    assert sum(int(np.prod(l.shape)) for l in jax.tree.leaves(shapes)) \
        == cfg["params"]


def test_rounds_flops_fedbwo_and_fedavg():
    fwd = 100.0
    bwo = Protocol("fedbwo", local_epochs=2, lr=0.1, mh_pop=6,
                   mh_generations=3, fitness_batches=2)
    logs = [{"eval_loss": 0.5}, {"eval_loss": math.nan}]
    # per client: 3 * 2 epochs * nb * 10 images + 6 * 2 * 10 * (1 + 3)
    want = sum(fwd * (60 * nb + 480) for nb in (5, 7)) * 2 + fwd * 1000
    assert flops.rounds_flops(fwd, bwo, [5, 7], 10, logs, 1000) == want

    avg = Protocol("fedavg", local_epochs=1, lr=0.1, mh_pop=1,
                   mh_generations=0, fitness_batches=2)
    logs = [{"eval_loss": 0.5, "participants": [1]}]
    want = fwd * (3 * 7 * 10 + 2 * 10) + fwd * 1000
    assert flops.rounds_flops(fwd, avg, [5, 7], 10, logs, 1000) == want


def test_paper_round_flops():
    """The paper cell's round: 23.17 TFLOP of model work."""
    cell = spec.workload("cnn_fedbwo_paper")
    cfg = spec.config(cell["config"])
    fwd = 2 * spec.model("paper_cnn").forward_macs(cfg)
    proto = Protocol("fedbwo", local_epochs=2, lr=0.0025, mh_pop=6,
                     mh_generations=3, fitness_batches=2)
    total = flops.rounds_flops(fwd, proto, [500] * 10, 10,
                               [{"eval_loss": 1.0}], 10_000)
    assert total == pytest.approx(23.17e12, rel=1e-3)
