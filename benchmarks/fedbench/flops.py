"""Model FLOPs of federated rounds, counted from shapes.

A forward pass of one example (an image, or a sequence of tokens)
costs ``2 * forward_macs`` FLOPs, from the configuration's reference
module; where a layer has experts, ``forward_macs`` counts the ones a
token is routed to.  Per round and participating client:

* local SGD: 3 forward-equivalents per valid example-step
  (``local_epochs * valid_batches * batch`` example-steps);
* fitness: one forward per example scored, ``mh_pop * fitness_batches *
  batch * (1 + mh_generations)`` for FedX, ``fitness_batches * batch``
  (the one score of the trained weights) for FedAvg;

and one forward per test example on each evaluated round.  Padded
batches, recomputation and BWO's elementwise arithmetic do not count.
"""
from __future__ import annotations

import math
from typing import Sequence


def client_flops(fwd: float, proto, valid_batches: int, batch: int) -> float:
    sgd = 3 * proto.local_epochs * valid_batches * batch
    if proto.is_fedx:
        fit = (proto.mh_pop * proto.fitness_batches * batch
               * (1 + proto.mh_generations))
    else:
        fit = proto.fitness_batches * batch
    return fwd * (sgd + fit)


def rounds_flops(fwd: float, proto, valid_batches: Sequence[int], batch: int,
                 logs: Sequence[dict], n_test: int) -> float:
    """FLOPs of the rounds in ``logs`` (their participants and evals)."""
    per_client = [client_flops(fwd, proto, nb, batch) for nb in valid_batches]
    total = 0.0
    for log in logs:
        who = (range(len(valid_batches)) if proto.is_fedx
               else log["participants"])
        total += sum(per_client[k] for k in who)
        if not math.isnan(log["eval_loss"]):
            total += fwd * n_test
    return total
