"""Names of the FL round's device layers in a profiler trace.

The round programs open one ``jax.named_scope`` per layer.  A scope is
op metadata only: it puts its name on the ``op_name`` path of every HLO
operation traced inside it (``jit(block_fn)/vmap(fl.local_sgd)/...``)
and changes no operation, so a profile of the device can be split by
layer while the outputs stay bit-identical.  Scopes nest; an operation
belongs to the innermost ``fl.*`` name on its path (fitness passes run
inside an evolution step).

The model's own layers carry names of their own (``MODEL_SCOPES``,
defined in the leaf module :mod:`repro.scopes` and re-exported here),
kept apart from ``SCOPES`` so that the FL layers' accounting does not
move.  They nest inside the FL layer that runs the model
(``fl.local_sgd``, ``fl.bwo_fitness``, ``fl.eval``).

The host side of a round is named by ``jax.profiler.TraceAnnotation``
spans in :class:`repro.core.server.Server` (``Server.run_round``,
``Server.run_round.sync``, ``Server.evaluate``, ``Server.evaluate.sync``,
``Server.dispatch_block``, ``Server.finish_block``,
``Server.finish_block.sync``, ``Server.finish_block.process``), on the
profiler's clock.  Both cost nothing measurable while no profiler runs.
"""
from __future__ import annotations

from repro.scopes import (DENSE_FFN, LM_HEAD, MLA,  # noqa: F401
                          MODEL_SCOPES, MOE_EXPERTS, MOE_ROUTE)

LOCAL_SGD = "fl.local_sgd"            # client local SGD epochs
BWO_FITNESS = "fl.bwo_fitness"        # population fitness passes
BWO_EVOLVE = "fl.bwo_evolve"          # population draw, generations, pick
SERVER_REDUCE = "fl.server_reduce"    # FedX winner / FedAvg mean
EVAL = "fl.eval"                      # global model on the test set

SCOPES = (LOCAL_SGD, BWO_FITNESS, BWO_EVOLVE, SERVER_REDUCE, EVAL)
