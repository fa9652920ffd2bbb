"""The paper's primary contribution: the FedBWO communication-efficient
FL protocol (score-only uplink + best-client weight fetch) and its
FedAvg/FedPSO/FedGWO/FedSCA baselines.

``FLConfig`` -> ``build_experiment()`` -> ``run()`` (repro.core.api) is
the one construction path for experiments; the lower-level pieces
(``Server``, ``ClientHP``, the round engine) remain directly usable.
"""
from repro.core.client import ClientHP, Task, make_client_update
from repro.core.comm import (BlockTiming, CommMeter, fedavg_total,
                             fedx_total, normalized_cost, SCORE_BYTES)
from repro.core.engine import (BatchedRoundEngine, make_fused_rounds,
                               pipeline_blocks, resolve_vectorize,
                               stack_clients)
from repro.core.knobs import (AUDIT_MODES, DEFAULT_PIPELINE_DEPTH,
                              DEFAULT_ROUNDS_PER_DISPATCH, VECTORIZE_MODES,
                              parse_audit, parse_rounds_per_dispatch,
                              parse_vectorize, validate_audit,
                              validate_rounds_per_dispatch,
                              validate_vectorize)
from repro.core.protocol import RoundLog, StopConditions, run_federated
from repro.core.server import (PendingBlock, PipelineResult, Server,
                               Strategy, get_strategy)
from repro.core.api import (Experiment, ExperimentResult, FLConfig,
                            build_experiment)
# the error the opt-in flcheck hook (build_experiment(..., audit=True))
# raises; re-exported so callers need not import repro.analysis directly
from repro.analysis.report import AuditError

__all__ = ["ClientHP", "Task", "make_client_update", "BlockTiming",
           "CommMeter",
           "fedavg_total", "fedx_total", "normalized_cost", "SCORE_BYTES",
           "BatchedRoundEngine", "make_fused_rounds",
           "pipeline_blocks", "resolve_vectorize", "stack_clients",
           "DEFAULT_PIPELINE_DEPTH", "DEFAULT_ROUNDS_PER_DISPATCH",
           "AUDIT_MODES", "VECTORIZE_MODES",
           "parse_audit", "parse_rounds_per_dispatch",
           "parse_vectorize", "validate_audit",
           "validate_rounds_per_dispatch", "validate_vectorize",
           "RoundLog", "StopConditions", "run_federated",
           "PendingBlock", "PipelineResult", "Server", "Strategy",
           "get_strategy",
           "Experiment", "ExperimentResult", "FLConfig", "build_experiment",
           "AuditError"]
