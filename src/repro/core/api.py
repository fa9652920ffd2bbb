"""Unified experiment facade: ``FLConfig`` -> ``build_experiment()`` ->
``run()``.

One construction path shared by the CLI driver
(``repro.launch.fl_train``), the quickstart example, and the
paper-figure benchmarks (``benchmarks/fl_bench.py``): dataset synthesis,
partitioning (IID or Dirichlet), client batching, ``Server`` wiring, and
the paper's stopping conditions all hang off a single dataclass instead
of being re-derived at every call site.

    cfg = FLConfig(strategy="fedbwo", n_clients=10, partition="dirichlet")
    result = build_experiment(cfg).run(verbose=True)
    print(result.summary())

``build_experiment`` accepts ``task`` / ``client_data`` / ``eval_data``
/ ``hp`` overrides so benchmarks can reuse one synthesized dataset (or a
custom task) across many configs while keeping the rest of the wiring.
"""
from __future__ import annotations

import dataclasses
from typing import Any, List, Optional

import jax

from repro.core.client import ClientHP, Task
from repro.core.comm import fedavg_total, normalized_cost
from repro.core.knobs import (parse_audit, validate_engine,
                              validate_pipeline_blocks,
                              validate_rounds_per_dispatch,
                              validate_vectorize)
from repro.core.protocol import RoundLog, StopConditions, run_federated
from repro.core.server import Server, get_strategy
from repro.metaheuristics import REGISTRY

TASKS = ("cnn", "mlp", "lm")
PARTITIONS = ("iid", "dirichlet")
GENOMES = ("flat", "tensor")
# packed sequence length of the token data build_experiment makes itself
LM_SEQ_LEN = 32


def strategy_names() -> tuple:
    """fedavg plus one fedX per registered meta-heuristic."""
    return ("fedavg",) + tuple(sorted("fed" + k for k in REGISTRY))


@dataclasses.dataclass(frozen=True)
class FLConfig:
    """Everything needed to reproduce one federated run.

    Defaults follow the paper's §IV-A setup (batch 10, lr 0.0025,
    tau 0.70); knob vocabularies are validated once, at construction,
    through ``repro.core.knobs``.
    """
    strategy: str = "fedbwo"
    # "cnn" (paper) | "mlp" (FedAvg 2NN) | "lm" (a language model of the
    # registry, ``arch``: next-token loss over packed documents)
    task: str = "cnn"
    arch: Optional[str] = None      # repro.configs.ARCHS name (task "lm")
    n_clients: int = 10
    client_ratio: float = 1.0       # C — FedAvg participation ratio
    partition: str = "iid"          # "iid" | "dirichlet"
    dirichlet_alpha: float = 0.5
    n_train: int = 1000
    n_test: int = 300
    batch_size: int = 10            # paper §IV-A
    local_epochs: int = 2
    lr: float = 0.0025              # paper §IV-A
    mh_pop: int = 6
    mh_generations: int = 3
    fitness_batches: int = 2        # batches a fitness score is over
    # the meta-heuristic's genome: "flat", the weight vector (the
    # paper's), or "tensor", one gain per weight tensor decoded as
    # w * (1 + genome_scale * (z - 1)) (ClientHP.subspace)
    genome: str = "flat"
    genome_scale: float = 0.05
    engine: str = "auto"            # repro.core.knobs.ENGINES
    vectorize: str = "auto"         # knobs.VECTORIZE_MODES, opt. ":k"
    # rounds fused into one device dispatch ("auto" | int >= 1): R > 1
    # runs blocks of R rounds as one XLA program with one host sync per
    # block (DESIGN.md §6); "auto" = measured default on the batched
    # engine, 1 on the sequential fallback
    rounds_per_dispatch: Any = 1
    # double-buffer fused block dispatches against host-side log
    # processing ("auto" | "on" | "off" | bool, DESIGN.md §7): block
    # k+1 runs on device while block k's logs sync and the stopping
    # conditions are checked (one-block stopping overshoot, trimmed
    # from the logs); "auto" pipelines whenever there is a fused
    # batched block to overlap
    pipeline_blocks: Any = "auto"
    # evaluate the global model every k-th round; with fused blocks the
    # cadence runs on device, so skipped evals cost neither compute nor
    # a sync (block boundaries always evaluate)
    eval_every: int = 1
    max_rounds: int = 8
    patience: int = 5               # paper: t = 5
    tau: float = 0.70               # paper §IV-D
    data_seed: int = 42
    partition_seed: int = 1
    server_seed: int = 7

    def __post_init__(self):
        validate_engine(self.engine)
        validate_vectorize(self.vectorize)
        validate_rounds_per_dispatch(self.rounds_per_dispatch)
        validate_pipeline_blocks(self.pipeline_blocks)
        if self.eval_every < 1:
            raise ValueError(f"eval_every={self.eval_every} must be >= 1")
        if self.task not in TASKS:
            raise ValueError(f"task={self.task!r} not in {TASKS}")
        if (self.task == "lm") != (self.arch is not None):
            raise ValueError("task='lm' needs arch, and only it takes one")
        if self.task == "lm" and self.partition != "iid":
            raise ValueError("task='lm' has no labels to skew; its "
                             "skew is the data's own")
        if self.genome not in GENOMES:
            raise ValueError(f"genome={self.genome!r} not in {GENOMES}")
        if self.partition not in PARTITIONS:
            raise ValueError(
                f"partition={self.partition!r} not in {PARTITIONS}")
        if self.strategy not in strategy_names():
            raise ValueError(f"strategy={self.strategy!r} not in "
                             f"{strategy_names()}")
        if not 0.0 < self.client_ratio <= 1.0:
            raise ValueError(
                f"client_ratio={self.client_ratio} not in (0, 1]")

    def client_hp(self) -> ClientHP:
        return ClientHP(local_epochs=self.local_epochs, lr=self.lr,
                        mh_pop=self.mh_pop,
                        mh_generations=self.mh_generations,
                        fitness_batches=self.fitness_batches,
                        subspace=self.genome == "tensor",
                        subspace_scale=self.genome_scale,
                        vectorize=self.vectorize)

    def stop_conditions(self) -> StopConditions:
        return StopConditions(max_rounds=self.max_rounds,
                              patience=self.patience, tau=self.tau)


def build_experiment(cfg: FLConfig, *, task: Optional[Task] = None,
                     client_data: Optional[list] = None,
                     eval_data: Any = None,
                     hp: Optional[ClientHP] = None,
                     audit: Any = "off") -> "Experiment":
    """Materialize an :class:`Experiment` from a config: synthesize the
    dataset, partition and batch it across clients, and construct the
    ``Server`` (which picks the round engine per ``cfg.engine``).

    Any of ``task`` / ``client_data`` / ``eval_data`` / ``hp`` may be
    passed to override the config-derived default — benchmarks use this
    to share one dataset across strategy sweeps.

    ``audit`` opts the build into the flcheck static auditor
    (``repro.analysis``, knobs.AUDIT_MODES): ``"report"`` runs the rule
    catalogue over the engine-built round programs and prints the
    findings; ``"strict"`` (or ``audit=True``) additionally raises
    :class:`repro.analysis.AuditError` on any error-severity finding,
    so a contract regression fails the build before any round runs.
    """
    # local imports: repro.data modules import repro.core.client, so a
    # module-level import here would cycle through the package inits
    from repro.data.loader import client_batches
    from repro.data.partition import partition_dirichlet, partition_iid
    from repro.data.synthetic import (cnn_task, lm_task, make_cifar_like,
                                      make_packed_tokens, mlp_task)

    if task is None:
        if cfg.task == "lm":
            from repro.configs import get_arch
            task = lm_task(get_arch(cfg.arch))
        else:
            task = cnn_task() if cfg.task == "cnn" else mlp_task()
    if client_data is None or eval_data is None:
        if cfg.task == "lm":
            from repro.configs import get_arch
            train, test = make_packed_tokens(
                jax.random.PRNGKey(cfg.data_seed), cfg.n_train, cfg.n_test,
                LM_SEQ_LEN, get_arch(cfg.arch).vocab_size)
        else:
            train, test = make_cifar_like(jax.random.PRNGKey(cfg.data_seed),
                                          cfg.n_train, cfg.n_test)
        if eval_data is None:
            eval_data = test
        if client_data is None:
            pkey = jax.random.PRNGKey(cfg.partition_seed)
            if cfg.partition == "dirichlet":
                parts = partition_dirichlet(pkey, train, cfg.n_clients,
                                            alpha=cfg.dirichlet_alpha)
            else:
                parts = partition_iid(pkey, train, cfg.n_clients)
            client_data = client_batches(parts, cfg.batch_size)
    server = Server(task,
                    get_strategy(cfg.strategy,
                                 client_ratio=cfg.client_ratio),
                    hp if hp is not None else cfg.client_hp(),
                    client_data, jax.random.PRNGKey(cfg.server_seed),
                    engine=cfg.engine,
                    rounds_per_dispatch=cfg.rounds_per_dispatch,
                    pipeline_blocks=cfg.pipeline_blocks)
    experiment = Experiment(cfg=cfg, server=server, eval_data=eval_data,
                            stop=cfg.stop_conditions())
    mode = parse_audit(audit)
    if mode != "off":
        # local import: repro.analysis.audit imports this module's
        # collaborators from repro.core, so the hook resolves lazily
        from repro.analysis.audit import audit_experiment
        report = audit_experiment(experiment, strict=(mode == "strict"))
        print(report.render())
    return experiment


@dataclasses.dataclass
class Experiment:
    """A wired-up federated run: ``.run()`` drives it to completion."""
    cfg: FLConfig
    server: Server
    eval_data: Any
    stop: StopConditions

    @property
    def meter(self):
        return self.server.meter

    def run(self, verbose: bool = False) -> "ExperimentResult":
        logs = run_federated(self.server, self.eval_data, self.stop,
                             verbose=verbose,
                             eval_every=self.cfg.eval_every)
        return ExperimentResult(cfg=self.cfg, server=self.server,
                                logs=logs)


@dataclasses.dataclass
class ExperimentResult:
    cfg: FLConfig
    server: Server
    logs: List[RoundLog]

    def summary(self, fedavg_rounds: int = 30) -> dict:
        """Headline numbers plus the full CommMeter ledger; the
        normalized cost is computed against a ``fedavg_rounds``-round
        full-participation FedAvg baseline (paper default: 30).  FedX
        runs use Eq. 4 straight off the meter; FedAvg runs — whose
        rounds Eq. 4 must not price at FedX rates, see
        ``normalized_cost`` — use their recorded uplink over the
        baseline's (the Fig. 6 convention)."""
        meter = self.server.meter
        if self.server.strategy.is_fedx:
            cost = normalized_cost(meter, t_avg=fedavg_rounds)
        else:
            cost = meter.total_uplink / max(1, fedavg_total(
                fedavg_rounds, 1.0, meter.n_clients, meter.model_bytes))
        return {
            "strategy": self.cfg.strategy,
            "task": self.cfg.task,
            "partition": self.cfg.partition,
            "engine": self.server.engine,
            "rounds_per_dispatch": self.server.rounds_per_dispatch,
            "pipeline_blocks": self.server.pipeline_blocks,
            "rounds": len(self.logs),
            "final_acc": self.logs[-1].test_acc,
            "final_loss": self.logs[-1].test_loss,
            "comm": meter.summary(),
            "block_timing": meter.timing_summary(),
            "sgd_steps": meter.sgd_step_summary(),
            "bwo_rows": meter.bwo_row_summary(),
            "expert_slots": meter.expert_slot_summary(),
            "attention_paths": dict(meter.attention_paths),
            f"normalized_cost_vs_fedavg{fedavg_rounds}": cost,
        }
