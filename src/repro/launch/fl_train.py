"""FedBWO / FedX / FedAvg federated-training driver (the paper's
experiment), a thin CLI over the ``FLConfig`` experiment facade
(repro.core.api).

    PYTHONPATH=src python -m repro.launch.fl_train --strategy fedbwo \
        --clients 10 --rounds 8 --train 1000
"""
from __future__ import annotations

import argparse
import json

from repro.core import FLConfig, build_experiment
from repro.core.api import GENOMES, strategy_names, PARTITIONS, TASKS
from repro.launch.compile_cache import enable_compile_cache
from repro.core.knobs import (AUDIT_MODES, validate_audit,
                              validate_rounds_per_dispatch,
                              validate_vectorize)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--strategy", default="fedbwo",
                    choices=list(strategy_names()))
    ap.add_argument("--task", default="cnn", choices=list(TASKS),
                    help="cnn = the paper's CNN; mlp = FedAvg 2NN; lm = "
                         "a language model of the registry (--arch)")
    ap.add_argument("--arch", default=None,
                    help="repro.configs.ARCHS name of the lm task's model")
    ap.add_argument("--genome", default="flat", choices=list(GENOMES),
                    help="flat = BWO over the weight vector (the "
                         "paper's); tensor = one gain per weight tensor")
    ap.add_argument("--clients", type=int, default=10)
    ap.add_argument("--rounds", type=int, default=8)
    ap.add_argument("--client-ratio", type=float, default=1.0)
    ap.add_argument("--train", type=int, default=1000)
    ap.add_argument("--test", type=int, default=300)
    ap.add_argument("--batch", type=int, default=10)       # paper §IV-A
    ap.add_argument("--local-epochs", type=int, default=2)
    ap.add_argument("--lr", type=float, default=0.0025)    # paper §IV-A
    ap.add_argument("--pop", type=int, default=6)
    ap.add_argument("--generations", type=int, default=3)
    ap.add_argument("--tau", type=float, default=0.70)     # paper §IV-D
    ap.add_argument("--non-iid", action="store_true",
                    help="Dirichlet label-skew partition; the engine "
                         "pads+masks the ragged client shards")
    ap.add_argument("--alpha", type=float, default=0.5,
                    help="Dirichlet concentration for --non-iid")
    ap.add_argument("--vectorize", default="auto", type=validate_vectorize,
                    metavar="auto|vmap|scan[:k]",
                    help="client-axis traversal inside the round program "
                         "(auto: scan on CPU, vmap elsewhere; scan:k "
                         "chunks the scan with unroll=k)")
    ap.add_argument("--rounds-per-dispatch", default="1",
                    type=validate_rounds_per_dispatch, metavar="auto|R",
                    help="fuse R rounds into one device dispatch with "
                         "one host sync per block, blocks pipelined "
                         "when R > 1 (auto = DEFAULT_ROUNDS_PER_DISPATCH, "
                         "DESIGN.md §6-§7)")
    ap.add_argument("--eval-every", type=int, default=1, metavar="K",
                    help="evaluate the global model every K-th round; "
                         "fused blocks run the cadence on device")
    ap.add_argument("--audit", nargs="?", const="strict", default="off",
                    type=validate_audit, metavar="|".join(AUDIT_MODES),
                    help="run the flcheck static auditor "
                         "(repro.analysis) over the server's block "
                         "programs before training; bare flag = strict "
                         "(abort on error-severity findings), 'report' "
                         "prints findings without gating")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = FLConfig(
        strategy=args.strategy, task=args.task, arch=args.arch,
        genome=args.genome, n_clients=args.clients,
        client_ratio=args.client_ratio,
        partition="dirichlet" if args.non_iid else "iid",
        dirichlet_alpha=args.alpha, n_train=args.train, n_test=args.test,
        batch_size=args.batch, local_epochs=args.local_epochs, lr=args.lr,
        mh_pop=args.pop, mh_generations=args.generations,
        vectorize=args.vectorize,
        rounds_per_dispatch=args.rounds_per_dispatch,
        eval_every=args.eval_every,
        max_rounds=args.rounds, tau=args.tau)
    exp = build_experiment(cfg, audit=args.audit)
    print(f"strategy={cfg.strategy} clients={cfg.n_clients} "
          f"partition={cfg.partition} "
          f"rounds_per_dispatch={exp.server.rounds_per_dispatch} "
          f"model_bytes={exp.meter.model_bytes:,}")
    result = exp.run(verbose=True)

    summary = result.summary(fedavg_rounds=30)
    print(json.dumps(summary, indent=1))
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"summary": summary,
                       "rounds": [vars(l) for l in result.logs]}, f,
                      indent=1, default=str)


if __name__ == "__main__":
    main()
