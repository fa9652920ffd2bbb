"""FL benchmarks reproducing the paper's four figures on synthetic
CIFAR-like data (offline container; see repro.data.synthetic).

Fig. 4  accuracy comparison      -> bench_accuracy
Fig. 5  loss comparison          -> bench_loss
Fig. 6  communication cost       -> bench_comm_cost (Eqs. 1-4)
Fig. 7  execution time           -> bench_exec_time

Scale knobs: REPRO_BENCH_TRAIN, REPRO_BENCH_TEST, REPRO_BENCH_ROUNDS,
REPRO_BENCH_CLIENTS, REPRO_BENCH_EPOCHS.  The protocol/accounting is
exact regardless of scale; only absolute accuracies shift.  Times are
those of whatever backend runs the script, not chip measurements (the
chip's are ``benchmarks/fedbench``'s).  Cached results in
results/bench/fl_runs.json are invalidated automatically when these
knobs change.
"""
from __future__ import annotations

import json
import os
import time
from typing import Dict, List

import jax

from repro.core import FLConfig, build_experiment, normalized_cost
from repro.data import (client_batches, cnn_task, make_cifar_like,
                        partition_iid)

# defaults sized for the 1-core CPU container (~20 min total); scale up
# with the env knobs for a fuller reproduction
N_TRAIN = int(os.environ.get("REPRO_BENCH_TRAIN", 600))
N_TEST = int(os.environ.get("REPRO_BENCH_TEST", 200))
ROUNDS = int(os.environ.get("REPRO_BENCH_ROUNDS", 5))
N_CLIENTS = int(os.environ.get("REPRO_BENCH_CLIENTS", 10))
BATCH = 10                       # paper §IV-A
LOCAL_EPOCHS = int(os.environ.get("REPRO_BENCH_EPOCHS", 1))
TAU = 0.70                       # paper §IV-D
PATIENCE = 5

STRATEGIES = ["fedbwo", "fedpso", "fedgwo", "fedsca", "fedavg"]
FEDAVG_CS = [1.0, 0.1]

_cache: Dict[str, dict] = {}


def _bench_config() -> Dict[str, int]:
    """The knobs a cached run must match to be reusable."""
    return {"train": N_TRAIN, "test": N_TEST, "rounds": ROUNDS,
            "clients": N_CLIENTS, "batch": BATCH, "epochs": LOCAL_EPOCHS}


def _load_cached_runs(disk: str):
    """Return cached runs only when they were produced under the current
    REPRO_BENCH_* config (older config-less caches are treated stale)."""
    with open(disk) as f:
        payload = json.load(f)
    if payload.get("config") != _bench_config():
        print(f"  [fl_bench] cache {disk} stale "
              f"(config {payload.get('config')} != {_bench_config()}); "
              "re-running", flush=True)
        return None
    return payload["runs"]


def _run_all() -> Dict[str, dict]:
    if _cache:
        return _cache
    # reuse a previous run's results if present (delete
    # results/bench/fl_runs.json or set REPRO_BENCH_FRESH to re-train)
    disk = "results/bench/fl_runs.json"
    if os.path.exists(disk) and not os.environ.get("REPRO_BENCH_FRESH"):
        cached = _load_cached_runs(disk)
        if cached is not None:
            _cache.update(cached)
            return _cache
    # one shared dataset across the strategy sweep, passed through the
    # build_experiment overrides so each run still goes through FLConfig
    rng = jax.random.PRNGKey(42)
    train, test = make_cifar_like(rng, N_TRAIN, N_TEST)
    clients = client_batches(
        partition_iid(jax.random.PRNGKey(1), train, N_CLIENTS), BATCH)
    task = cnn_task()
    runs = {}
    for name in STRATEGIES:
        cs = FEDAVG_CS if name == "fedavg" else [1.0]
        for c in cs:
            key = name if name != "fedavg" else f"fedavg_c{c}"
            cfg = FLConfig(strategy=name, client_ratio=c,
                           n_clients=N_CLIENTS, batch_size=BATCH,
                           local_epochs=LOCAL_EPOCHS, mh_pop=6,
                           mh_generations=3,
                           max_rounds=ROUNDS, patience=PATIENCE, tau=TAU)
            t0 = time.perf_counter()
            exp = build_experiment(cfg, task=task, client_data=clients,
                                   eval_data=test)
            server = exp.server
            logs = exp.run().logs
            jax.block_until_ready(server.global_params)
            wall = time.perf_counter() - t0
            # round 0 pays XLA compilation; steady state is the rest
            steady = ([l.round_time_s for l in logs[1:]]
                      or [logs[0].round_time_s])
            runs[key] = {
                "rounds": len(logs),
                "acc": [l.test_acc for l in logs],
                "loss": [l.test_loss for l in logs],
                "final_acc": logs[-1].test_acc,
                "final_loss": logs[-1].test_loss,
                "wall_s": wall,
                "compile_round_s": logs[0].round_time_s,
                "steady_round_s": sum(steady) / len(steady),
                "model_bytes": server.meter.model_bytes,
                "uplink_bytes": server.meter.total_uplink,
            }
            print(f"  [{key}] rounds={len(logs)} acc={logs[-1].test_acc:.3f} "
                  f"loss={logs[-1].test_loss:.3f} wall={wall:.1f}s "
                  f"(first={logs[0].round_time_s:.1f}s "
                  f"steady={runs[key]['steady_round_s']:.2f}s/round)",
                  flush=True)
    _cache.update(runs)
    os.makedirs("results/bench", exist_ok=True)
    with open("results/bench/fl_runs.json", "w") as f:
        json.dump({"config": _bench_config(), "runs": runs}, f, indent=1)
    return runs


def bench_accuracy() -> List[tuple]:
    """Paper Fig. 4."""
    runs = _run_all()
    return [(f"fig4_accuracy/{k}", v["wall_s"] / max(v["rounds"], 1) * 1e6,
             round(v["final_acc"], 4)) for k, v in runs.items()]


def bench_loss() -> List[tuple]:
    """Paper Fig. 5."""
    runs = _run_all()
    return [(f"fig5_loss/{k}", v["wall_s"] / max(v["rounds"], 1) * 1e6,
             round(v["final_loss"], 4)) for k, v in runs.items()]


def bench_comm_cost() -> List[tuple]:
    """Paper Fig. 6: normalized communication cost vs FedAvg C=1.0."""
    runs = _run_all()
    t_avg = runs["fedavg_c1.0"]["rounds"]
    m = runs["fedavg_c1.0"]["model_bytes"]
    out = []
    for k, v in runs.items():
        if k.startswith("fedavg"):
            c = float(k.split("_c")[1])
            cost = (v["rounds"] * max(int(c * N_CLIENTS), 1) * m) \
                / (t_avg * N_CLIENTS * m)
        else:
            cost = normalized_cost(v["rounds"], N_CLIENTS, m, t_avg, c=1.0)
        out.append((f"fig6_comm_cost/{k}", v["uplink_bytes"],
                    round(cost, 5)))
    return out


def bench_noniid_ablation() -> List[tuple]:
    """Beyond-paper ablation: FedBWO under IID vs Dirichlet(0.5) label
    skew (the paper only evaluates IID).  Winner-takes-all aggregation
    is expected to degrade under skew — one client's model can't cover
    absent classes.  The Dirichlet run exercises the engine's pad+mask
    path (DESIGN.md §5)."""
    out = []
    for label, part in [("iid", "iid"), ("dirichlet0.5", "dirichlet")]:
        cfg = FLConfig(strategy="fedbwo", partition=part, n_clients=5,
                       n_train=max(400, N_TRAIN // 2), n_test=150,
                       batch_size=10, local_epochs=1, mh_pop=4,
                       mh_generations=2, max_rounds=3, tau=0.95,
                       data_seed=13)
        t0 = time.perf_counter()
        logs = build_experiment(cfg).run().logs
        out.append((f"ablation_noniid/fedbwo_{label}",
                    (time.perf_counter() - t0) * 1e6,
                    round(logs[-1].test_acc, 4)))
    return out


def bench_exec_time() -> List[tuple]:
    """Paper Fig. 7: execution time normalized to the slowest method."""
    runs = _run_all()
    walls = {k: v["wall_s"] for k, v in runs.items()}
    mx = max(walls.values())
    return [(f"fig7_exec_time/{k}", w * 1e6, round(w / mx, 4))
            for k, w in walls.items()]

