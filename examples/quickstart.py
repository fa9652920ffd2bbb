"""Quickstart: FedBWO on the paper's CNN in ~25 lines.

Runs three federated rounds of the paper's protocol (every client trains
locally + refines with BWO, uploads a 4-byte score, the server adopts
the best client's weights) and prints the communication ledger.  All the
wiring — dataset synthesis, partitioning, client batching, server and
stop conditions — hangs off one ``FLConfig`` (repro.core.api).

    PYTHONPATH=src python examples/quickstart.py
"""
from repro.core import FLConfig, build_experiment

# Every round compiles to ONE device dispatch: all clients' local
# training plus the server's argmin/averaging.  Ragged
# (partition="dirichlet") client shards are stacked too, via pad+mask
# (DESIGN.md §5).  ``vectorize`` picks the client-axis traversal inside
# the round program: "auto" = an O(2 x model) streaming lax.scan on
# CPU, vmap on TPU/GPU; "scan:k" chunks the scan.
cfg = FLConfig(strategy="fedbwo", task="cnn", n_clients=5,
               n_train=600, n_test=200, batch_size=10,
               local_epochs=1, mh_pop=4, mh_generations=2,
               vectorize="auto",
               max_rounds=3, tau=0.95,
               data_seed=0, partition_seed=1, server_seed=7)
exp = build_experiment(cfg)
print(f"FedBWO | {cfg.n_clients} clients | model = "
      f"{exp.meter.model_bytes / 1e6:.1f} MB")

result = exp.run(verbose=True)

s = result.summary()
print(f"\nrounds={s['rounds']}  uplink={s['comm']['uplink_bytes']:,} bytes "
      f"(score uplink per round = {cfg.n_clients * 4} bytes "
      f"+ one model fetch)")
print(f"final accuracy = {s['final_acc']:.3f}")
