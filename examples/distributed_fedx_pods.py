"""The paper's protocol as a multi-device collective schedule: every
device of the host stands in for a pod holding two federation clients
under shard_map.  Local training runs with ZERO cross-device
collectives; per round the only traffic is the 4-byte-score all-gather
+ the winner weight fetch — versus FedAvg's full-model all-reduce every
round.  On a CPU-only host, JAX is given 8 virtual CPU devices.

    PYTHONPATH=src python examples/distributed_fedx_pods.py
"""
import jax
import jax.numpy as jnp

# takes effect only on the CPU platform, so an accelerator host keeps
# its own devices
jax.config.update("jax_num_cpu_devices", 8)

from repro.core.client import ClientHP, Task
from repro.core.engine import (make_sharded_fedavg_round,
                               make_sharded_fedx_round)
from repro.launch.mesh import make_host_mesh
from repro.metaheuristics import bwo


def init_params(rng):
    k1, k2 = jax.random.split(rng)
    return {"w1": jax.random.normal(k1, (16, 32)) * 0.2,
            "w2": jax.random.normal(k2, (32, 4)) * 0.2}


def loss_fn(params, batch):
    h = jnp.tanh(batch["x"] @ params["w1"])
    logits = h @ params["w2"]
    lp = jax.nn.log_softmax(logits)
    nll = -jnp.take_along_axis(lp, batch["y"][:, None], -1).mean()
    return nll, (logits.argmax(-1) == batch["y"]).mean()


task = Task(init_params, loss_fn)
n_devices = len(jax.devices())
N = 2 * n_devices          # two clients per device
rng = jax.random.PRNGKey(0)
w_true = jax.random.normal(jax.random.PRNGKey(9), (16, 4))
x = jax.random.normal(rng, (N, 8, 32, 16))
y = (x @ w_true).argmax(-1).astype(jnp.int32)
data = {"x": x, "y": y}

mesh = make_host_mesh(n_devices)
hp = ClientHP(local_epochs=2, mh_pop=6, mh_generations=3, lr=0.1)
keys = jax.vmap(jax.random.key_data)(jax.random.split(rng, N))

print(f"mesh: {mesh.shape} — each device holds {N // n_devices} "
      f"federation clients")
for label, rnd in [("FedBWO", make_sharded_fedx_round(task, hp, bwo(),
                                                     mesh)),
                   ("FedAvg", make_sharded_fedavg_round(task, hp, mesh))]:
    params = task.init_params(jax.random.PRNGKey(3))
    nbytes = sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(params))
    print(f"\n{label}: model = {nbytes:,} bytes")
    for r in range(5):
        params, scores = rnd(params, data, keys)
        comm = (N * 4 + nbytes) if label == "FedBWO" else N * nbytes
        print(f"  round {r}: best_score={float(scores.min()):.4f} "
              f"logical uplink={comm:,}B")
