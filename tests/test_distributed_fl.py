"""shard_map FL rounds on a virtual-CPU host mesh, with one and with two
clients per device (run in a CPU-only subprocess so the forced device
count doesn't leak into other tests)."""
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import sys
    import jax, jax.numpy as jnp
    import numpy as np
    n_devices = int(sys.argv[1])
    jax.config.update("jax_num_cpu_devices", n_devices)
    from repro.core.client import Task, ClientHP, make_client_update
    from repro.core.distributed import make_fedx_round, make_fedavg_round
    from repro.core.engine import make_batched_fedx_round
    from repro.launch.mesh import make_host_mesh
    from repro.metaheuristics import bwo

    def init_params(rng):
        return {"w": jax.random.normal(rng, (6, 3)) * 0.1,
                "b": jnp.zeros((3,))}

    def loss_fn(params, batch):
        logits = batch["x"] @ params["w"] + params["b"]
        lp = jax.nn.log_softmax(logits)
        nll = -jnp.take_along_axis(lp, batch["y"][:, None], -1).mean()
        return nll, (logits.argmax(-1) == batch["y"]).mean()

    task = Task(init_params, loss_fn)
    rng = jax.random.PRNGKey(0)
    N = 8
    w_true = jax.random.normal(jax.random.PRNGKey(9), (6, 3))
    x = jax.random.normal(rng, (N, 4, 16, 6))
    y = (x @ w_true).argmax(-1).astype(jnp.int32)
    data = {"x": x, "y": y}
    mesh = make_host_mesh(n_devices)
    hp = ClientHP(local_epochs=2, mh_pop=4, mh_generations=2, lr=0.1)
    keys = jax.vmap(jax.random.key_data)(jax.random.split(rng, N))

    # --- FedX: winner weights adopted identically on all clients ---
    rnd = make_fedx_round(task, hp, bwo(), mesh)
    params = task.init_params(rng)
    s_prev = None
    for r in range(4):
        params, scores = rnd(params, data, keys)
        s = float(scores.min())
        if s_prev is not None:
            assert s <= s_prev * 1.5, (r, s, s_prev)
        s_prev = s
    assert np.isfinite(s), s

    # every client of every shard trains: scores and the adopted winner
    # match the single-device batched round on the same data and keys
    p0 = task.init_params(rng)
    got, got_scores = rnd(p0, data, keys)
    want, want_scores, _ = make_batched_fedx_round(task, hp, bwo())(
        p0, data, None, keys)
    assert got_scores.shape == (N,), got_scores.shape
    np.testing.assert_allclose(np.asarray(got_scores),
                               np.asarray(want_scores), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)

    # --- FedAvg: averaged weights identical to manual mean ---
    rnd2 = make_fedavg_round(task, hp, mesh)
    p0 = task.init_params(rng)
    pavg, scores2 = rnd2(p0, data, keys)
    manual = []
    for k in range(N):
        dk = jax.tree.map(lambda a: a[k], data)
        key = jax.random.wrap_key_data(keys[k], impl="threefry2x32")
        _, pk = jax.jit(make_client_update(task, hp, None))(p0, dk, key)
        manual.append(pk)
    pm = jax.tree.map(lambda *xs: jnp.mean(jnp.stack(xs), 0), *manual)
    for a, b in zip(jax.tree.leaves(pavg), jax.tree.leaves(pm)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)
    print("DISTRIBUTED_OK")
""")


def _run_script(n_devices):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run([sys.executable, "-c", SCRIPT, str(n_devices)],
                         env=env, capture_output=True, text=True,
                         timeout=900)
    assert res.returncode == 0, res.stderr[-3000:]
    assert "DISTRIBUTED_OK" in res.stdout


def test_fl_rounds_on_8_device_mesh():
    _run_script(8)


def test_fl_rounds_two_clients_per_device():
    """8 clients on a 4-device mesh: every shard trains both of its
    clients, not only the first."""
    _run_script(4)
