"""Sharding-rule unit tests on a small host mesh: every derived spec must
divide its dim, FSDP rule shards big matrices on both axes, expert dims
go to `model`, and the constrain() helper is a no-op without a mesh."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np

from repro.sharding.context import constrain

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    from jax.sharding import Mesh
    from repro.configs import ARCHS
    from repro.launch.steps import make_train_step
    from repro.models.transformer import build_model
    from repro.sharding import rules

    mesh = jax.make_mesh((2, 4), ("data", "model"))

    for name in ["granite-8b", "deepseek-v2-236b", "jamba-v0.1-52b"]:
        cfg = ARCHS[name].reduced()
        model = build_model(cfg, max_seq=64)
        _, init_state = make_train_step(model)
        shapes = jax.eval_shape(init_state, jax.random.PRNGKey(0))
        specs = jax.tree_util.tree_map_with_path(
            lambda p, l: (rules.param_spec(mesh, p, l), l), shapes)
        for (spec, leaf) in jax.tree.leaves(
                specs, is_leaf=lambda x: isinstance(x, tuple)):
            for dim, ax in enumerate(spec):
                if ax is None:
                    continue
                size = mesh.shape[ax] if isinstance(ax, str) else 1
                assert leaf.shape[dim] % size == 0, (name, spec, leaf.shape)
        # MoE expert dim sharded over model where divisible
        if cfg.moe is not None and cfg.moe.num_experts % 4 == 0:
            found = [s for (s, l) in jax.tree.leaves(
                         specs, is_leaf=lambda x: isinstance(x, tuple))
                     if "model" in s]
            assert found, name
    print("RULES_OK")
""")


def test_param_specs_divide_dims():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, res.stderr[-3000:]
    assert "RULES_OK" in res.stdout


def test_constrain_noop_without_mesh():
    x = jnp.ones((4, 6))
    y = constrain(x, "data", None)
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
