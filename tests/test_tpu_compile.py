"""Compiles for a TPU v5e that is described, not attached.

The TPU compiler refuses what interpret mode and the CPU accept (block
shapes off the (8, 128) tiling, casts Mosaic lacks), so the main path's
kernels are compiled here: BWO evolution at the paper CNN's width, and
latent attention's flash kernel at the token cell's.  The topology is
described inside a fixture: only the worker that runs these tests loads
the TPU library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.client import ClientHP, make_client_update
from repro.data.loader import batch_dataset
from repro.kernels.bwo_evolve.ops import bwo_evolve
from repro.kernels.flash_attention import splash
from repro.metaheuristics import bwo

from conftest import make_toy_data, make_toy_task

PAPER_CNN_PARAMS = 2_465_322


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("pop", [6, 8])
def test_bwo_evolve_compiles_for_v5e(one_chip, pop):
    def shape(s, dtype):
        return jax.ShapeDtypeStruct(s, dtype, sharding=one_chip)

    compiled = jax.jit(
        lambda p, f, k: bwo_evolve(p, f, k, interpret=False)).lower(
            shape((pop, PAPER_CNN_PARAMS), jnp.float32),
            shape((pop,), jnp.float32), shape((2,), jnp.uint32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_compiles_for_v5e(one_chip):
    """DeepSeek-V2-Lite's latent attention in the token cell: 16 heads,
    query/key width 192, value width 128, 4,096 packed tokens; the
    forward and the gradient of q, k and v."""
    def shape(s, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(s, dtype, sharding=one_chip)

    def loss(q, k, v, segments):
        return splash.causal_flash_attention(
            q, k, v, segments, scale=0.1, interpret=False).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        shape((1, 4096, 16, 192)), shape((1, 4096, 16, 192)),
        shape((1, 4096, 16, 128)), shape((1, 4096), jnp.int32)).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 2


def test_client_loops_roll_off_the_cpu():
    """Off the CPU the client update's program does not grow with the
    batches per client; on the CPU its loops stay unrolled.  Sizes are
    lines of the lowered program, where a scan's unroll shows."""
    task = make_toy_task()
    params = task.init_params(jax.random.PRNGKey(0))
    hp = ClientHP(local_epochs=2, mh_pop=4, mh_generations=3)
    key = jax.random.PRNGKey(1)

    def size(backend, n_batches):
        data = batch_dataset(make_toy_data(key, 8 * n_batches), 8)
        update = make_client_update(task, hp, bwo(), backend=backend)
        text = jax.jit(update).lower(params, data, key).as_text()
        return len(text.splitlines())

    assert size("tpu", 4) == size("tpu", 64)
    assert size("cpu", 64) > size("cpu", 4) > size("tpu", 4)
