"""Batched round engine vs the sequential per-client loop.

Parity: identical CommMeter byte accounting and numerically-close
scores/weights for FedBWO and FedAvg on a tiny synthetic task.
Memory shape: the FedX batched scan path never materializes an
(n_clients, n_params) weights array.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend.core import ClosedJaxpr, Jaxpr

from repro.core import ClientHP, Server, get_strategy
from repro.core.engine import (BatchedRoundEngine, make_batched_fedx_round,
                               resolve_vectorize, stack_clients)
from repro.data.loader import batch_dataset
from repro.data.partition import partition_iid
from repro.metaheuristics import bwo

from conftest import make_toy_data, make_toy_task

N_CLIENTS = 5


def _servers(strategy, engines=("sequential", "batched"), **kw):
    task = make_toy_task()
    data = make_toy_data(jax.random.PRNGKey(0), 400)
    clients = [batch_dataset(d, 8) for d in
               partition_iid(jax.random.PRNGKey(1), data, N_CLIENTS)]
    hp = ClientHP(local_epochs=1, mh_pop=4, mh_generations=2, lr=0.05,
                  fitness_batches=2)
    return {e: Server(task, get_strategy(strategy, **kw), hp, clients,
                      jax.random.PRNGKey(3), engine=e) for e in engines}


@pytest.mark.parametrize("strategy,kw", [("fedbwo", {}),
                                         ("fedavg", {}),
                                         ("fedavg", {"client_ratio": 0.6})])
def test_engine_parity(strategy, kw):
    servers = _servers(strategy, **kw)
    infos = {e: [s.run_round() for _ in range(2)]
             for e, s in servers.items()}
    seq, bat = servers["sequential"], servers["batched"]
    assert seq.engine == "sequential" and bat.engine == "batched"
    # identical byte accounting (the paper's Eqs. 1-2 per round)
    assert seq.meter.uplink == bat.meter.uplink
    assert seq.meter.downlink == bat.meter.downlink
    assert seq.meter.total == bat.meter.total
    for a, b in zip(infos["sequential"], infos["batched"]):
        if strategy == "fedbwo":
            assert a["best_client"] == b["best_client"]
            np.testing.assert_allclose(a["scores"], b["scores"], rtol=1e-4)
        else:
            assert a["participants"] == b["participants"]
    for x, y in zip(jax.tree.leaves(seq.global_params),
                    jax.tree.leaves(bat.global_params)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=1e-4, atol=1e-5)


def test_vectorize_modes_agree():
    task = make_toy_task()
    data = make_toy_data(jax.random.PRNGKey(0), 240)
    clients = [batch_dataset(d, 8) for d in
               partition_iid(jax.random.PRNGKey(1), data, 3)]
    hp = ClientHP(local_epochs=1, mh_pop=4, mh_generations=2, lr=0.05)
    stacked = stack_clients(clients)
    params = task.init_params(jax.random.PRNGKey(9))
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    scores = {}
    for mode in ("vmap", "scan", "scan:2"):
        fn = make_batched_fedx_round(task, hp, bwo(), vectorize=mode)
        _, s, best = fn(params, stacked, None, keys)
        scores[mode] = np.asarray(s)
        assert int(best) == int(np.argmin(scores[mode]))
    np.testing.assert_allclose(scores["vmap"], scores["scan"], rtol=1e-4)
    # the chunked scan is the same scan program, just unrolled by 2
    np.testing.assert_allclose(scores["scan"], scores["scan:2"], rtol=1e-6)


def test_resolve_vectorize():
    assert resolve_vectorize("auto", backend="cpu") == "scan"
    assert resolve_vectorize("auto", backend="tpu") == "vmap"
    assert resolve_vectorize("unroll", backend="cpu") == "unroll"
    assert resolve_vectorize("scan:4", backend="cpu") == "scan"
    with pytest.raises(ValueError):
        resolve_vectorize("bogus")


def test_auto_engine_keeps_conv_tasks_sequential_on_cpu():
    """DESIGN.md §4: on CPU, conv tasks measured faster as per-client
    dispatches — engine="auto" must detect the convs and stay
    sequential, while engine="batched" still forces the batched path."""
    from repro.core.engine import task_uses_conv
    from repro.data import cnn_task, make_cifar_like, mlp_task
    from repro.data.loader import client_batches
    from repro.data.partition import partition_iid

    train, _ = make_cifar_like(jax.random.PRNGKey(0), 40, 8)
    clients = client_batches(
        partition_iid(jax.random.PRNGKey(1), train, 2), 10)
    sample = jax.tree.map(lambda a: a[0], clients[0])
    conv, dense = cnn_task(), mlp_task()
    assert task_uses_conv(conv, conv.init_params(jax.random.PRNGKey(2)),
                          sample)
    assert not task_uses_conv(dense,
                              dense.init_params(jax.random.PRNGKey(2)),
                              sample)
    if jax.default_backend() == "cpu":
        hp = ClientHP(local_epochs=1, mh_pop=2, mh_generations=1)
        server = Server(conv, get_strategy("fedbwo"), hp, clients,
                        jax.random.PRNGKey(3), engine="auto")
        assert server.engine == "sequential"
        server = Server(dense, get_strategy("fedbwo"), hp, clients,
                        jax.random.PRNGKey(3), engine="auto")
        assert server.engine == "batched"


def test_ragged_clients_batch_via_pad_and_mask():
    """Ragged batch counts no longer force the sequential fallback: the
    engine pads to the longest client and masks (DESIGN.md §5)."""
    task = make_toy_task()
    clients = [batch_dataset(make_toy_data(jax.random.PRNGKey(i), n), 8)
               for i, n in enumerate([64, 96])]   # ragged: 8 vs 12 batches
    assert stack_clients(clients) is None         # legacy exact stacking
    stacked, mask = stack_clients(clients, pad=True)
    assert jax.tree.leaves(stacked)[0].shape[0] == 2
    assert mask.shape == (2, 12)
    assert int(mask.sum()) == 8 + 12
    hp = ClientHP(local_epochs=1, mh_pop=4, mh_generations=2)
    server = Server(task, get_strategy("fedbwo"), hp, clients,
                    jax.random.PRNGKey(3), engine="auto")
    assert server.engine == "batched"
    assert server._engine.padded
    info = server.run_round()
    assert info["engine"] == "batched"
    assert 0 <= info["best_client"] < 2


def test_unstackable_clients_fall_back_to_sequential():
    """Mismatched trailing shapes (not just ragged batch counts) are
    genuinely unstackable: auto falls back, batched raises."""
    task = make_toy_task()
    clients = [batch_dataset(make_toy_data(jax.random.PRNGKey(0), 64), 8),
               batch_dataset(make_toy_data(jax.random.PRNGKey(1), 64,
                                           d=16), 8)]   # feature dim 8 vs 16
    assert stack_clients(clients) is None
    assert stack_clients(clients, pad=True) == (None, None)
    hp = ClientHP(local_epochs=1, mh_pop=4, mh_generations=2)
    server = Server(task, get_strategy("fedbwo"), hp, clients,
                    jax.random.PRNGKey(3), engine="auto")
    assert server.engine == "sequential"
    with pytest.raises(ValueError):
        Server(task, get_strategy("fedbwo"), hp, clients,
               jax.random.PRNGKey(3), engine="batched")


# --------------------------------------------------- memory shape ----
def _iter_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for val in eqn.params.values():
            subs = val if isinstance(val, (tuple, list)) else (val,)
            for sub in subs:
                if isinstance(sub, ClosedJaxpr):
                    yield from _iter_eqns(sub.jaxpr)
                elif isinstance(sub, Jaxpr):
                    yield from _iter_eqns(sub)


def _max_intermediate_size(fn, *args):
    jaxpr = jax.make_jaxpr(fn)(*args)
    sizes = [v.aval.size for eqn in _iter_eqns(jaxpr.jaxpr)
             for v in eqn.outvars if hasattr(v.aval, "size")]
    return max(sizes)


def test_fedx_scan_path_streams_weights():
    """The streaming winner reduction must keep peak weight memory at
    O(2 x model): no intermediate of size >= n_clients x n_params."""
    # n_clients comfortably above 2 x mh_pop so BWO's row union (pop +
    # children, 2 x mh_pop rows of n_params) stays under the
    # weights-stack threshold
    n_clients, d, classes = 12, 64, 32
    task = make_toy_task(d=d, classes=classes)
    n_params = d * classes + classes
    # data deliberately smaller than the weights stack so the threshold
    # can only be crossed by materializing per-client weights
    clients = [batch_dataset(make_toy_data(jax.random.PRNGKey(i), 8, d=d,
                                           classes=classes), 4)
               for i in range(n_clients)]
    stacked = stack_clients(clients)
    params = task.init_params(jax.random.PRNGKey(9))
    keys = jax.random.split(jax.random.PRNGKey(3), n_clients)
    hp = ClientHP(local_epochs=1, mh_pop=4, mh_generations=2,
                  fitness_batches=2)
    threshold = n_clients * n_params

    fn = make_batched_fedx_round(task, hp, bwo(), vectorize="scan")
    assert _max_intermediate_size(fn, params, stacked, None, keys) < threshold

    # positive control: the vmap path DOES stack all client weights,
    # so the detector is actually measuring what we think it measures
    fn_vmap = make_batched_fedx_round(task, hp, bwo(), vectorize="vmap")
    assert _max_intermediate_size(fn_vmap, params, stacked, None,
                                  keys) >= threshold
