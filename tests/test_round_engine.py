"""The round program against the per-client reference loop.

``Server.run_round`` runs a block of one of the fused round program;
``conftest.ReferenceRounds`` runs the same round as a loop of jitted
per-client updates.  Parity: identical CommMeter byte accounting,
winners and participants, and numerically-close scores/weights.
Memory shape: the FedX scan path never materializes an (n_clients,
n_params) weights array.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend.core import ClosedJaxpr, Jaxpr

from repro.core import ClientHP, Server, get_strategy
from repro.core.engine import (_fedx_round_body, resolve_vectorize,
                               stack_clients)
from repro.data.loader import batch_dataset
from repro.data.partition import partition_iid
from repro.metaheuristics import bwo

from conftest import ReferenceRounds, make_toy_data, make_toy_task

N_CLIENTS = 5


def _iid_clients(n=400, n_clients=N_CLIENTS):
    data = make_toy_data(jax.random.PRNGKey(0), n)
    return [batch_dataset(d, 8) for d in
            partition_iid(jax.random.PRNGKey(1), data, n_clients)]


def _ragged_clients(sizes=(48, 24, 72, 16, 40)):
    raw = make_toy_data(jax.random.PRNGKey(0), sum(sizes))
    edges = np.cumsum((0,) + tuple(sizes))
    return [batch_dataset(jax.tree.map(lambda a: a[lo:hi], raw), 8)
            for lo, hi in zip(edges[:-1], edges[1:])]


def _pair(strategy, clients, vectorize="auto", **kw):
    """A server and the reference loop, from the same task, data and
    key."""
    task = make_toy_task()
    hp = ClientHP(local_epochs=1, mh_pop=4, mh_generations=2, lr=0.05,
                  fitness_batches=2, vectorize=vectorize)
    strat = get_strategy(strategy, **kw)
    return (Server(task, strat, hp, clients, jax.random.PRNGKey(3)),
            ReferenceRounds(task, strat, hp, clients,
                            jax.random.PRNGKey(3)))


def _assert_rounds_match(a, b, strategy):
    """``a`` from the reference loop, ``b`` from the server."""
    if strategy == "fedbwo":
        assert a["best_client"] == b["best_client"]
    else:
        assert a["participants"] == b["participants"]
    np.testing.assert_allclose(a["scores"], b["scores"], rtol=1e-4)


def _assert_params_close(x, y):
    for a, b in zip(jax.tree.leaves(x), jax.tree.leaves(y)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("strategy,kw", [("fedbwo", {}),
                                         ("fedavg", {}),
                                         ("fedavg", {"client_ratio": 0.6})])
def test_engine_parity(strategy, kw):
    bat, ref = _pair(strategy, _iid_clients(), **kw)
    infos = [(ref.run_round(), bat.run_round()) for _ in range(2)]
    assert bat.engine == "batched"
    # identical byte accounting (the paper's Eqs. 1-2 per round)
    assert ref.meter.uplink == bat.meter.uplink
    assert ref.meter.downlink == bat.meter.downlink
    assert ref.meter.total == bat.meter.total
    for a, b in infos:
        _assert_rounds_match(a, b, strategy)
    _assert_params_close(ref.global_params, bat.global_params)


@pytest.mark.parametrize("layout", ["uniform", "ragged"])
@pytest.mark.parametrize("vectorize", ["vmap", "scan"])
@pytest.mark.parametrize("strategy,kw", [("fedbwo", {}),
                                         ("fedavg", {}),
                                         ("fedavg", {"client_ratio": 0.6})])
def test_run_round_matches_reference_loop(strategy, kw, vectorize, layout):
    """A block of one == the per-client loop: winner or participants,
    scores, adopted weights and the byte ledger, under either client-
    axis traversal, on uniform and on padded (ragged) client data."""
    clients = _iid_clients() if layout == "uniform" else _ragged_clients()
    server, ref = _pair(strategy, clients, vectorize, **kw)
    assert server._engine.padded == (layout == "ragged")
    _assert_rounds_match(ref.run_round(), server.run_round(), strategy)
    _assert_params_close(ref.global_params, server.global_params)
    np.testing.assert_array_equal(np.asarray(ref.rng),
                                  np.asarray(server.rng))
    assert ref.meter.uplink == server.meter.uplink
    assert ref.meter.downlink == server.meter.downlink


def test_run_round_records_no_block_timing():
    """run_round is a block of one but stays out of the block ledger:
    only run_block / run_pipelined record a BlockTiming."""
    server, _ = _pair("fedbwo", _iid_clients())
    server.run_round()
    server.run_round()
    assert server.meter.block_timings == []
    assert len(server.meter.uplink) == 2
    server.run_block(2)
    assert [t.n_rounds for t in server.meter.block_timings] == [2]


def test_vectorize_modes_agree():
    task = make_toy_task()
    data = make_toy_data(jax.random.PRNGKey(0), 240)
    clients = [batch_dataset(d, 8) for d in
               partition_iid(jax.random.PRNGKey(1), data, 3)]
    hp = ClientHP(local_epochs=1, mh_pop=4, mh_generations=2, lr=0.05)
    stacked, _ = stack_clients(clients)
    params = task.init_params(jax.random.PRNGKey(9))
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    scores = {}
    for mode in ("vmap", "scan", "scan:2"):
        fn = jax.jit(_fedx_round_body(task, hp, bwo(), vectorize=mode))
        _, s, best = fn(params, stacked, None, keys)
        scores[mode] = np.asarray(s)
        assert int(best) == int(np.argmin(scores[mode]))
    np.testing.assert_allclose(scores["vmap"], scores["scan"], rtol=1e-4)
    # the chunked scan is the same scan program, just unrolled by 2
    np.testing.assert_allclose(scores["scan"], scores["scan:2"], rtol=1e-6)


def test_resolve_vectorize():
    assert resolve_vectorize("auto", backend="cpu") == "scan"
    assert resolve_vectorize("auto", backend="tpu") == "vmap"
    assert resolve_vectorize("scan:4", backend="cpu") == "scan"
    for bad in ("bogus", "unroll"):
        with pytest.raises(ValueError):
            resolve_vectorize(bad)


def test_ragged_clients_batch_via_pad_and_mask():
    """Ragged batch counts stack: the engine pads to the longest client
    and masks (DESIGN.md §5)."""
    task = make_toy_task()
    clients = [batch_dataset(make_toy_data(jax.random.PRNGKey(i), n), 8)
               for i, n in enumerate([64, 96])]   # ragged: 8 vs 12 batches
    stacked, mask = stack_clients(clients)
    assert jax.tree.leaves(stacked)[0].shape[0] == 2
    assert mask.shape == (2, 12)
    assert int(mask.sum()) == 8 + 12
    hp = ClientHP(local_epochs=1, mh_pop=4, mh_generations=2)
    server = Server(task, get_strategy("fedbwo"), hp, clients,
                    jax.random.PRNGKey(3))
    assert server._engine.padded
    info = server.run_round()
    assert info["engine"] == "batched"
    assert 0 <= info["best_client"] < 2


def test_unstackable_clients_fall_back_to_sequential():
    """Mismatched trailing shapes (not just ragged batch counts) are
    genuinely unstackable: there is no per-client fallback, so the
    server raises the engine's ValueError."""
    task = make_toy_task()
    clients = [batch_dataset(make_toy_data(jax.random.PRNGKey(0), 64), 8),
               batch_dataset(make_toy_data(jax.random.PRNGKey(1), 64,
                                           d=16), 8)]   # feature dim 8 vs 16
    assert stack_clients(clients) == (None, None)
    hp = ClientHP(local_epochs=1, mh_pop=4, mh_generations=2)
    with pytest.raises(ValueError, match="not stackable"):
        Server(task, get_strategy("fedbwo"), hp, clients,
               jax.random.PRNGKey(3))


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
    stacked, _ = stack_clients(clients)
    params = task.init_params(jax.random.PRNGKey(9))
    keys = jax.random.split(jax.random.PRNGKey(3), n_clients)
    hp = ClientHP(local_epochs=1, mh_pop=4, mh_generations=2,
                  fitness_batches=2)
    threshold = n_clients * n_params

    fn = _fedx_round_body(task, hp, bwo(), vectorize="scan")
    assert _max_intermediate_size(fn, params, stacked, None, keys) < threshold

    # positive control: the vmap path DOES stack all client weights,
    # so the detector is actually measuring what we think it measures
    fn_vmap = _fedx_round_body(task, hp, bwo(), vectorize="vmap")
    assert _max_intermediate_size(fn_vmap, params, stacked, None,
                                  keys) >= threshold
