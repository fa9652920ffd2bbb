"""Double-buffered block pipeline (DESIGN.md §7).

``Server.run_pipelined`` dispatches fused block k+1 before fetching
block k's logs, so host-side log reconstruction / meter recording /
stopping checks overlap device execution.  Everything here is BIT-exact
against the serial ``run_block`` loop: params, the PRNG carry, the info
dicts, and the CommMeter ledger — pipelining reorders host work, never
device work.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (ClientHP, Server, Task, get_strategy,
                        stack_clients)
from repro.core.engine import (BatchedRoundEngine, _donate_argnums,
                               pipeline_blocks)
from repro.core.knobs import DEFAULT_PIPELINE_DEPTH
from repro.core.protocol import StopConditions, run_federated
from repro.data.loader import batch_dataset
from repro.data.partition import partition_dirichlet, partition_iid

from conftest import make_toy_data, make_toy_task

N_CLIENTS = 5
R = 5


def _clients(n=400, n_clients=N_CLIENTS, batch=8):
    data = make_toy_data(jax.random.PRNGKey(0), n)
    return [batch_dataset(d, batch) for d in
            partition_iid(jax.random.PRNGKey(1), data, n_clients)]


def _hp():
    return ClientHP(local_epochs=1, mh_pop=4, mh_generations=2, lr=0.05,
                    fitness_batches=2)


def _server(strategy, clients, rounds_per_dispatch=R, task=None, **kw):
    return Server(task or make_toy_task(), get_strategy(strategy, **kw),
                  _hp(), clients, jax.random.PRNGKey(3),
                  rounds_per_dispatch=rounds_per_dispatch)


def _assert_trees_bitexact(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _assert_infos_equal(a, b):
    assert len(a) == len(b)
    for ia, ib in zip(a, b):
        assert set(ia) == set(ib)
        for k in ia:
            va, vb = ia[k], ib[k]
            if isinstance(va, float):
                assert (va == vb or (math.isnan(va) and math.isnan(vb)))
            else:
                assert va == vb


# ---------------------------------------------------------------- generic

def test_pipeline_blocks_overlap_order_and_results():
    """dispatch runs ahead of finish by exactly depth-1 entries, and the
    results come back in schedule order."""
    events = []

    def dispatch(spec):
        events.append(("d", spec))
        return spec

    def finish(pending):
        events.append(("f", pending))
        return pending * 10

    results, kept, stopped = pipeline_blocks(dispatch, finish,
                                             [1, 2, 3, 4], depth=2)
    assert results == [10, 20, 30, 40]
    assert kept == 4 and not stopped
    # depth=2 double buffering: two dispatches precede the first finish,
    # then dispatch/finish strictly alternate until the drain
    assert events == [("d", 1), ("d", 2), ("f", 1), ("d", 3), ("f", 2),
                      ("d", 4), ("f", 3), ("f", 4)]


def test_pipeline_blocks_stop_drains_in_flight():
    """A stop after block k still finishes the depth-1 in-flight blocks
    (their side effects land) but marks kept at the triggering block."""
    dispatched = []

    def dispatch(spec):
        dispatched.append(spec)
        return spec

    results, kept, stopped = pipeline_blocks(
        dispatch, lambda p: p, [1, 2, 3, 4, 5], depth=2,
        should_stop=lambda r: r == 2)
    assert stopped and kept == 2
    # block 3 was already in flight when 2 finished -> drained, 4/5 never
    # dispatched
    assert dispatched == [1, 2, 3]
    assert results == [1, 2, 3]


def test_pipeline_blocks_depth_one_is_serial():
    events = []
    results, kept, stopped = pipeline_blocks(
        lambda s: events.append(("d", s)) or s,
        lambda p: events.append(("f", p)) or p, [1, 2], depth=1)
    assert events == [("d", 1), ("f", 1), ("d", 2), ("f", 2)]
    with pytest.raises(ValueError):
        pipeline_blocks(lambda s: s, lambda p: p, [1], depth=0)


# ----------------------------------------------------------- bit-exactness

@pytest.mark.parametrize("strategy,kw", [("fedbwo", {}),
                                         ("fedavg", {"client_ratio": 0.6})])
def test_run_pipelined_bitexact_vs_serial_run_block(strategy, kw):
    """run_pipelined == a serial run_block loop, bit for bit: params,
    rng, info dicts (incl. on-device eval cadence), and the byte
    ledger + per-round kinds."""
    clients = _clients()
    test = make_toy_data(jax.random.PRNGKey(7), 100)
    serial = _server(strategy, clients, **kw)
    piped = _server(strategy, clients, **kw)
    infos_s = []
    for _ in range(3):
        infos_s += serial.run_block(R, eval_data=test, eval_every=2)
    res = piped.run_pipelined(3 * R, eval_data=test, eval_every=2)
    assert res.kept == 3 * R and not res.stopped
    _assert_trees_bitexact(serial.global_params, piped.global_params)
    np.testing.assert_array_equal(np.asarray(serial.rng),
                                  np.asarray(piped.rng))
    _assert_infos_equal(infos_s, res.infos)
    assert serial.meter.uplink == piped.meter.uplink
    assert serial.meter.downlink == piped.meter.downlink
    assert serial.meter.kinds == piped.meter.kinds
    assert serial.meter.summary() == piped.meter.summary()
    # the pipeline recorded one timing entry per block
    assert len(piped.meter.block_timings) == 3
    assert piped.meter.timing_summary()["rounds"] == 3 * R


def test_run_pipelined_bitexact_on_ragged_dirichlet():
    """Pipelining composes with pad+mask ragged shards (DESIGN.md §5)."""
    def labeled_task(d=8, classes=3):
        def init_params(rng):
            k1, _ = jax.random.split(rng)
            return {"w": jax.random.normal(k1, (d, classes)) * 0.1,
                    "b": jnp.zeros((classes,))}

        def loss_fn(params, batch):
            logits = batch["x"] @ params["w"] + params["b"]
            lp = jax.nn.log_softmax(logits)
            nll = -jnp.take_along_axis(
                lp, batch["labels"][:, None], -1).mean()
            acc = (logits.argmax(-1) == batch["labels"]).mean()
            return nll, acc

        return Task(init_params, loss_fn)

    raw = make_toy_data(jax.random.PRNGKey(0), 480)
    parts = partition_dirichlet(jax.random.PRNGKey(5),
                                {"x": raw["x"], "labels": raw["y"]},
                                4, alpha=0.5, num_classes=3)
    clients = [batch_dataset(p, 8) for p in parts]
    serial = _server("fedbwo", clients, task=labeled_task())
    piped = _server("fedbwo", clients, task=labeled_task())
    assert piped._engine.padded
    infos_s = serial.run_block(R) + serial.run_block(R)
    res = piped.run_pipelined(2 * R)
    _assert_trees_bitexact(serial.global_params, piped.global_params)
    _assert_infos_equal(infos_s, res.infos)
    assert serial.meter.uplink == piped.meter.uplink


def test_run_pipelined_stopping_overshoot():
    """When stop_fn triggers in block k, the in-flight block k+1
    completes (server state/meter advance — the documented one-block
    overshoot) but ``kept`` trims the returned logs at block k's end."""
    clients = _clients()
    test = make_toy_data(jax.random.PRNGKey(7), 100)
    server = _server("fedbwo", clients)
    res = server.run_pipelined(4 * R, eval_data=test, eval_every=1,
                               stop_fn=lambda info: True)
    assert res.stopped
    assert res.kept == R                 # triggering block
    assert len(res.infos) == 2 * R       # + one drained in-flight block
    assert server.rounds_completed == 2 * R
    assert len(server.meter.uplink) == 2 * R


def test_run_federated_pipelined_matches_serial_fused():
    """End-to-end: the pipelined driver's logs match a serial run_block
    loop with the same leftover single rounds, round for round (tau
    never triggers)."""
    clients = _clients()
    test = make_toy_data(jax.random.PRNGKey(7), 100)
    stop = StopConditions(max_rounds=12, patience=100, tau=1.1)
    piped = _server("fedbwo", clients)
    logs = run_federated(piped, test, stop)
    serial = _server("fedbwo", clients)
    want = [(i.get("eval_loss"), i.get("eval_acc"))
            for i in serial.run_block(R, test) + serial.run_block(R, test)]
    for _ in range(2):       # the leftover rounds run as blocks of one
        serial.run_round()
        want.append(serial.evaluate(test))
    assert len(logs) == len(want) == 12
    for rnd, (log, (loss, acc)) in enumerate(zip(logs, want)):
        assert log.round == rnd
        assert (log.test_loss, log.test_acc) == (loss, acc)
    _assert_trees_bitexact(serial.global_params, piped.global_params)


def test_run_federated_pipelined_trims_overshoot_from_logs():
    """tau triggers in the first block: the returned logs end at that
    block even though the in-flight block ran (and is accounted)."""
    clients = _clients()
    test = make_toy_data(jax.random.PRNGKey(7), 100)
    server = _server("fedbwo", clients, rounds_per_dispatch=2)
    stop = StopConditions(max_rounds=20, patience=1000, tau=0.0)
    logs = run_federated(server, test, stop)
    assert len(logs) == 2                       # triggering block only
    assert server.rounds_completed == 4         # + drained in-flight
    assert len(server.meter.uplink) == 4


# ---------------------------------------------------------- derived rule

def test_pipeline_blocks_auto_resolution():
    """run_federated pipelines exactly when rounds_per_dispatch R > 1
    and a full block of R remains: R = 1 runs single rounds (no block
    timing), R = 2 over 5 rounds runs two pipelined blocks and one
    leftover single round."""
    clients = _clients()
    test = make_toy_data(jax.random.PRNGKey(7), 100)
    stop = StopConditions(max_rounds=5, patience=100, tau=1.1)
    single = _server("fedbwo", clients, rounds_per_dispatch=1)
    logs = run_federated(single, test, stop)
    assert single.meter.block_timings == []
    assert [l.info["engine"] for l in logs] == ["batched"] * 5
    piped = _server("fedbwo", clients, rounds_per_dispatch=2)
    logs = run_federated(piped, test, stop)
    assert [t.n_rounds for t in piped.meter.block_timings] == [2, 2]
    assert [l.info["engine"] for l in logs] == ["fused"] * 4 + ["batched"]
    assert DEFAULT_PIPELINE_DEPTH == 2


def test_block_timing_ledger():
    """finish_block records one BlockTiming per block with coherent
    fields; summary() stays byte-ledger-only (fused parity tests compare
    it across engines)."""
    clients = _clients()
    server = _server("fedbwo", clients)
    server.run_pipelined(2 * R)
    ts = server.meter.block_timings
    assert len(ts) == 2
    for t in ts:
        assert t.n_rounds == R
        assert t.total_s > 0 and t.sync_s >= 0 and t.dispatch_s >= 0
    s = server.meter.timing_summary()
    assert s["blocks"] == 2 and s["rounds"] == 2 * R
    assert 0.0 <= s["sync_fraction"] <= 1.0
    assert "block_timings" not in server.meter.summary()
    assert "kinds" not in server.meter.summary()


# ------------------------------------------------- satellite regressions

def test_server_rejects_empty_client_shard():
    """A zero-batch shard used to surface as an opaque IndexError from
    the conv probe; now a clear ValueError naming the shard."""
    clients = _clients()
    clients[2] = jax.tree.map(lambda a: a[:0], clients[2])
    with pytest.raises(ValueError, match=r"client shards \[2\].*empty"):
        Server(make_toy_task(), get_strategy("fedbwo"), _hp(), clients,
               jax.random.PRNGKey(3))
    with pytest.raises(ValueError, match=r"empty"):
        BatchedRoundEngine(make_toy_task(), get_strategy("fedbwo"),
                           _hp(), clients)


def test_stack_clients_zero_length_shard_masks_out():
    """stack_clients represents a zero-batch shard as an all-False mask
    row instead of crashing."""
    clients = _clients(n_clients=3)
    clients[1] = jax.tree.map(lambda a: a[:0], clients[1])
    stacked, mask = stack_clients(clients)
    assert stacked is not None
    assert not bool(mask[1].any())
    assert bool(mask[0].all()) and bool(mask[2].all())


def test_donate_argnums_uses_explicit_backend():
    """Donation is resolved from the backend passed at build time, never
    implicitly from jax.default_backend() at call time."""
    assert _donate_argnums(True, (0,), backend="cpu") == ()
    assert _donate_argnums(True, (0, 1), backend="gpu") == (0, 1)
    assert _donate_argnums(True, (0,), backend="tpu") == (0,)
    assert _donate_argnums(False, (0,), backend="gpu") == ()
    # engine resolves its backend once at construction
    engine = BatchedRoundEngine(make_toy_task(), get_strategy("fedbwo"),
                                _hp(), _clients())
    assert engine.backend == jax.default_backend()
    explicit = BatchedRoundEngine(make_toy_task(),
                                  get_strategy("fedbwo"), _hp(),
                                  _clients(), backend="cpu")
    assert explicit.backend == "cpu"
