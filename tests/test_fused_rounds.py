"""Multi-round fusion: R rounds scanned into one dispatch (DESIGN.md §6).

Parity here is BIT-exact, not approximate: every block derives its
rounds' threefry key schedule on device, so a block of R rounds must
reproduce R ``run_round`` calls (R blocks of one) bit for bit — global
params, scores, winner indices / participant sets, the PRNG carry, and
the CommMeter ledger.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ClientHP, Server, Task, get_strategy
from repro.core.knobs import (DEFAULT_ROUNDS_PER_DISPATCH,
                              parse_rounds_per_dispatch,
                              validate_rounds_per_dispatch)
from repro.core.protocol import StopConditions, run_federated
from repro.data.loader import batch_dataset
from repro.data.partition import partition_dirichlet

from conftest import ReferenceRounds, make_toy_data, make_toy_task

N_CLIENTS = 5
R = 5


def _clients(n=400, n_clients=N_CLIENTS, batch=8):
    from repro.data.partition import partition_iid
    data = make_toy_data(jax.random.PRNGKey(0), n)
    return [batch_dataset(d, batch) for d in
            partition_iid(jax.random.PRNGKey(1), data, n_clients)]


def _hp():
    return ClientHP(local_epochs=1, mh_pop=4, mh_generations=2, lr=0.05,
                    fitness_batches=2)


def _server(strategy, clients, rounds_per_dispatch=1, task=None, **kw):
    return Server(task or make_toy_task(), get_strategy(strategy, **kw),
                  _hp(), clients, jax.random.PRNGKey(3),
                  rounds_per_dispatch=rounds_per_dispatch)


def _assert_trees_bitexact(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("strategy,kw", [("fedbwo", {}),
                                         ("fedavg", {}),
                                         ("fedavg", {"client_ratio": 0.6})])
def test_fused_block_bitexact_vs_single_rounds(strategy, kw):
    """One R-round fused dispatch == R run_round calls (blocks of one),
    bit for bit: params, scores, winners/participants, and the
    CommMeter ledger."""
    clients = _clients()
    single = _server(strategy, clients, **kw)
    fused = _server(strategy, clients, rounds_per_dispatch=R, **kw)
    infos_s = [single.run_round() for _ in range(R)]
    infos_f = fused.run_block(R)
    assert len(infos_f) == R
    _assert_trees_bitexact(single.global_params, fused.global_params)
    for a, b in zip(infos_s, infos_f):
        if strategy == "fedbwo":
            assert a["best_client"] == b["best_client"]
            assert a["scores"] == b["scores"]        # bit-exact floats
            assert a["score"] == b["score"]
        else:
            assert a["participants"] == b["participants"]
        assert b["engine"] == "fused"
    # identical per-round byte ledger (Eqs. 1-2), entry for entry
    assert single.meter.uplink == fused.meter.uplink
    assert single.meter.downlink == fused.meter.downlink
    assert single.meter.summary() == fused.meter.summary()


def test_fused_block_bitexact_on_ragged_dirichlet():
    """The fused scan composes with the pad+mask (masked) client update:
    bit-exact on a ragged Dirichlet partition too (DESIGN.md §5+§6)."""
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
    lens = [jax.tree.leaves(c)[0].shape[0] for c in clients]
    assert len(set(lens)) > 1, f"partition not ragged: {lens}"
    single = _server("fedbwo", clients, task=labeled_task())
    fused = _server("fedbwo", clients, rounds_per_dispatch=R,
                    task=labeled_task())
    assert fused._engine.padded
    infos_s = [single.run_round() for _ in range(R)]
    infos_f = fused.run_block(R)
    _assert_trees_bitexact(single.global_params, fused.global_params)
    for a, b in zip(infos_s, infos_f):
        assert a["best_client"] == b["best_client"]
        assert a["scores"] == b["scores"]
    assert single.meter.uplink == fused.meter.uplink


def test_fused_key_schedule_matches_host_split_sequence():
    """The scan carries the rng and re-derives split(rng, n+2) per round
    on device; after R rounds the server PRNG key must equal the
    host-side sequence's, so fused and unfused runs stay exchangeable
    mid-experiment."""
    clients = _clients()
    single = _server("fedbwo", clients)
    fused = _server("fedbwo", clients, rounds_per_dispatch=R)
    for _ in range(R):
        single.run_round()
    fused.run_block(R)
    np.testing.assert_array_equal(np.asarray(single.rng),
                                  np.asarray(fused.rng))
    # ...and a subsequent single round on the fused server still matches
    a, b = single.run_round(), fused.run_round()
    assert a["scores"] == b["scores"]
    _assert_trees_bitexact(single.global_params, fused.global_params)


def test_on_device_eval_cadence():
    """eval_every=k folds task.loss_fn into the scan: evaluated rounds
    carry eval_loss/eval_acc matching Server.evaluate on a twin server;
    skipped rounds carry none; the block's last round always evaluates."""
    clients = _clients()
    test = make_toy_data(jax.random.PRNGKey(7), 100)
    twin = _server("fedbwo", clients)
    fused = _server("fedbwo", clients, rounds_per_dispatch=R)
    infos = fused.run_block(R, eval_data=test, eval_every=2)
    evaluated = [i for (i, info) in enumerate(infos) if "eval_acc" in info]
    # rounds 2 and 4 (cadence) plus round 5 (block boundary), 0-indexed
    assert evaluated == [1, 3, 4]
    for i, info in enumerate(infos):
        twin.run_round()
        if "eval_acc" in info:
            loss, acc = twin.evaluate(test)
            assert math.isclose(info["eval_loss"], loss, rel_tol=1e-6)
            assert math.isclose(info["eval_acc"], acc, rel_tol=1e-6)


def test_run_federated_fused_driver_matches_unfused():
    """End-to-end through run_federated: same accuracy curve and round
    count with rounds_per_dispatch=R (pipelined blocks) as with 1 (tau
    high enough that no early stop hits, so block atomicity doesn't
    change the trajectory); leftover rounds (max_rounds % R) run on the
    single-round path."""
    clients = _clients()
    test = make_toy_data(jax.random.PRNGKey(7), 100)
    stop = StopConditions(max_rounds=7, patience=100, tau=1.1)
    logs = {}
    for rpd in (1, R):
        server = _server("fedbwo", clients, rounds_per_dispatch=rpd)
        logs[rpd] = run_federated(server, test, stop)
    assert len(logs[1]) == len(logs[R]) == 7
    for a, b in zip(logs[1], logs[R]):
        assert math.isclose(a.test_acc, b.test_acc, rel_tol=1e-6)
        assert math.isclose(a.test_loss, b.test_loss, rel_tol=1e-6)
    # the 2 leftover rounds run as blocks of one
    assert [l.info["engine"] for l in logs[R]] == \
        ["fused"] * 5 + ["batched"] * 2


def test_fused_fedavg_subsample_compiles_once_per_m():
    """The fused block gathers participants on device at fixed m: one
    traced participant count for the whole run, equal to m."""
    clients = _clients(480, 6)
    server = _server("fedavg", clients, rounds_per_dispatch=R,
                     client_ratio=0.5)
    assert server._engine.n_participants == 3
    for _ in range(2):
        server.run_block(R)
    assert server._engine.traced_participant_counts == [3]


def test_rounds_per_dispatch_knob():
    assert parse_rounds_per_dispatch("auto") is None
    assert parse_rounds_per_dispatch(None) is None
    assert parse_rounds_per_dispatch(4) == 4
    assert parse_rounds_per_dispatch("4") == 4
    for bad in (0, -1, "x", 1.5):
        with pytest.raises(ValueError):
            validate_rounds_per_dispatch(bad)
    clients = _clients()
    auto = _server("fedbwo", clients, rounds_per_dispatch="auto")
    assert auto.rounds_per_dispatch == DEFAULT_ROUNDS_PER_DISPATCH
    assert _server("fedbwo", clients).rounds_per_dispatch == 1


@pytest.mark.parametrize("ratio", [1.0, 0.6])
def test_fedavg_scores_parity_across_engines(ratio):
    """FedAvg infos carry per-participant scores on the reference loop,
    the block of one AND the block of R — the fused engine used to drop
    them on the host side even though the device computed them."""
    clients = _clients()
    ref = ReferenceRounds(make_toy_task(),
                          get_strategy("fedavg", client_ratio=ratio),
                          _hp(), clients, jax.random.PRNGKey(3))
    single = _server("fedavg", clients, client_ratio=ratio)
    fused = _server("fedavg", clients, rounds_per_dispatch=R,
                    client_ratio=ratio)
    infos_ref = [ref.run_round() for _ in range(R)]
    infos_s = [single.run_round() for _ in range(R)]
    infos_f = fused.run_block(R)
    for a, b, c in zip(infos_ref, infos_s, infos_f):
        assert a["participants"] == b["participants"] == c["participants"]
        for info in (a, b, c):
            assert len(info["scores"]) == len(info["participants"])
            assert all(isinstance(s, float) for s in info["scores"])
        # blocks of one and of R run the same round body -> bit-exact;
        # the reference loop differs only by reduction order
        assert b["scores"] == c["scores"]
        np.testing.assert_allclose(a["scores"], b["scores"], rtol=1e-5)
