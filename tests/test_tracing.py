"""The FL round's layer names: ``fl.*`` named scopes in the compiled
programs' op metadata, ``Server.*`` host spans in a profiler trace, and
the per-round ledger of real against computed local SGD steps."""
import contextlib
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ClientHP, Server, get_strategy, tracing
from repro.data.loader import batch_dataset

from conftest import make_toy_data, make_toy_task

BATCH = 4
SIZES = (24, 12, 36, 8)              # ragged: 6, 3, 9, 2 batches
EPOCHS = 2


def _hp(vectorize="auto"):
    return ClientHP(local_epochs=EPOCHS, mh_pop=3, mh_generations=1,
                    lr=0.05, fitness_batches=2, vectorize=vectorize)


def _clients(sizes=SIZES):
    raw = make_toy_data(jax.random.PRNGKey(0), sum(sizes))
    edges = np.cumsum((0,) + tuple(sizes))
    return [batch_dataset(jax.tree.map(lambda a: a[lo:hi], raw), BATCH)
            for lo, hi in zip(edges[:-1], edges[1:])]


def _eval_data():
    return make_toy_data(jax.random.PRNGKey(9), 16)


def _server(strategy="fedbwo", sizes=SIZES, engine="batched",
            vectorize="auto", **kw):
    ratio = kw.pop("client_ratio", 1.0)
    return Server(make_toy_task(), get_strategy(strategy,
                                                client_ratio=ratio),
                  _hp(vectorize), _clients(sizes), jax.random.PRNGKey(3),
                  engine=engine, **kw)


def _scopes(fn, *args):
    """The ``fl.*`` scopes named in the compiled program's op metadata."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    paths = re.findall(r'op_name="([^"]*)"', text)
    return {s for s in tracing.SCOPES if any(s in p for p in paths)}


def _round_args(server):
    eng = server._engine
    keys = jax.random.split(jax.random.PRNGKey(1), server.n_clients)
    return server.global_params, eng.data, eng.mask, keys


FEDX = {tracing.LOCAL_SGD, tracing.BWO_FITNESS, tracing.BWO_EVOLVE,
        tracing.SERVER_REDUCE}


@pytest.mark.parametrize("vectorize", ["vmap", "scan:2"])
@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_fedx_round_carries_its_scopes(vectorize, backend):
    """Unrolled (CPU) and rolled (chip) client loops, under vmap and a
    chunked scan over clients."""
    from repro.core.engine import _fedx_round_body
    from repro.metaheuristics import REGISTRY
    server = _server(vectorize=vectorize)
    body = _fedx_round_body(make_toy_task(), _hp(vectorize),
                            REGISTRY["bwo"](), vectorize, masked=True,
                            backend=backend)
    assert _scopes(body, *_round_args(server)) == FEDX


def test_fused_block_with_eval_carries_every_scope():
    server = _server(rounds_per_dispatch=2)
    block = server._engine.fused_rounds(2, eval_every=1)
    eng = server._engine
    args = (server.global_params, server.rng, eng.data, eng.mask,
            _eval_data(), jnp.asarray(0, jnp.int32))
    assert _scopes(block, *args) == set(tracing.SCOPES)


def test_fedavg_round_and_eval_carry_their_scopes():
    server = _server("fedavg")
    assert _scopes(server._engine._round, *_round_args(server)) == {
        tracing.LOCAL_SGD, tracing.BWO_FITNESS, tracing.SERVER_REDUCE}
    assert _scopes(server._eval, server.global_params,
                   _eval_data()) == {tracing.EVAL}


def _operations(text):
    """Compiled HLO text less its source-location tables and op
    metadata: the operations alone."""
    if "StackFrames" in text:
        text = text[text.index("\n\n", text.index("StackFrames")):]
    return re.sub(r", metadata=\{[^}]*\}", "", text)


@pytest.mark.parametrize("strategy", ["fedbwo", "fedavg"])
def test_scopes_change_no_operation(strategy, monkeypatch):
    """Without the scopes the fused block compiles to the same
    operations and returns the same bits."""
    def block_of():
        server = _server(strategy, rounds_per_dispatch=2)
        eng = server._engine
        args = (server.global_params, server.rng, eng.data, eng.mask,
                _eval_data(), jnp.asarray(0, jnp.int32))
        fn = eng.fused_rounds(2, eval_every=1)
        text = _operations(fn.lower(*args).compile().as_text())
        return text, jax.device_get(fn(*args))

    text, out = block_of()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain_text, plain_out = block_of()
    assert text == plain_text
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(plain_out)):
        np.testing.assert_array_equal(a, b)


# ----------------------------------------------------------- host spans --
def _host_spans(log_dir):
    from jax.profiler import ProfileData
    (path,) = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith("Server.")]


def test_server_spans_nest_in_a_profile(tmp_path):
    """One pipelined fused drive plus one single round and one
    evaluation, traced on the CPU: every ``Server.*`` span is there,
    and each child lies inside a parent of its own name."""
    server = _server(rounds_per_dispatch=2)
    eval_data = _eval_data()
    server.run_pipelined(4, eval_data)          # compile outside the trace
    server.run_round()
    server.evaluate(eval_data)
    with jax.profiler.trace(str(tmp_path)):
        server.run_pipelined(4, eval_data)
        server.run_round()
        server.evaluate(eval_data)
    spans = _host_spans(tmp_path)
    names = [n for n, _, _ in spans]
    for parent, children in [
            ("Server.dispatch_block", ()),
            ("Server.finish_block", (".sync", ".process")),
            ("Server.run_round", (".sync",)),
            ("Server.evaluate", (".sync",))]:
        assert parent in names
        for child in children:
            name = parent + child
            inner = [s for s in spans if s[0] == name]
            assert inner, name
            for _, a, b in inner:
                assert any(p == parent and pa <= a and b <= pb
                           for p, pa, pb in spans), name
    assert names.count("Server.dispatch_block") == 2
    assert names.count("Server.finish_block.sync") == 2


# ---------------------------------------------------------- step ledger --
def _batches(sizes=SIZES):
    return np.asarray(sizes) // BATCH


def test_ragged_fedx_steps_are_the_mask_sums():
    server = _server()
    mask = np.asarray(server._engine.mask)
    assert server._engine.padded
    server.run_round()
    server.run_block(2)
    assert server.meter.sgd_steps == [
        (EPOCHS * int(mask.sum()), EPOCHS * mask.size)] * 3
    s = server.meter.sgd_step_summary()
    assert s["real_frac"] == pytest.approx(_batches().sum()
                                           / (len(SIZES) * 9))
    assert "sgd_steps" not in server.meter.summary()


def test_fedavg_partial_fused_steps_follow_the_participants():
    server = _server("fedavg", client_ratio=0.5, rounds_per_dispatch=2)
    infos = server.run_block(2)
    mask = np.asarray(server._engine.mask)
    want = [(EPOCHS * int(mask[i["participants"]].sum()),
             EPOCHS * 2 * mask.shape[1]) for i in infos]
    assert server.meter.sgd_steps == want
    info = server.run_round()
    assert server.meter.sgd_steps[-1] == (
        EPOCHS * int(mask[info["participants"]].sum()),
        EPOCHS * 2 * mask.shape[1])


@pytest.mark.parametrize("strategy", ["fedbwo", "fedavg"])
def test_sequential_engine_computes_only_real_steps(strategy):
    server = _server(strategy, engine="sequential", client_ratio=1.0)
    server.run_round()
    real = EPOCHS * int(_batches().sum())
    assert server.meter.sgd_steps == [(real, real)]
    assert server.meter.sgd_step_summary()["real_frac"] == 1.0


def test_uniform_clients_compute_no_padding():
    server = _server(sizes=(16, 16, 16))
    server.run_round()
    assert server.meter.sgd_steps == [(EPOCHS * 12, EPOCHS * 12)]


# ----------------------------------------------------------- row ledger --
@pytest.mark.parametrize("engine", ["batched", "sequential"])
def test_bwo_rows_count_parent_rows_of_two_rounds(engine):
    """pop 3, 1 generation: BWO mutates n_par = 2 of 3 rows and draws
    rows 1..2 of its initial population, per client and round."""
    server = _server(engine=engine)
    assert server.engine == engine
    server.run_round()
    server.run_round()
    n = len(SIZES)
    assert server.meter.bwo_rows == [(n * 2, n * 3, n * 2, n * 3)] * 2
    s = server.meter.bwo_row_summary()
    assert s == {"rounds": 2, "drawn": 2 * n * 2, "full": 2 * n * 3,
                 "drawn_frac": pytest.approx(2 / 3),
                 "init_drawn": 2 * n * 2, "init_full": 2 * n * 3}
    assert "bwo_rows" not in server.meter.summary()


def test_bwo_rows_follow_fused_blocks_and_skip_fedavg():
    server = _server(rounds_per_dispatch=2)
    server.run_block(2)
    assert len(server.meter.bwo_rows) == 2
    fedavg = _server("fedavg")
    fedavg.run_round()
    assert fedavg.meter.bwo_rows == []
