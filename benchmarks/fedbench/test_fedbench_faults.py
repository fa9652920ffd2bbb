"""A whole run on the CPU (the look for a chip skipped) with the timed
path broken underneath: each fault the cells can have turns ``correct``
false.  The cells run on one chip, so no exchange between chips can be
left out."""
import jax
import pytest

import repro.core.engine as engine
import repro.data.synthetic as synthetic
from fedbench.test_fedbench_check import run, tiny_cell


def unchanged_state(monkeypatch):
    """Every client hands back the global weights it was given."""
    make = engine.make_client_update

    def broken(task, hp, mh=None, masked=False, backend=None):
        update = make(task, hp, mh, masked=masked, backend=backend)
        if masked:
            return lambda p, d, m, k: (update(p, d, m, k)[0], p)
        return lambda p, d, k: (update(p, d, k)[0], p)
    monkeypatch.setattr(engine, "make_client_update", broken)


def half_batch(monkeypatch):
    """Training steps see the first half of each batch, and take the mean
    over it."""
    make = synthetic.mlp_task

    def broken(*a, **k):
        task = make(*a, **k)

        def loss_fn(params, batch):
            if "rng" in batch:
                batch = jax.tree.map(lambda x: x[:x.shape[0] // 2]
                                     if x.ndim else x, batch)
            return task.loss_fn(params, batch)
        return task._replace(loss_fn=loss_fn)
    monkeypatch.setattr(synthetic, "mlp_task", broken)


def wrong_winner(monkeypatch):
    """The round reports another client than the lowest score as its
    winner."""
    body = engine._fedx_round_body

    def broken(*a, **k):
        round_fn = body(*a, **k)

        def fn(params, data, mask, keys):
            winner, scores, best = round_fn(params, data, mask, keys)
            return winner, scores, (best + 1) % scores.shape[0]
        return fn
    monkeypatch.setattr(engine, "_fedx_round_body", broken)


@pytest.mark.parametrize("fault", [unchanged_state, half_batch,
                                   wrong_winner])
def test_fault_turns_correct_false(fault, tmp_path, monkeypatch):
    fault(monkeypatch)
    out = run(tiny_cell(tmp_path))
    assert not out.result["correct"], out.checks
