"""The check on the CPU with the limits of the paper-CNN cell, at a size
a test run holds: a sound run is correct; the control (the reference in
bfloat16 in the program's place) and each planted fault are not."""
import json
import time

import jax.numpy as jnp
import pytest

import repro.data.synthetic as synthetic
from fedbench import check, harness, spec
from fedbench.test_fedbench_faults import unchanged_state, wrong_winner

LIMITS_FROM = "cnn_fedbwo_paper"


def tiny_cell(tmp_path):
    real = spec.workload(LIMITS_FROM)
    cell = {
        "name": "tiny_cnn", "config": real["config"], "chips": 1,
        "why": "CPU-sized copy of the paper-CNN cell",
        "traffic": {"n_train": 100, "n_test": 50, "n_clients": 2,
                    "batch_size": 10, "partition": "iid"},
        "fl": {"strategy": "fedbwo", "local_epochs": 1, "lr": 0.01,
               "mh_pop": 2, "mh_generations": 1, "eval_every": 1,
               "engine": "batched"},
        "protocol": real["protocol"],
        "window": {"round_s_hint": 0.05, "check_rounds": 2},
        "limits": real["limits"],
    }
    path = tmp_path / "tiny_cnn.json"
    path.write_text(json.dumps(cell))
    return path


def run(path, seed=5):
    return harness.run_cell(str(path), seed, 0.1, False,
                            time.perf_counter(), require_tpu=False)


def half_batch(monkeypatch):
    """Training steps see the first half of each batch, and take the mean
    over it."""
    make = synthetic.cnn_task

    def broken(*a, **k):
        task = make(*a, **k)

        def loss_fn(params, batch):
            if "rng" in batch:
                half = batch["labels"].shape[0] // 2
                batch = dict(batch, images=batch["images"][:half],
                             labels=batch["labels"][:half])
            return task.loss_fn(params, batch)
        return task._replace(loss_fn=loss_fn)
    monkeypatch.setattr(synthetic, "cnn_task", broken)


def test_cnn_sound_run_is_correct(tmp_path):
    out = run(tiny_cell(tmp_path))
    assert out.result["correct"], out.checks


def test_cnn_control_in_bfloat16_is_not_correct(tmp_path):
    cell = spec.workload(str(tiny_cell(tmp_path)))
    p = harness.prepare(cell, 6)
    p.exp = None
    ref = harness.reference(p)
    ref_run = harness.follow(ref, p.first)
    control = harness.follow(
        harness.reference(p, dtype=jnp.bfloat16, precision=None), p.first)
    numbers = harness.judge(control, ref, ref_run, p.proto.is_fedx)
    checks = check.compare(numbers, cell["limits"])
    assert not check.passed(checks), checks


@pytest.mark.parametrize("fault", [unchanged_state, half_batch,
                                   wrong_winner])
def test_cnn_fault_turns_correct_false(fault, tmp_path, monkeypatch):
    fault(monkeypatch)
    out = run(tiny_cell(tmp_path))
    assert not out.result["correct"], out.checks
