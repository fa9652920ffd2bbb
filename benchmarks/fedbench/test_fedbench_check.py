"""The check on the CPU at a size a test run holds, with the limits of
the FedAvg-2NN cell: a sound run is correct, and the control (the
reference computed in bfloat16, put in the program's place) is not."""
import json
import time

import jax.numpy as jnp

from fedbench import check, harness, spec
from fedbench.reference import RunRecord

LIMITS_FROM = "mlp2nn_fedbwo_noniid"


def tiny_cell(tmp_path):
    real = spec.workload(LIMITS_FROM)
    cell = {
        "name": "tiny_2nn", "config": real["config"], "chips": 1,
        "why": "CPU-sized copy of the 2NN cell",
        "traffic": {"n_train": 1200, "n_test": 200, "n_clients": 4,
                    "batch_size": 10, "partition": "dirichlet",
                    "dirichlet_alpha": 0.5, "partition_seed": 1},
        "fl": {"strategy": "fedbwo", "local_epochs": 1, "lr": 0.02,
               "mh_pop": 3, "mh_generations": 2, "eval_every": 1,
               "rounds_per_dispatch": 2, "engine": "batched"},
        "protocol": real["protocol"],
        "window": {"round_s_hint": 0.05, "check_rounds": 2},
        "limits": real["limits"],
    }
    path = tmp_path / "tiny_2nn.json"
    path.write_text(json.dumps(cell))
    return path


def run(path, seed=5):
    return harness.run_cell(str(path), seed, 0.1, False,
                            time.perf_counter(), require_tpu=False)


def test_sound_run_is_correct(tmp_path):
    out = run(tiny_cell(tmp_path))
    assert out.result["correct"], out.checks
    assert list(out.result)[-1] == "checks"
    assert out.result["failed"] == 0 and out.result["attempted"] >= 2


def test_control_in_bfloat16_is_not_correct(tmp_path):
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


def test_first_round_loss_follows_the_adopted_client(tmp_path):
    """A run that adopts another client than the reference in its first
    round (a near-tie flipped by rounding) reads ``loss_gap_r0`` 0 when
    its test loss is that client's, where ``loss_gap`` reads the two
    clients' difference."""
    cell = spec.workload(str(tiny_cell(tmp_path)))
    p = harness.prepare(cell, 7)
    p.exp = None
    ref = harness.reference(p)
    ref_run = harness.follow(ref, p.first)
    other = (ref_run.logs[0]["best"] + 1) % p.traffic.n_clients
    loss, acc = ref.evaluate(ref.first_round(other))
    flipped = RunRecord(w0=ref_run.w0, snapshots=ref_run.snapshots,
                        logs=[dict(ref_run.logs[0], best=other,
                                   eval_loss=loss, eval_acc=acc)]
                        + ref_run.logs[1:])
    numbers = harness.judge(flipped, ref, ref_run, True)
    assert numbers["loss_gap_r0"] < 1e-6
    assert numbers["loss_gap"] > 1e-3
    assert harness.judge(ref_run, ref, ref_run, True)["loss_gap_r0"] < 1e-6
