"""The image path, pinned.

``testdata/image_golden.json`` holds what both image cells produce at a
small size (4 clients, 400 images, 100 test images, the cell's own
protocol), on the CPU: a hash of every array ``gen.make_dataset`` makes,
and a 2-round reference run's scores, winners, test losses and
accuracies, and the norm of every weight tensor after each round.  A
change to the generator or to the reference's image arithmetic shows
here.  Regenerate the file only with a change that means to move the
yardstick:

    PYTHONPATH=src:benchmarks python3 \
        benchmarks/fedbench/test_fedbench_image_path.py > FILE
"""
import hashlib
import json

import jax
import numpy as np
import pytest

from fedbench import gen, harness, spec
from fedbench.reference import Reference

GOLDEN = spec.HERE / "testdata" / "image_golden.json"
CELLS = ["cnn_fedbwo_paper", "mlp2nn_fedbwo_noniid"]
SMALL = {"n_clients": 4, "n_train": 400, "n_test": 100}
SEED = 2600000011
ROUNDS = 2
# The reference's numbers are float32 arithmetic of XLA:CPU; a machine
# whose vector code fuses or orders a sum otherwise may differ in the
# last bits, never by more than this.  Any change of the arithmetic
# itself (a precision, a key, an order of draws) moves them by far more.
RTOL = 1e-5


def small_cell(name: str) -> dict:
    cell = spec.workload(name)
    cell["traffic"].update(SMALL)
    return cell


def dataset(cell: dict) -> gen.Dataset:
    return gen.make_dataset(gen.Traffic.from_dict(cell["traffic"]), SEED)


def hashes(data: gen.Dataset) -> dict:
    out = {}
    for who, arrays in [(f"client{k}", c) for k, c in
                        enumerate(data.clients)] + [("test", data.test)]:
        for key in sorted(arrays):
            a = np.ascontiguousarray(arrays[key])
            h = hashlib.sha256(f"{a.shape} {a.dtype.str}".encode())
            h.update(a.tobytes())
            out[f"{who}.{key}"] = h.hexdigest()
    return out


def readings(cell: dict, data: gen.Dataset) -> dict:
    ref = Reference(spec.model(cell["config"]), spec.config(cell["config"]),
                    harness.protocol(cell), data.clients, data.test,
                    data.server_seed)
    run = ref.run(ROUNDS, keep_after=list(range(1, ROUNDS + 1)))
    return {
        "server_seed": data.server_seed,
        "scores": [[float(s) for s in l["scores"]] for l in run.logs],
        "best": [int(l["best"]) for l in run.logs],
        "eval_loss": [float(l["eval_loss"]) for l in run.logs],
        "eval_acc": [float(l["eval_acc"]) for l in run.logs],
        "norms": [[float(np.linalg.norm(np.asarray(leaf, np.float64)))
                   for leaf in jax.tree.leaves(run.snapshots[r])]
                  for r in range(1, ROUNDS + 1)],
    }


def golden() -> dict:
    with open(GOLDEN) as f:
        return json.load(f)


@pytest.mark.parametrize("name", CELLS)
def test_generator_arrays_are_unchanged(name):
    assert hashes(dataset(small_cell(name))) == golden()[name]["hashes"]


@pytest.mark.parametrize("name", CELLS)
def test_reference_run_is_unchanged(name):
    cell = small_cell(name)
    got, want = readings(cell, dataset(cell)), golden()[name]["reference"]
    assert got["server_seed"] == want["server_seed"]
    assert got["best"] == want["best"]
    for key in ("scores", "eval_loss", "eval_acc", "norms"):
        np.testing.assert_allclose(np.asarray(got[key]),
                                   np.asarray(want[key]), rtol=RTOL,
                                   err_msg=key)


if __name__ == "__main__":
    out = {}
    for name in CELLS:
        cell = small_cell(name)
        data = dataset(cell)
        out[name] = {"hashes": hashes(data),
                     "reference": readings(cell, data)}
    print(json.dumps(out, indent=1))
