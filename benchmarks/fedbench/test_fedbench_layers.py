"""Device time per FL layer (``layers.py``) on a synthesized trace: the
innermost ``fl.*`` scope through transform wrappers, metadata-less ops
inside a scoped ``while``, unscoped ops, and the layers summing to the
ops' own time."""
import re

import jax
import jax.numpy as jnp
import pytest

from fedbench import layers, traces

MS = 1e6   # ns
SGD = "jit(block_fn)/while/body/vmap(fl.local_sgd)"
EVOLVE = "jit(block_fn)/while/body/vmap(fl.bwo_evolve)"


@pytest.mark.parametrize("path,scope", [
    (SGD + "/transpose(jvp())/dot_general", "fl.local_sgd"),
    ("jit(f)/transpose(jvp(fl.local_sgd))/mul", "fl.local_sgd"),
    (EVOLVE + "/fl.bwo_fitness/while/body/closed_call/dot_general",
     "fl.bwo_fitness"),
    (EVOLVE + "/add", "fl.bwo_evolve"),
    ("jit(f)/fl.server_reduce/argmin", "fl.server_reduce"),
    ("jit(eval_loss)/fl.eval/log_softmax", "fl.eval"),
    ("jit(f)/vmap(fl.bwo_evolve)/fl.other/add", "fl.bwo_evolve"),
    ("jit(f)/fl.local_sgd_extra/add", None),
    ("jit(_threefry_split)/threefry2x32", None),
    ("", None),
])
def test_scope_of_looks_through_transforms(path, scope):
    assert layers.scope_of(path) == scope


def synthetic():
    ops = [("while.1", 0, 100 * MS, SGD + "/while"),
           ("fusion.2", 10 * MS, 30 * MS, SGD + "/transpose(jvp())/dot"),
           ("copy.3", 50 * MS, 10 * MS, None),          # inside while.1
           ("while.4", 100 * MS, 100 * MS, EVOLVE + "/while"),
           ("fusion.5", 110 * MS, 40 * MS,
            EVOLVE + "/fl.bwo_fitness/while/body/dot_general"),
           ("copy.6", 120 * MS, 10 * MS, None),         # inside fusion.5
           ("fusion.7", 160 * MS, 10 * MS, EVOLVE + "/add"),
           ("copy.8", 200 * MS, 10 * MS, None),         # enclosed by none
           ("fusion.9", 210 * MS, 10 * MS, "jit(f)/threefry2x32"),
           ("fusion.10", 230 * MS, 20 * MS, "jit(f)/fl.eval/dot"),
           ("fusion.11", 300 * MS, 5 * MS, "jit(f)/fl.eval/dot")]
    return {"/device:TPU:0": ops}, (0, 240 * MS)


def test_layers_split_the_ops_own_time():
    devices, window = synthetic()
    got = layers.per_layer(layers.own_times(devices, window))
    assert got == {"fl.local_sgd": pytest.approx(100 * MS),
                   "fl.bwo_fitness": pytest.approx(40 * MS),
                   "fl.bwo_evolve": pytest.approx(60 * MS),
                   "fl.server_reduce": 0.0,
                   "fl.eval": pytest.approx(10 * MS),   # clipped at 240
                   "unscoped": pytest.approx(20 * MS)}
    assert list(got)[-1] == layers.UNSCOPED
    lo, hi = window
    clipped = [(n, max(s, lo), min(s + d, hi))
               for n, s, d, _ in devices["/device:TPU:0"]
               if min(s + d, hi) > max(s, lo)]
    assert sum(got.values()) == pytest.approx(
        sum(traces.self_times(clipped).values()))


def test_own_times_keep_each_instruction():
    devices, window = synthetic()
    own = layers.own_times(devices, window)
    assert own[("fl.local_sgd", "while.1")] == pytest.approx(60 * MS)
    assert own[("fl.local_sgd", "copy.3")] == pytest.approx(10 * MS)
    assert own[("fl.bwo_fitness", "copy.6")] == pytest.approx(10 * MS)
    assert own[("unscoped", "copy.8")] == pytest.approx(10 * MS)
    assert own[("unscoped", "fusion.9")] == pytest.approx(10 * MS)


def test_layers_sum_over_devices():
    devices, window = synthetic()
    two = {"/device:TPU:0": devices["/device:TPU:0"],
           "/device:TPU:1": devices["/device:TPU:0"]}
    one = layers.per_layer(layers.own_times(devices, window))
    both = layers.per_layer(layers.own_times(two, window))
    assert both == {k: pytest.approx(2 * v) for k, v in one.items()}


def test_attach_finds_each_op_in_its_program():
    paths = {"jit_block_fn(123)": {"fusion.1": "a/fl.local_sgd/x",
                                   "copy.2": None},
             "jit_eval_loss(77)": {"fusion.1": "jit(eval_loss)/fl.eval/d"}}
    modules = [("jit_eval_loss(77)", 50, 20), ("jit_block_fn(123)", 0, 40)]
    ops = [("fusion.1", 5, 10), ("copy.2", 20, 5), ("fusion.1", 55, 5),
           ("fusion.1", 45, 2), ("fusion.3", 10, 1)]
    assert layers.attach(modules, ops, paths) == [
        ("fusion.1", 5, 10, "a/fl.local_sgd/x"),
        ("fusion.3", 10, 1, None),
        ("copy.2", 20, 5, None),
        ("fusion.1", 45, 2, None),                   # between programs
        ("fusion.1", 55, 5, "jit(eval_loss)/fl.eval/d")]


def _scoped(x):
    with jax.named_scope("fl.local_sgd"):
        y = jnp.tanh(x @ x)
    with jax.named_scope("fl.eval"):
        return jnp.sum(jax.vmap(jnp.dot)(y, y))


def test_hlo_paths_read_the_programs_a_trace_keeps(tmp_path):
    """The trace's metadata plane holds the compiled HLO of each
    program it ran, named like its "XLA Modules" events."""
    fn = jax.jit(_scoped)
    x = jnp.ones((8, 8))
    fn(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        fn(x).block_until_ready()
    with open(traces.find_xplane(str(tmp_path)), "rb") as f:
        table = layers.hlo_paths(f.read())
    (name,) = [k for k in table if k.startswith("jit__scoped(")]
    got = table[name]
    assert {layers.scope_of(p) for p in got.values() if p} >= {
        "fl.local_sgd", "fl.eval"}
    text = fn.lower(x).compile().as_text()
    assert set(got) == set(re.findall(r"^\s*(?:ROOT )?%?([\w.\-]+) = ",
                                      text, re.M))


def test_fields_read_varints_and_skip_fixed_width():
    # field 1 varint 300, field 2 fixed64, field 3 bytes "ab", field 4
    # fixed32
    msg = bytes([0x08, 0xAC, 0x02, 0x11]) + bytes(8) + \
        bytes([0x1A, 0x02]) + b"ab" + bytes([0x25]) + bytes(4)
    got = [(f, v if isinstance(v, int) else bytes(v))
           for f, v in layers._fields(msg)]
    assert got == [(1, 300), (3, b"ab")]
