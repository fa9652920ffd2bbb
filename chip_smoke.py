#!/usr/bin/env python3
"""Bring-up smoke: the paper's FedBWO setting on one TPU chip.

Drives the main path ``FLConfig -> build_experiment -> Experiment.run``
at the paper's scale (§IV-A): the paper CNN at its published width
(2,465,322 parameters), CIFAR-10-sized synthetic data (50,000 train and
10,000 test images of 32x32x3, generated from a seed), IID over 10
clients, batch 10, lr 0.0025, and the ``FLConfig`` defaults for local
epochs, population and generations.

  A  FedBWO: single-dispatch rounds, then fused 2-round blocks
     (pipelined, as ``pipeline_blocks="auto"`` chooses)
  B  FedAvg with C = 1.0
  C  the Pallas ``bwo_evolve`` kernel at the paper CNN's D against its
     pure-jnp reference, compiled for the chip (not interpreted)
  D  one reduced FedBWO round on the chip against the same round on the
     host CPU backend of this process

    python chip_smoke.py                # one chip, phases A-D
    python chip_smoke.py --four-chips   # only the sharded round on a
                                        # 4-chip mesh vs one chip

Lines before the last are bring-up observations, not benchmark results.
The last line is one JSON object naming the device.  The script exits
non-zero, printing no such line, when JAX finds no TPU or any check
fails; it never falls back to the CPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# Phase D compares against this process's host CPU backend, so keep it
# available when the platform list is pinned without it.
_platforms = os.environ.get("JAX_PLATFORMS")
if _platforms and "cpu" not in _platforms.split(","):
    os.environ["JAX_PLATFORMS"] = _platforms + ",cpu"

import jax                                                   # noqa: E402
import jax.numpy as jnp                                      # noqa: E402
import numpy as np                                           # noqa: E402

from repro.core import (FLConfig, build_experiment,          # noqa: E402
                        get_strategy, normalized_cost,
                        stack_clients)
from repro.core.comm import SCORE_BYTES                      # noqa: E402
from repro.core.distributed import (make_fedavg_round,       # noqa: E402
                                    make_fedx_round)
from repro.core.engine import (make_batched_fedavg_round,    # noqa: E402
                               make_batched_fedx_round)
from repro.data.loader import client_batches                 # noqa: E402
from repro.data.partition import partition_iid               # noqa: E402
from repro.data.synthetic import cnn_task, make_cifar_like   # noqa: E402
from repro.kernels.bwo_evolve.ops import (bwo_evolve,        # noqa: E402
                                          bwo_evolve_reference)
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_host_mesh                 # noqa: E402

# TPU f32 matmuls and convolutions multiply in bf16 at JAX's default
# precision (unit roundoff 2^-8 ~= 3.9e-3) where the CPU multiplies in
# f32.  A score is a mean cross-entropy after a few SGD steps and BWO
# generations, so chip and CPU scores may differ by a few roundoffs:
# allow five.
SCORE_RTOL = 2e-2
# A FedAvg mean is compared by its update from the round's start
# weights, relative to that update's size.
UPDATE_RTOL = 5e-2
# The kernel and its reference do the same f32 elementwise arithmetic,
# so they agree to the tolerance of the repository's kernel tests.
KERNEL_TOL = 1e-5
NEVER = 2.0          # a tau no accuracy reaches: run a fixed round count


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str):
    if not ok:
        raise SmokeFailure(what)


def observe(phase: str, name: str, value):
    print(f"[bring-up observation, not a benchmark] {phase} {name}={value}",
          flush=True)


@dataclasses.dataclass(frozen=True)
class Scale:
    n_train: int = 50_000          # CIFAR-10's split
    n_test: int = 10_000
    n_clients: int = 10            # paper §IV-A
    small_batches: int = 4         # batches per client in phase D / mesh


class CompileLog:
    """Backend compile seconds per program, from JAX's monitoring events
    (a persistent-cache hit reports its retrieval time)."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.entries = []
        jax.monitoring.register_event_duration_secs_listener(self._record)

    def _record(self, event, duration, **kwargs):
        if event == self.EVENT:
            self.entries.append((kwargs.get("fun_name", "?"), duration))

    def report(self, phase: str, since: int):
        new = self.entries[since:]
        for name, secs in new:
            if secs >= 0.5:
                observe(phase, f"compile_s[{name}]", f"{secs:.2f}")
        observe(phase, "compile_s_total", f"{sum(s for _, s in new):.2f}")
        observe(phase, "programs_compiled", len(new))


def model_bytes(params) -> int:
    return sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(params))


def n_params(task) -> int:
    shapes = jax.eval_shape(task.init_params, jax.random.PRNGKey(0))
    return sum(l.size for l in jax.tree.leaves(shapes))


def peak_bytes(phase: str):
    stats = jax.devices()[0].memory_stats() or {}
    observe(phase, "peak_bytes_in_use", stats.get("peak_bytes_in_use"))


def check_round_logs(phase: str, logs, n_rounds: int):
    check(len(logs) == n_rounds, f"{phase}: {len(logs)} rounds logged, "
                                 f"expected {n_rounds}")
    for log in logs:
        check(all(math.isfinite(s) for s in log.info["scores"]),
              f"{phase}: round {log.round} has non-finite scores")
        if not math.isnan(log.test_loss):
            check(math.isfinite(log.test_loss),
                  f"{phase}: round {log.round} test loss not finite")
    last = logs[-1]
    check(math.isfinite(last.test_loss) and last.test_acc > 0.1,
          f"{phase}: final test accuracy {last.test_acc} is not above "
          f"chance (0.1)")
    observe(phase, "final_test_acc", last.test_acc)


def check_bytes(phase: str, meter, strategy: str, n: int, m: int,
                ratio: float = 1.0):
    """Per-round bytes against the paper's Eqs. 1-4, written out."""
    for up, down in zip(meter.uplink, meter.downlink):
        if strategy == "fedavg":
            want_up = int(max(ratio * n, 1)) * m                 # Eq. 1
            want_down = want_up
        else:
            want_up = n * SCORE_BYTES + m                        # Eq. 2
            want_down = n * m
        check((up, down) == (want_up, want_down),
              f"{phase}: round bytes up/down {up}/{down}, expected "
              f"{want_up}/{want_down}")
    if strategy != "fedavg":
        t = len(meter.uplink)
        want = t * (n * SCORE_BYTES + m) / (30 * n * m)          # Eqs. 3-4
        check(abs(normalized_cost(meter, t_avg=30) - want) <= 1e-12 * want,
              f"{phase}: normalized cost differs from Eq. 4")
    observe(phase, "bytes_per_round_up_down",
            f"{meter.uplink[-1]}/{meter.downlink[-1]}")


def paper_config(scale: Scale, **kw) -> FLConfig:
    return FLConfig(n_clients=scale.n_clients, n_train=scale.n_train,
                    n_test=scale.n_test, engine="auto", tau=NEVER, **kw)


# ---------------------------------------------------------------- phases --
def phase_fedbwo(scale: Scale, compiles: CompileLog):
    """A: single-dispatch rounds, then fused blocks of two rounds."""
    mark = len(compiles.entries)
    cfg = paper_config(scale, strategy="fedbwo", rounds_per_dispatch=1,
                       max_rounds=3)
    exp = build_experiment(cfg)
    server = exp.server
    check(server.engine == "batched",
          f"A: engine is {server.engine!r}, not the batched round engine")
    m = model_bytes(server.global_params)
    observe("A", "model_bytes", m)
    logs = exp.run().logs
    check_round_logs("A/single", logs, 3)
    steady = [log.round_time_s for log in logs[1:]]
    observe("A/single", "steady_s_per_round",
            ",".join(f"{s:.4f}" for s in steady))

    # keep the data, free the first run's device state
    client_data, eval_data = server.client_data, exp.eval_data
    del exp, server

    cfg2 = dataclasses.replace(cfg, rounds_per_dispatch=2, max_rounds=4)
    exp2 = build_experiment(cfg2, client_data=client_data,
                            eval_data=eval_data)
    s2 = exp2.server
    check(s2.engine == "batched" and s2.rounds_per_dispatch == 2
          and s2.pipeline_blocks,
          f"A: fused run resolved engine={s2.engine} rpd="
          f"{s2.rounds_per_dispatch} pipeline={s2.pipeline_blocks}")
    logs2 = exp2.run().logs
    check_round_logs("A/fused", logs2, 4)
    check(all(log.info["engine"] == "fused" for log in logs2),
          "A: the fused run did not run fused blocks")
    # a steady window on the already compiled block shape
    t0 = time.perf_counter()
    res = s2.run_pipelined(4, exp2.eval_data, eval_every=cfg2.eval_every)
    jax.block_until_ready(s2.global_params)
    dt = time.perf_counter() - t0
    check(len(res.infos) == 4 and all(
        math.isfinite(s) for i in res.infos for s in i["scores"]),
        "A: steady fused window produced bad scores")
    observe("A/fused", "steady_s_per_round", f"{dt / 4:.4f}")
    check_bytes("A", s2.meter, "fedbwo", scale.n_clients, m)
    compiles.report("A", mark)
    peak_bytes("A")
    return client_data, eval_data


def phase_fedavg(scale: Scale, compiles: CompileLog, client_data,
                 eval_data):
    """B: FedAvg at full participation."""
    mark = len(compiles.entries)
    cfg = paper_config(scale, strategy="fedavg", client_ratio=1.0,
                       rounds_per_dispatch=1, max_rounds=2)
    exp = build_experiment(cfg, client_data=client_data,
                           eval_data=eval_data)
    check(exp.server.engine == "batched",
          f"B: engine is {exp.server.engine!r}, not batched")
    logs = exp.run().logs
    check_round_logs("B", logs, 2)
    observe("B", "steady_s_per_round", f"{logs[-1].round_time_s:.4f}")
    check_bytes("B", exp.meter, "fedavg", scale.n_clients,
                model_bytes(exp.server.global_params), ratio=1.0)
    compiles.report("B", mark)
    peak_bytes("B")


def phase_kernel(d: int, compiles: CompileLog, pop_size: int = 6):
    """C: the fused BWO generation kernel at the paper CNN's D."""
    mark = len(compiles.entries)
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(11), 3)
    pop = jax.random.normal(k1, (pop_size, d), jnp.float32)
    fit = jax.random.uniform(k2, (pop_size,))
    compiled = bwo_evolve.lower(pop, fit, k3).compile()
    check("tpu_custom_call" in compiled.as_text(),
          "C: the compiled bwo_evolve holds no tpu_custom_call (the "
          "kernel ran in interpret mode)")
    got = compiled(pop, fit, k3)
    want = jax.jit(bwo_evolve_reference)(pop, fit, k3)
    err = float(jnp.max(jnp.abs(got - want)))
    observe("C", "kernel_vs_reference_max_abs_err", err)
    observe("C", "kernel_bit_equal_reference",
            bool(jnp.array_equal(got, want)))
    check(bool(jnp.allclose(got, want, rtol=KERNEL_TOL, atol=KERNEL_TOL)),
          f"C: kernel differs from its reference (max abs err {err})")
    compiles.report("C", mark)


def _rel_diff(a, b):
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-6)))


def _winner_agrees(want_scores, best, want_best):
    """The winner is the same, unless the reference's two best scores are
    closer than the score tolerance."""
    if best == want_best:
        return True
    top2 = np.sort(want_scores)[:2]
    return abs(top2[1] - top2[0]) <= SCORE_RTOL * abs(top2[0])


def _update_rel_err(got, want, start):
    num = sum(float(np.sum((np.asarray(g) - np.asarray(w)) ** 2))
              for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)))
    den = sum(float(np.sum((np.asarray(w) - np.asarray(s)) ** 2))
              for w, s in zip(jax.tree.leaves(want), jax.tree.leaves(start)))
    return math.sqrt(num / max(den, 1e-30))


def phase_cpu_parity(scale: Scale, compiles: CompileLog):
    """D: one reduced FedBWO round on the chip and on the host CPU."""
    mark = len(compiles.entries)
    n = scale.n_clients
    n_train = n * scale.small_batches * 10
    cfg = dataclasses.replace(
        paper_config(scale, strategy="fedbwo", rounds_per_dispatch=1,
                     max_rounds=1), n_train=n_train, n_test=100)
    exp = build_experiment(cfg)
    server = exp.server
    check(server.engine == "batched", f"D: engine is {server.engine!r}")
    # host copies: the round donates the chip's params buffer
    p0 = jax.device_get(server.global_params)
    keys = jax.device_get(jax.random.split(server.rng, n + 2)[2:])
    info = server.run_round()
    adopted = jax.device_get(server.global_params)
    scores = np.asarray(info["scores"], np.float32)
    best = info["best_client"]

    # protocol invariant: the server adopted exactly the round's winner
    winner, scores2, best2 = server._engine.fedx_round(
        jax.device_put(p0), jnp.asarray(keys))
    winner = jax.device_get(winner)
    check(int(best2) == best and np.array_equal(
        np.asarray(scores2), scores),
        "D: rerunning the round program gave other scores or winner")
    check(all(np.array_equal(a, b) for a, b in zip(
        jax.tree.leaves(adopted), jax.tree.leaves(winner))),
        "D: adopted global weights differ from the winner's weights")

    cpu = jax.devices("cpu")[0]
    stacked = jax.device_put(
        jax.device_get(stack_clients(server.client_data)), cpu)
    cpu_round = make_batched_fedx_round(server.task, server.hp,
                                        server.strategy.mh, backend="cpu")
    c_winner, c_scores, c_best = cpu_round(
        jax.device_put(p0, cpu), stacked, None, jax.device_put(keys, cpu))
    c_scores = np.asarray(c_scores)
    c_best = int(c_best)
    rel = _rel_diff(scores, c_scores)
    observe("D", "chip_vs_cpu_score_max_rel_diff", rel)
    observe("D", "chip_vs_cpu_winner", f"{best}/{c_best}")
    check(rel <= SCORE_RTOL,
          f"D: chip scores {scores} differ from CPU {c_scores} beyond "
          f"rtol {SCORE_RTOL}")
    check(_winner_agrees(c_scores, best, c_best),
          f"D: chip winner {best} differs from CPU winner {c_best}")
    # Not a check: BWO ranks its population by fitness and breeds from
    # the ranks, so fitnesses that differ in the last bf16 bits can pick
    # other parents and give other (equally good) refined weights.
    err = _update_rel_err(adopted, jax.device_get(c_winner), p0)
    observe("D", "chip_vs_cpu_winner_update_rel_err", err)
    check_bytes("D", server.meter, "fedbwo", n,
                model_bytes(server.global_params))
    compiles.report("D", mark)
    peak_bytes("D")


def phase_four_chips(scale: Scale, compiles: CompileLog,
                     per_device: int = 2):
    """The sharded round on a 4-chip mesh against the single-chip
    batched round, on the same data and keys."""
    mark = len(compiles.entries)
    mesh = make_host_mesh(4)
    n = 4 * per_device
    task = cnn_task()
    hp = FLConfig().client_hp()
    mh = get_strategy("fedbwo").mh
    k_data, k_part, k_init, k_keys = jax.random.split(
        jax.random.PRNGKey(5), 4)
    train, _ = make_cifar_like(k_data, n * scale.small_batches * 10, 10)
    data = stack_clients(client_batches(partition_iid(k_part, train, n),
                                        10))
    keys = jax.random.split(k_keys, n)
    p0 = jax.device_get(task.init_params(k_init))

    got, got_scores = make_fedx_round(task, hp, mh, mesh)(p0, data, keys)
    want, want_scores, want_best = make_batched_fedx_round(
        task, hp, mh, backend="tpu")(jax.device_put(p0), data, None, keys)
    got_scores = np.asarray(got_scores)
    want_scores = np.asarray(want_scores)
    check(got_scores.shape == (n,),
          f"mesh: {got_scores.shape} scores for {n} clients")
    rel = _rel_diff(got_scores, want_scores)
    observe("mesh", "sharded_vs_single_score_max_rel_diff", rel)
    check(rel <= SCORE_RTOL, f"mesh: scores {got_scores} vs {want_scores}")
    best, want_best = int(np.argmin(got_scores)), int(want_best)
    observe("mesh", "sharded_vs_single_winner", f"{best}/{want_best}")
    check(_winner_agrees(want_scores, best, want_best),
          f"mesh: winner {best} vs single-chip {want_best}")

    avg, _ = make_fedavg_round(task, hp, mesh)(p0, data, keys)
    want_avg, _ = make_batched_fedavg_round(task, hp, backend="tpu")(
        jax.device_put(p0), data, None, keys)
    err = _update_rel_err(jax.device_get(avg), jax.device_get(want_avg), p0)
    observe("mesh", "fedavg_mean_update_rel_err", err)
    check(err <= UPDATE_RTOL, f"mesh: FedAvg mean differs by {err}")
    compiles.report("mesh", mark)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded FedX/FedAvg round on a "
                         "4-chip mesh against the single-chip round")
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX sees {len(devices)} "
              f"{devices[0].platform} device(s)); nothing was run",
              file=sys.stderr)
        return 1
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: {need} chips needed, {len(devices)} found",
              file=sys.stderr)
        return 1
    cache = enable_compile_cache()
    compiles = CompileLog()
    scale = Scale()
    dev = devices[0]
    observe("setup", "device", f"{dev.platform}/{dev.device_kind}"
                               f"/count={len(devices)}")
    observe("setup", "compile_cache", cache)

    if args.four_chips:
        phases = [("mesh", lambda: phase_four_chips(scale, compiles))]
    else:
        shared = {}

        def run_a():
            shared["data"] = phase_fedbwo(scale, compiles)

        phases = [
            ("A", run_a),
            ("B", lambda: phase_fedavg(scale, compiles, *shared["data"])),
            ("C", lambda: phase_kernel(n_params(cnn_task()), compiles)),
            ("D", lambda: phase_cpu_parity(scale, compiles)),
        ]
    for name, run in phases:
        t0 = time.perf_counter()
        run()
        observe(name, "phase_wall_s", f"{time.perf_counter() - t0:.2f}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
