"""Federated training driver with the paper's stopping conditions (§IV-D):

1. no significant improvement for ``t`` consecutive rounds,
2. accuracy above threshold ``tau``,
3. round limit reached.

When the server runs with ``rounds_per_dispatch`` R > 1, the driver
runs every full block of R rounds through ``Server.run_pipelined``: one
XLA program and one device->host sync per block, with eval folded into
the device program at the ``eval_every`` cadence (DESIGN.md §6), and
block k+1 dispatched before block k's logs are fetched, so host-side
log processing and stopping checks overlap device execution (DESIGN.md
§7).  Stopping conditions are still checked per evaluated round, but a
dispatched block is atomic, and one more block is in flight: when
tau/patience triggers in block k, block k+1 completes (it advances the
server's params/round counter/meter), but its rounds are trimmed from
the returned logs, which end at the triggering block.  Leftover rounds
(fewer than R), and every round when R = 1, run as ``run_round`` +
``evaluate``.
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Any, Dict, List, Optional

import jax

from repro.core.server import Server


@dataclasses.dataclass
class StopConditions:
    max_rounds: int = 30          # paper: 30 global epochs
    patience: int = 5             # paper: t = 5
    tau: float = 0.70             # paper: tau = 70%
    min_delta: float = 1e-3


@dataclasses.dataclass
class RoundLog:
    round: int
    test_loss: float
    test_acc: float
    wall_time_s: float
    info: Dict[str, Any]
    round_time_s: float = 0.0    # run_round only, blocked on the result


def run_federated(server: Server, eval_data, stop: StopConditions,
                  verbose: bool = False,
                  eval_every: int = 1) -> List[RoundLog]:
    """Drive ``server`` to a stopping condition.

    ``eval_every``: evaluate the global model every k-th round (1 =
    every round, the paper's cadence).  Skipped rounds log NaN
    loss/accuracy and don't advance the patience counter.  On the fused
    path the cadence runs *inside* the device program; the driver also
    always gets an eval at each block boundary so stopping decisions
    never act on stale accuracy.
    """
    logs: List[RoundLog] = []
    best_acc, stale = -1.0, 0
    rpd = int(server.rounds_per_dispatch)
    rnd, stop_now = 0, False

    def check_stop(acc):
        nonlocal best_acc, stale
        if math.isnan(acc):
            return False
        if acc > best_acc + stop.min_delta:
            best_acc, stale = acc, 0
        else:
            stale += 1
        return acc >= stop.tau or stale >= stop.patience

    while rnd < stop.max_rounds and not stop_now:
        if rpd > 1 and stop.max_rounds - rnd >= rpd:
            # double-buffered: all remaining full blocks in one
            # pipelined drive; block k's log processing + stopping
            # checks overlap block k+1's device execution.  If a stop
            # triggers, the in-flight block completes (one-block
            # overshoot on the server's state/meter) but its rounds are
            # trimmed from the logs; leftover rounds (< rpd) fall
            # through to the single-round path below, so only one block
            # shape ever compiles.
            n = ((stop.max_rounds - rnd) // rpd) * rpd
            t0 = time.perf_counter()
            res = server.run_pipelined(
                n, eval_data, eval_every=eval_every,
                stop_fn=lambda info: check_stop(
                    info.get("eval_acc", float("nan"))))
            jax.block_until_ready(server.global_params)
            dt = (time.perf_counter() - t0) / max(len(res.infos), 1)
            for info in res.infos[:res.kept]:
                loss = info.pop("eval_loss", float("nan"))
                acc = info.pop("eval_acc", float("nan"))
                logs.append(RoundLog(rnd, loss, acc, dt, info, dt))
                if verbose:
                    print(f"  round {rnd:3d}  loss={loss:.4f} "
                          f"acc={acc:.4f} ({dt:.2f}s amortized, "
                          f"pipelined) {info if rnd < 2 else ''}")
                rnd += 1
            stop_now = res.stopped
        else:
            t0 = time.perf_counter()
            info = server.run_round()
            # block on the new global model so round_time_s measures
            # device work, not dispatch (round 0 additionally includes
            # compilation)
            jax.block_until_ready(server.global_params)
            t_round = time.perf_counter() - t0
            if (rnd + 1) % max(eval_every, 1) == 0 \
                    or rnd == stop.max_rounds - 1:
                loss, acc = server.evaluate(eval_data)
            else:
                loss, acc = float("nan"), float("nan")
            dt = time.perf_counter() - t0
            logs.append(RoundLog(rnd, loss, acc, dt, info, t_round))
            if verbose:
                print(f"  round {rnd:3d}  loss={loss:.4f} acc={acc:.4f} "
                      f"({dt:.2f}s) {info if rnd < 2 else ''}")
            stop_now = check_stop(acc)
            rnd += 1
    return logs
