"""The comparison that decides ``correct``.

Every number below is computed in every run; a cell's ``limits`` name
the ones it is held to.  They measure a run against the plain reference
(``reference.py``) in three ways.

Exact counts, limit 0, over every round of the run:
  ``bytes_rounds_off``   rounds whose uplink/downlink bytes differ from
                         the paper's Eqs. 1-2 for the cell's protocol;
  ``winner_not_argmin``  FedX rounds whose winner is not the first
                         lowest score.

The program's own outputs, re-read by the reference's arithmetic
(float32, ``highest`` precision), at each state the set-up kept:
  ``winner_fit_gap``  the winner's reported score against the fitness
                      of the adopted weights on the winner's data
                      (relative): the adopted weights are the winner's;
  ``eval_loss_gap``   the reported test loss against the test loss of
                      those weights (relative);
  ``eval_acc_gap``    the same for accuracy (absolute share).

The program's trajectory against the reference's own, both from the
seed (the reference takes no weights from the program):
  ``score_gap_r0``     every client's first-round score (relative);
  ``score_bias_r0``    the mean over the clients of
                       ``log(score_prog / score_ref)`` in the first
                       round, taken absolute: rounding moves a client's
                       score either way, a fault in training moves them
                       all one way;
  ``loss_gap``         each followed round's test loss (relative);
  ``loss_gap_r0``      the first round's test loss against the test
                       loss of the reference's own first-round weights
                       of the client the program adopted (FedAvg: of
                       the reference's first round), so a near-tie
                       that makes the two runs adopt different clients
                       does not move it (relative);
  ``update_norm_gap``  per weight tensor, the norm of the first kept
                       state's change from the initial weights;
  ``change_norm_gap``  the same at the last kept state.
A norm gap is ``|‖Δ_prog‖ - ‖Δ_ref‖|`` over the larger of the
reference's ``‖Δ‖`` for that tensor and for the median tensor, worst
tensor first.  Tensors that the reference leaves all but unmoved (under
a thousandth of the median tensor's change) are left out.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional

import numpy as np

SCORE_BYTES = 4
UNMOVED = 1e-3
TINY = 1e-30


def _leaves(tree) -> List[np.ndarray]:
    import jax
    return [np.asarray(l, np.float64) for l in jax.tree.leaves(tree)]


def _rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-12)


def norm_gap(w0, w, ref_w0, ref_w) -> float:
    got = [np.linalg.norm(a - b) for a, b in zip(_leaves(w), _leaves(w0))]
    want = [np.linalg.norm(a - b)
            for a, b in zip(_leaves(ref_w), _leaves(ref_w0))]
    med = float(np.median(want))
    gaps = [abs(g - r) / max(r, med) for g, r in zip(got, want)
            if r >= UNMOVED * med]
    return float(max(gaps)) if gaps else math.inf


def bytes_rounds_off(uplink, downlink, kinds_fedx: bool, n_clients: int,
                     n_participants: int, model_bytes: int,
                     rounds: int) -> int:
    if kinds_fedx:
        want = (n_clients * SCORE_BYTES + model_bytes,
                n_clients * model_bytes)                      # Eq. 2
    else:
        want = (n_participants * model_bytes,) * 2             # Eq. 1
    off = sum((u, d) != want for u, d in zip(uplink, downlink))
    return off + abs(len(uplink) - rounds)


def winner_not_argmin(logs) -> int:
    return sum(int(np.argmin(l["scores"])) != l["best"] for l in logs
               if l.get("best") is not None)


def consistency(run, ref, is_fedx: bool) -> Dict[str, float]:
    """The kept states of ``run`` re-read by ``ref``."""
    out = {"eval_loss_gap": 0.0, "eval_acc_gap": 0.0}
    if is_fedx:
        out["winner_fit_gap"] = 0.0
    for r, w in run.snapshots.items():
        log = run.logs[r - 1]
        loss, acc = ref.evaluate(w)
        if not math.isnan(log["eval_loss"]):
            out["eval_loss_gap"] = max(out["eval_loss_gap"],
                                       _rel(log["eval_loss"], loss))
            out["eval_acc_gap"] = max(out["eval_acc_gap"],
                                      abs(log["eval_acc"] - acc))
        if is_fedx:
            best = log["best"]
            fit = ref.fitness(w, best)
            out["winner_fit_gap"] = max(out["winner_fit_gap"],
                                        _rel(log["scores"][best], fit))
    return out


def first_round(run, ref, ref_run, is_fedx: bool) -> Dict[str, float]:
    """``loss_gap_r0`` of ``run``'s first round."""
    log = run.logs[0]
    if math.isnan(log["eval_loss"]):
        return {"loss_gap_r0": math.inf}
    if is_fedx:
        want = ref.evaluate(ref.first_round(log["best"]))[0]
    else:
        want = ref_run.logs[0]["eval_loss"]
    return {"loss_gap_r0": _rel(log["eval_loss"], want)}


def trajectory(run, ref_run, is_fedx: bool) -> Dict[str, float]:
    """``run`` against the reference's own run from the same seed."""
    first, last = min(run.snapshots), max(run.snapshots)

    def by_client(log):
        if is_fedx:
            return dict(enumerate(log["scores"]))
        return dict(zip(log["participants"], log["scores"]))

    got, want = by_client(run.logs[0]), by_client(ref_run.logs[0])
    same = set(got) == set(want)
    score = max(_rel(got[k], want[k]) for k in want) if same else math.inf
    bias = abs(float(np.mean([np.log(max(got[k], TINY) / max(want[k], TINY))
                              for k in want]))) if same else math.inf
    losses = [_rel(a["eval_loss"], b["eval_loss"])
              for a, b in zip(run.logs[:last], ref_run.logs[:last])
              if not math.isnan(a["eval_loss"])]
    return {
        "score_gap_r0": score,
        "score_bias_r0": bias,
        "loss_gap": max(losses) if losses else math.inf,
        "update_norm_gap": norm_gap(run.w0, run.snapshots[first],
                                    ref_run.w0, ref_run.snapshots[first]),
        "change_norm_gap": norm_gap(run.w0, run.snapshots[last],
                                    ref_run.w0, ref_run.snapshots[last]),
    }


def compare(numbers: Dict[str, float],
            limits: Optional[Dict[str, float]]) -> Dict[str, dict]:
    """Each limited number beside its limit; numbers without a limit in
    the cell are not compared."""
    limits = limits or {}
    return {k: {"value": numbers.get(k, math.inf), "limit": lim}
            for k, lim in limits.items()}


def passed(checks: Dict[str, dict]) -> bool:
    return all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in checks.values())
