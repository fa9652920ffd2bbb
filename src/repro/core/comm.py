"""Communication-cost accounting (paper §IV-D, Eqs. 1-4).

FedAvg uplink per round:  C * N * M          (Eq. 1 over T rounds)
FedX   uplink per round:  N * 4 + M + eps    (Eq. 2; eps = server request,
                                              0 on TPU program order)
Normalized FedX cost (C=1, fixed N=10):  T_X / (T_Avg * 10)   (Eq. 4)
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Tuple

import numpy as np

SCORE_BYTES = 4  # one fp32 performance score — the paper's headline number

# per-round strategy kinds recorded on the CommMeter ledger
KIND_FEDX = "fedx"
KIND_FEDAVG = "fedavg"


def fedavg_round_bytes(c: float, n_clients: int, model_bytes: int) -> int:
    return int(max(c * n_clients, 1)) * model_bytes


def fedx_round_bytes(n_clients: int, model_bytes: int, eps: int = 0) -> int:
    return n_clients * SCORE_BYTES + model_bytes + eps


def fedavg_total(t_rounds: int, c: float, n: int, m: int) -> int:
    return t_rounds * fedavg_round_bytes(c, n, m)                 # Eq. 1


def fedx_total(t_rounds: int, n: int, m: int, eps: int = 0) -> int:
    return t_rounds * fedx_round_bytes(n, m, eps)                 # Eq. 2


def normalized_cost(t_x, n: int = None, m: int = None, t_avg: int = 30,
                    c: float = 1.0, eps: int = 0) -> float:
    """Eq. 3; with the paper's simplification it reduces to Eq. 4.

    The first argument is either the FedX round count ``t_x`` (with
    ``n`` clients and ``m`` model bytes given explicitly) or a
    :class:`CommMeter`, from which ``t_x`` (recorded rounds), ``n``, and
    ``m`` are read — so callers stop re-deriving the Eq. 4 inputs by
    hand.  ``t_avg`` defaults to the paper's 30 FedAvg rounds.

    Eq. 4's numerator counts *FedX* rounds, so a meter that recorded any
    FedAvg rounds (its per-round ``kinds`` ledger says which) raises
    ``ValueError`` instead of silently pricing FedAvg uplink at FedX
    rates: compute the FedAvg side of the comparison from
    :func:`fedavg_total` (or ``meter.total_uplink``) instead.
    """
    if isinstance(t_x, CommMeter):
        meter = t_x
        non_fedx = [k for k in meter.kinds if k != KIND_FEDX]
        if non_fedx:
            counts = {k: meter.kinds.count(k) for k in set(meter.kinds)}
            raise ValueError(
                f"normalized_cost(meter): Eq. 4's t_x counts FedX rounds "
                f"only, but this meter recorded {counts} — price the "
                f"FedAvg rounds with fedavg_total/meter.total_uplink "
                f"instead of Eq. 4")
        t_x, n, m = len(meter.uplink), meter.n_clients, meter.model_bytes
    if n is None or m is None:
        raise TypeError("normalized_cost needs (t_x, n, m) explicitly "
                        "or a CommMeter as the first argument")
    return fedx_total(t_x, n, m, eps) / max(1, fedavg_total(t_avg, c, n, m))


@dataclasses.dataclass(frozen=True)
class BlockTiming:
    """Host-side timing of one fused block (DESIGN.md §7).

    ``dispatch_s`` is the time spent *enqueueing* the block (tracing +
    compilation on the first block, near-zero after), ``sync_s`` the
    time the host blocked in ``jax.device_get`` waiting for the block's
    logs, ``process_s`` the host-side info-dict reconstruction + meter
    bookkeeping, and ``total_s`` the dispatch->finish wall time.  Under
    the double-buffered pipeline the next block executes while this
    block's logs are processed, so steady-state ``sync_s`` absorbs the
    device time the host could not hide — the overlap is observable as
    ``sync_s`` shrinking relative to the serial driver's.
    """
    n_rounds: int
    dispatch_s: float
    sync_s: float
    process_s: float
    total_s: float


@dataclasses.dataclass
class CommMeter:
    """Per-round byte accounting for a running FL experiment.

    ``kinds`` records each round's protocol (``"fedx"`` / ``"fedavg"``)
    so cost formulas that are strategy-specific (Eq. 4) can verify what
    they are pricing; ``block_timings`` is the per-block wall/sync
    ledger filled by ``record_block_timing``, ``sgd_steps`` the
    per-round ``(real, computed)`` local SGD step counts filled by
    ``record_sgd_steps``, ``bwo_rows`` the per-round BWO population
    rows drawn against a full draw's, filled by ``record_bwo_rows``, and
    ``expert_slots`` the per-round token-slots that local SGD routed to
    each held expert of each expert layer, filled by
    ``record_expert_slots``, and ``attention_paths`` the full-sequence
    attention calls traced into the round programs, by path (``flash``,
    ``blockwise``), filled by ``record_attention_paths`` (all kept out
    of ``summary()`` so byte ledgers of protocol-identical runs stay
    comparable).
    """
    model_bytes: int
    n_clients: int
    uplink: List[int] = dataclasses.field(default_factory=list)
    downlink: List[int] = dataclasses.field(default_factory=list)
    kinds: List[str] = dataclasses.field(default_factory=list)
    block_timings: List[BlockTiming] = dataclasses.field(
        default_factory=list)
    sgd_steps: List[Tuple[int, int]] = dataclasses.field(
        default_factory=list)
    bwo_rows: List[Tuple[int, int, int, int]] = dataclasses.field(
        default_factory=list)
    expert_slots: List[np.ndarray] = dataclasses.field(default_factory=list)
    attention_paths: Dict[str, int] = dataclasses.field(
        default_factory=dict)

    def record_fedavg_round(self, n_participants: int):
        self.uplink.append(n_participants * self.model_bytes)
        self.downlink.append(n_participants * self.model_bytes)
        self.kinds.append(KIND_FEDAVG)

    def record_fedx_round(self, fetched_model: bool = True):
        up = self.n_clients * SCORE_BYTES
        if fetched_model:
            up += self.model_bytes
        self.uplink.append(up)
        self.downlink.append(self.n_clients * self.model_bytes)
        self.kinds.append(KIND_FEDX)

    def record_block_timing(self, timing: BlockTiming):
        self.block_timings.append(timing)

    def timing_summary(self) -> Dict[str, float]:
        """Aggregate the block ledger: total/sync/process host seconds
        plus the per-round amortized wall time."""
        rounds = sum(t.n_rounds for t in self.block_timings)
        total = sum(t.total_s for t in self.block_timings)
        sync = sum(t.sync_s for t in self.block_timings)
        return {"blocks": len(self.block_timings),
                "rounds": rounds,
                "total_s": total,
                "dispatch_s": sum(t.dispatch_s for t in self.block_timings),
                "sync_s": sync,
                "process_s": sum(t.process_s for t in self.block_timings),
                "sync_fraction": sync / total if total else 0.0,
                "round_s": total / rounds if rounds else 0.0}

    def record_sgd_steps(self, real: int, computed: int):
        """One round's local SGD steps: ``real`` steps trained on a
        client's own batches, ``computed`` the steps the round program
        ran, masked padding included (equal when nothing is padded)."""
        self.sgd_steps.append((int(real), int(computed)))

    def sgd_step_summary(self) -> Dict[str, float]:
        """Totals of the step ledger and the real share of the
        computed steps."""
        real = sum(r for r, _ in self.sgd_steps)
        computed = sum(c for _, c in self.sgd_steps)
        return {"rounds": len(self.sgd_steps), "real": real,
                "computed": computed,
                "real_frac": real / computed if computed else 0.0}

    def record_bwo_rows(self, drawn: int, full: int, init_drawn: int,
                        init_full: int):
        """One round's BWO random rows: the mutation rows its generations
        drew (``drawn``) against the rows a full ``(P, D)`` draw covers
        (``full``), and the same for the initial populations."""
        self.bwo_rows.append((int(drawn), int(full), int(init_drawn),
                              int(init_full)))

    def bwo_row_summary(self) -> Dict[str, float]:
        """Totals of the row ledger and the drawn share of the
        generations' mutation rows."""
        drawn, full, init_drawn, init_full = (
            [sum(c) for c in zip(*self.bwo_rows)] if self.bwo_rows
            else [0, 0, 0, 0])
        return {"rounds": len(self.bwo_rows), "drawn": drawn, "full": full,
                "drawn_frac": drawn / full if full else 0.0,
                "init_drawn": init_drawn, "init_full": init_full}

    def record_expert_slots(self, slots):
        """One round's expert slots, summed over the participants and
        their local SGD steps: ``(expert layers, held + 1)``, the slots
        routed to each held expert of each layer, the absent experts'
        last."""
        self.expert_slots.append(np.asarray(slots, np.int64))

    def expert_slot_summary(self) -> Dict[str, float]:
        """Totals of the slot ledger over rounds and layers: each held
        expert's slots, the absent experts', the held share of all slots
        and the busiest held expert's load over the mean held load."""
        if not self.expert_slots:
            return {"rounds": 0}
        total = np.sum(self.expert_slots, axis=0).sum(0)
        held, absent = total[:-1], int(total[-1])
        n = int(total.sum())
        mean = float(held.mean())
        return {"rounds": len(self.expert_slots),
                "held": [int(h) for h in held], "absent": absent,
                "held_share": float(held.sum()) / n if n else 0.0,
                "max_over_mean": float(held.max()) / mean if mean else 0.0}

    def record_attention_paths(self, paths):
        """Add the attention calls of newly traced programs, counted by
        path at trace time (``repro.models.attention.
        count_attention_paths``): a compiled program adds nothing when
        it runs again."""
        for path, n in paths.items():
            self.attention_paths[path] = (self.attention_paths.get(path, 0)
                                          + int(n))

    def record_rounds(self, strategy, n_rounds: int,
                      n_participants: int = None,
                      fetched_model: bool = True):
        """Block recording for ``n_rounds`` protocol-identical rounds —
        the fused multi-round engine executes a whole block in one
        dispatch, then reconstructs the per-round ledger here so the
        byte accounting is entry-for-entry identical to ``n_rounds``
        single-round recordings.

        ``strategy`` is either a strategy name (``"fedavg"`` means
        FedAvg; any other name, e.g. ``"fedbwo"``, means FedX) or an
        object with an ``is_fedx`` attribute (e.g.
        ``repro.core.Strategy``).  FedAvg recording requires
        ``n_participants`` (fixed per round at a given client ratio).
        """
        is_fedx = getattr(strategy, "is_fedx", None)
        if is_fedx is None:
            is_fedx = str(strategy).lower() != "fedavg"
        if not is_fedx and n_participants is None:
            raise TypeError(
                "record_rounds for FedAvg needs n_participants")
        for _ in range(int(n_rounds)):
            if is_fedx:
                self.record_fedx_round(fetched_model=fetched_model)
            else:
                self.record_fedavg_round(n_participants)

    @property
    def total_uplink(self) -> int:
        return sum(self.uplink)

    @property
    def total_downlink(self) -> int:
        return sum(self.downlink)

    @property
    def total(self) -> int:
        return sum(self.uplink) + sum(self.downlink)

    def summary(self) -> Dict[str, float]:
        return {"rounds": len(self.uplink),
                "uplink_bytes": self.total_uplink,
                "downlink_bytes": self.total_downlink,
                "total_bytes": self.total,
                "model_bytes": self.model_bytes,
                "rounds_detail": [
                    {"round": i, "uplink_bytes": u, "downlink_bytes": d}
                    for i, (u, d) in enumerate(zip(self.uplink,
                                                   self.downlink))]}
