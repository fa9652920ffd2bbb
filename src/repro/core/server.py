"""FL server: strategy definitions and aggregation (paper Algorithms 2/3).

``FedAvg``  — clients upload weights; server averages (Alg. 2).
``FedX``    — clients upload a 4-byte score; server fetches the best
              client's weights and adopts them as the global model
              (Alg. 3: ServerRun + GetBestModel).  X ∈ {BWO, PSO, GWO,
              SCA} only changes the client-side meta-heuristic.

Every round runs as a block of the fused round program
(:func:`repro.core.engine.make_fused_rounds`): ``run_round`` is a block
of one, ``run_block`` a block of ``R`` rounds, and ``run_pipelined``
double-buffers blocks of ``R`` against the host's log processing
(DESIGN.md §6-§7).  Client datasets are stacked on device once (ragged,
e.g. Dirichlet-partitioned, shards are padded and masked, DESIGN.md
§5); the key schedule is derived on device, and each dispatch costs one
device->host sync, for its round logs.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import time
from typing import Any, Callable, List, Optional, Sequence, Tuple, Union

import jax

from repro.core import tracing
from repro.core.client import ClientHP, Task
from repro.core.comm import BlockTiming, CommMeter
from repro.core.engine import BatchedRoundEngine, pipeline_blocks
from repro.core.knobs import (DEFAULT_PIPELINE_DEPTH,
                              DEFAULT_ROUNDS_PER_DISPATCH,
                              parse_rounds_per_dispatch)
from repro.metaheuristics import REGISTRY, Metaheuristic
from repro.metaheuristics.base import init_rows
from repro.models.attention import count_attention_paths


@dataclasses.dataclass(frozen=True)
class Strategy:
    name: str                         # fedavg | fedbwo | fedpso | fedgwo | fedsca
    mh: Optional[Metaheuristic]       # None => FedAvg
    client_ratio: float = 1.0         # C (FedAvg participation ratio)

    @property
    def is_fedx(self) -> bool:
        return self.mh is not None


def get_strategy(name: str, client_ratio: float = 1.0, **mh_kw) -> Strategy:
    name = name.lower()
    if name == "fedavg":
        return Strategy("fedavg", None, client_ratio)
    if name.startswith("fed") and name[3:] in REGISTRY:
        return Strategy(name, REGISTRY[name[3:]](**mh_kw), 1.0)
    raise KeyError(f"unknown strategy {name!r}")


@dataclasses.dataclass
class PendingBlock:
    """An in-flight fused block: the stacked round-log device arrays
    (futures under JAX's async dispatch — touching them is the block's
    one host sync) plus the host bookkeeping needed to finish it."""
    n_rounds: int
    round_offset: int         # server.rounds_completed before the block
    logs: Any                 # stacked per-round device arrays
    t_dispatched: float       # perf_counter timestamp at dispatch
    dispatch_s: float         # host time spent enqueueing the dispatch


@dataclasses.dataclass
class PipelineResult:
    """Outcome of :meth:`Server.run_pipelined`.

    ``infos`` covers every round that actually executed — including the
    rounds of any block that was already in flight when a stopping
    condition triggered (the one-block overshoot, DESIGN.md §7).
    ``kept`` counts the leading infos up to and including the block that
    triggered the stop (``== len(infos)`` when nothing did); drivers
    trim their logs to ``infos[:kept]`` while the server's device state,
    round counter, and CommMeter ledger keep the overshoot rounds.
    """
    infos: List[dict]
    kept: int
    stopped: bool


class Server:
    """Orchestrates FL rounds over in-process simulated clients.

    The client datasets must stack (ragged batch counts are padded and
    masked, DESIGN.md §5): unstackable data or an empty shard raises
    ``ValueError``.

    ``rounds_per_dispatch``: how many rounds one device dispatch of the
    drivers executes (DESIGN.md §6).  1 = one block of one round per
    ``run_round``; R > 1 lets ``run_federated`` drive pipelined blocks
    of R rounds (``run_pipelined``).  "auto" resolves to
    ``knobs.DEFAULT_ROUNDS_PER_DISPATCH``.
    """

    engine = "batched"
    """Constant; the benchmark harness reads it (``rounds_per_call``)."""

    def __init__(self, task: Task, strategy: Strategy, hp: ClientHP,
                 client_data: Sequence[Any], rng: jax.Array,
                 model_bytes: Optional[int] = None,
                 rounds_per_dispatch: Union[int, str] = 1):
        rpd = parse_rounds_per_dispatch(rounds_per_dispatch)
        self.task = task
        self.strategy = strategy
        self.hp = hp
        self.client_data = list(client_data)
        self.n_clients = len(client_data)
        empty = [k for k, d in enumerate(self.client_data)
                 if any(l.ndim and l.shape[0] == 0
                        for l in jax.tree.leaves(d))]
        if empty:
            raise ValueError(
                f"client shards {empty} are empty (0 batches) — a client "
                f"with no data can neither train nor score; extreme "
                f"Dirichlet skew can starve clients, so drop empty "
                f"shards or repartition (larger alpha / fewer clients / "
                f"smaller batch size) before constructing the Server")
        # valid batches per client, from shapes (no device sync)
        self._client_batches = [jax.tree.leaves(d)[0].shape[0]
                                for d in self.client_data]
        rng, pkey = jax.random.split(rng)
        self.rng = rng
        self.global_params = task.init_params(pkey)
        if model_bytes is None:
            model_bytes = sum(l.size * l.dtype.itemsize
                              for l in jax.tree.leaves(self.global_params))
        self.meter = CommMeter(model_bytes=model_bytes,
                               n_clients=self.n_clients)
        # genome length of a client's meta-heuristic (client.py)
        leaves = jax.tree.leaves(self.global_params)
        self._genes = (len(leaves) if hp.subspace
                       else sum(l.size for l in leaves))
        self._engine = BatchedRoundEngine(task, strategy, hp,
                                          self.client_data)
        self.rounds_per_dispatch = (DEFAULT_ROUNDS_PER_DISPATCH
                                    if rpd is None else rpd)
        self.rounds_completed = 0
        # cache the jitted eval fn once: jax.jit(task.loss_fn) per
        # evaluate() call would re-trace and re-compile every round
        def eval_loss(params, batch):
            with jax.named_scope(tracing.EVAL):
                return task.loss_fn(params, batch)
        self._eval = jax.jit(eval_loss)

    @contextlib.contextmanager
    def _counting_paths(self):
        """Record on the meter the attention paths of the programs that
        the block traces (its first call of each)."""
        with count_attention_paths() as paths:
            yield
        self.meter.record_attention_paths(paths)

    # ------------------------------------------------------------ round --
    def run_round(self) -> dict:
        """Run one round as a block of one, with no evaluation in the
        program, and return its info dict.  The round is recorded on the
        meter as a block's rounds are, but adds no ``BlockTiming``: the
        block ledger holds the fused blocks of ``run_block`` /
        ``run_pipelined`` only."""
        with jax.profiler.TraceAnnotation("Server.run_round"):
            pending = self._dispatch(1)
            with jax.profiler.TraceAnnotation("Server.run_round.sync"):
                # the round's single device->host sync
                out = jax.device_get(pending.logs)
            return self._record(out, 1, "batched")[0]

    def _record_counters(self, participants: Optional[Sequence[int]]
                         = None):
        """Log one round's real and computed local SGD steps: the
        participants' (default: every client's) valid batches, and every
        participant's padded row.  Under BWO also log the population
        rows that the participants' generations and initial draws
        hashed, against the rows of full draws."""
        if participants is None:
            participants = range(self.n_clients)
        epochs = self.hp.local_epochs
        real = epochs * sum(self._client_batches[k] for k in participants)
        computed = epochs * len(participants) * self._engine.n_batches
        self.meter.record_sgd_steps(real, computed)
        mh = self.strategy.mh
        if mh is not None and mh.mutation_rows is not None:
            n, pop = len(participants), self.hp.mh_pop
            gens = n * self.hp.mh_generations
            drawn, full = mh.mutation_rows(pop, self._genes)
            self.meter.record_bwo_rows(gens * drawn, gens * full,
                                       n * init_rows(pop, self._genes),
                                       n * pop)

    # ------------------------------------------------------------ block --
    def run_block(self, n_rounds: Optional[int] = None, eval_data=None,
                  eval_every: int = 1) -> List[dict]:
        """Run ``n_rounds`` (default: ``rounds_per_dispatch``) rounds as
        ONE fused device dispatch and return one info dict per round, in
        ``run_round``'s format plus ``eval_loss`` / ``eval_acc`` entries
        on rounds the ``eval_every`` cadence (and the block's final
        round) evaluated on device.

        The fused program carries ``(global_params, rng)`` across rounds
        and derives each round's keys on device, so a block is
        bit-identical to ``n_rounds`` ``run_round`` calls — including
        the CommMeter ledger, reconstructed per round by
        ``CommMeter.record_rounds``.  The whole block costs one
        device->host sync (the stacked round logs).  This is the serial
        form of ``run_pipelined``.
        """
        return self.finish_block(
            self.dispatch_block(n_rounds, eval_data, eval_every))

    # --------------------------------------------------------- pipeline --
    def dispatch_block(self, n_rounds: Optional[int] = None, eval_data=None,
                       eval_every: int = 1) -> PendingBlock:
        """Dispatch one fused block WITHOUT fetching its logs.

        JAX dispatch is asynchronous, so the returned
        :class:`PendingBlock` holds device-array futures; the server's
        ``global_params`` / ``rng`` / ``rounds_completed`` advance
        immediately (also as futures), which is what lets the *next*
        ``dispatch_block`` enqueue before this block's device execution
        finishes.  Pair with :meth:`finish_block` — in dispatch order —
        to sync the logs, record the meter, and build the info dicts.
        """
        with jax.profiler.TraceAnnotation("Server.dispatch_block"):
            return self._dispatch(
                int(n_rounds or self.rounds_per_dispatch), eval_data,
                eval_every)

    def _dispatch(self, n_rounds: int, eval_data=None,
                  eval_every: int = 1) -> PendingBlock:
        """Enqueue a block of ``n_rounds`` and advance the server's
        state to its (future) outputs."""
        with self._counting_paths():
            t0 = time.perf_counter()
            offset = self.rounds_completed
            params, rng, logs = self._engine.run_block(
                self.global_params, self.rng, n_rounds,
                eval_batch=eval_data, eval_every=eval_every,
                round_offset=offset)
            self.global_params, self.rng = params, rng
            self.rounds_completed += n_rounds
            return PendingBlock(n_rounds=n_rounds, round_offset=offset,
                                logs=logs, t_dispatched=t0,
                                dispatch_s=time.perf_counter() - t0)

    def finish_block(self, pending: PendingBlock) -> List[dict]:
        """Finish a dispatched block: record its rounds on the meter,
        sync the stacked logs (the block's one device->host transfer —
        under the pipeline this host work overlaps the next block's
        device execution), reconstruct the per-round info dicts, and
        append a :class:`~repro.core.comm.BlockTiming` to the meter's
        block ledger.  Its ``sync_s`` and ``process_s`` time exactly the
        ``Server.finish_block.sync`` and ``.process`` spans."""
        with jax.profiler.TraceAnnotation("Server.finish_block"):
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("Server.finish_block.sync"):
                # the block's single device->host sync
                out = jax.device_get(pending.logs)
            t1 = time.perf_counter()
            with jax.profiler.TraceAnnotation("Server.finish_block.process"):
                infos = self._record(out, pending.n_rounds, "fused")
            t2 = time.perf_counter()
            self.meter.record_block_timing(BlockTiming(
                n_rounds=pending.n_rounds, dispatch_s=pending.dispatch_s,
                sync_s=t1 - t0, process_s=t2 - t1,
                total_s=t2 - pending.t_dispatched))
            return infos

    def _record(self, out, n_rounds: int, engine: str) -> List[dict]:
        """Record a block's fetched logs on the meter and rebuild its
        per-round info dicts (``engine`` labels them)."""
        for slots in out.get("expert_slots", ()):
            self.meter.record_expert_slots(slots)
        if self.strategy.is_fedx:
            self.meter.record_rounds(self.strategy, n_rounds,
                                     fetched_model=True)
            for _ in range(n_rounds):
                self._record_counters()
        else:
            self.meter.record_rounds(
                self.strategy, n_rounds,
                n_participants=self._engine.n_participants)
            for sel in out["participants"]:
                self._record_counters(sel)
        infos = []
        for r in range(n_rounds):
            scores = out["scores"][r]
            if self.strategy.is_fedx:
                best = int(out["best"][r])
                info = {"best_client": best, "score": float(scores[best]),
                        "scores": [float(s) for s in scores],
                        "engine": engine}
            else:
                # FedAvg scores align with the participants list
                info = {"participants": [int(k)
                                         for k in out["participants"][r]],
                        "scores": [float(s) for s in scores],
                        "engine": engine}
            if "eval_loss" in out and not math.isnan(
                    float(out["eval_loss"][r])):
                info["eval_loss"] = float(out["eval_loss"][r])
                info["eval_acc"] = float(out["eval_acc"][r])
            infos.append(info)
        return infos

    def run_pipelined(self, rounds: int, eval_data=None,
                      eval_every: int = 1,
                      stop_fn: Optional[Callable[[dict], bool]] = None,
                      block_rounds: Optional[int] = None,
                      depth: int = DEFAULT_PIPELINE_DEPTH) -> PipelineResult:
        """Run ``rounds`` rounds as double-buffered fused blocks.

        Blocks of ``block_rounds`` (default ``rounds_per_dispatch``)
        rounds are dispatched through :func:`repro.core.engine.
        pipeline_blocks`: block ``k+1`` is enqueued before block ``k``'s
        logs are fetched, so the host-side log sync, info
        reconstruction, CommMeter recording, and ``stop_fn`` checks of
        block ``k`` overlap block ``k+1``'s device execution.  The
        result is bit-exact with a serial ``run_block`` loop — the
        pipeline reorders host work, not device work.

        ``stop_fn(info)`` is called once per finished round, in round
        order; when it returns True no further block is dispatched, but
        the block already in flight completes (its rounds execute, its
        meter entries land) — a worst-case overshoot of ``(depth - 1) *
        block_rounds`` rounds.  See :class:`PipelineResult` for the
        trim contract.  A trailing partial block (``rounds`` not a
        multiple of the block size) compiles a second block shape;
        drivers that care (``run_federated``) pass a multiple and run
        leftovers on the single-round path.
        """
        rounds = int(rounds)
        block = int(block_rounds or self.rounds_per_dispatch)
        sizes = [block] * (rounds // block)
        if rounds % block:
            sizes.append(rounds % block)
        should_stop = None
        if stop_fn is not None:
            def should_stop(infos):
                return any(stop_fn(i) for i in infos)
        results, kept_blocks, stopped = pipeline_blocks(
            lambda n: self.dispatch_block(n, eval_data, eval_every),
            self.finish_block, sizes, depth=depth,
            should_stop=should_stop)
        return PipelineResult(
            infos=[i for blk in results for i in blk],
            kept=sum(len(blk) for blk in results[:kept_blocks]),
            stopped=stopped)

    # ------------------------------------------------------------- eval --
    def evaluate(self, eval_data) -> Tuple[float, float]:
        with jax.profiler.TraceAnnotation("Server.evaluate"), \
                self._counting_paths():
            out = self._eval(self.global_params, eval_data)
            # one device_get for both scalars: float(loss), float(acc) on
            # the device arrays would block on the device twice (flcheck's
            # paired-host-conversions lint — the first audit's finding)
            with jax.profiler.TraceAnnotation("Server.evaluate.sync"):
                loss, acc = jax.device_get(out)
            return float(loss), float(acc)
