"""FL server: strategy definitions and aggregation (paper Algorithms 2/3).

``FedAvg``  — clients upload weights; server averages (Alg. 2).
``FedX``    — clients upload a 4-byte score; server fetches the best
              client's weights and adopts them as the global model
              (Alg. 3: ServerRun + GetBestModel).  X ∈ {BWO, PSO, GWO,
              SCA} only changes the client-side meta-heuristic.

Two round engines execute the same protocol with identical ``CommMeter``
accounting:

``batched``    — one jit'd dispatch for the whole round via
                 :class:`repro.core.engine.BatchedRoundEngine`; zero
                 per-client host syncs (exactly one device->host
                 transfer per round, for the round log).  Ragged
                 (e.g. Dirichlet-partitioned) client datasets batch
                 too, via pad+mask stacking (DESIGN.md §5); FedAvg
                 partial participation is sample-then-stack, compiled
                 for the participant count only.
``sequential`` — the original per-client jit loop; kept as the fallback
                 for genuinely unstackable client datasets (mismatched
                 structures/shapes/dtypes) and as the baseline for the
                 engine-parity tests/benchmarks.

On top of the batched engine, ``rounds_per_dispatch > 1`` fuses whole
*blocks* of rounds into one XLA program (``run_block``,
:func:`repro.core.engine.make_fused_rounds`): the threefry key schedule
moves on device bit-exactly, eval runs at an on-device cadence, and the
host pays one dispatch + one log sync per R rounds (DESIGN.md §6).
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import time
from typing import Any, Callable, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import tracing
from repro.core.client import ClientHP, Task, make_client_update
from repro.core.comm import BlockTiming, CommMeter
from repro.core.engine import (BatchedRoundEngine, pipeline_blocks,
                               task_uses_conv)
from repro.core.knobs import (DEFAULT_PIPELINE_DEPTH,
                              DEFAULT_ROUNDS_PER_DISPATCH, ENGINES,
                              parse_pipeline_blocks,
                              parse_rounds_per_dispatch, validate_engine)
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

    ``engine``: "auto" (batched when the client datasets stack — ragged
    batch counts are padded and masked, DESIGN.md §5 — and the batched
    traversal is a measured win for the task/backend; on CPU conv tasks
    stay sequential, see DESIGN.md §4), "batched" (forced), or
    "sequential".

    ``rounds_per_dispatch``: how many rounds one device dispatch
    executes (DESIGN.md §6).  1 = the classic one-dispatch-per-round
    loop; R > 1 fuses blocks of R rounds into a single XLA program via
    :func:`repro.core.engine.make_fused_rounds` (``run_block``), paying
    one host round-trip per block.  "auto" resolves to 1 whenever the
    round engine is sequential (conv tasks on CPU per the §4 policy —
    there is no batched program to fuse) and to the measured
    ``knobs.DEFAULT_ROUNDS_PER_DISPATCH`` otherwise.

    ``pipeline_blocks``: double-buffer fused block dispatches against
    the host-side log processing (``run_pipelined``, DESIGN.md §7).
    "auto" turns the pipeline on exactly when there is a fused batched
    block to overlap (batched engine, ``rounds_per_dispatch > 1``);
    "on"/"off" force it (on the sequential engine "on" degrades to the
    serial block loop — there is no async dispatch to overlap).
    """

    def __init__(self, task: Task, strategy: Strategy, hp: ClientHP,
                 client_data: Sequence[Any], rng: jax.Array,
                 model_bytes: Optional[int] = None, engine: str = "auto",
                 rounds_per_dispatch: Union[int, str] = 1,
                 pipeline_blocks: Union[bool, str] = "auto"):
        validate_engine(engine)
        rpd = parse_rounds_per_dispatch(rounds_per_dispatch)
        pipe = parse_pipeline_blocks(pipeline_blocks)
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
        self._engine: Optional[BatchedRoundEngine] = None
        if engine != "sequential" and self.n_clients > 0:
            # measured policy (DESIGN.md §4): on CPU, conv tasks run
            # faster as per-client dispatches than under any batched
            # client-axis traversal, so engine="auto" keeps them
            # sequential; engine="batched" forces the batched engine
            want = engine == "batched" or not (
                jax.default_backend() == "cpu"
                and task_uses_conv(
                    task, self.global_params,
                    jax.tree.map(lambda a: a[0], self.client_data[0])))
            if want:
                try:
                    self._engine = BatchedRoundEngine(task, strategy, hp,
                                                      self.client_data)
                except ValueError:
                    if engine == "batched":
                        raise
        self.engine = "batched" if self._engine is not None else "sequential"
        # auto: fuse only where there is a batched round program to fuse
        # (the §4 conv-on-CPU policy has already resolved to sequential)
        if rpd is None:
            rpd = (DEFAULT_ROUNDS_PER_DISPATCH
                   if self._engine is not None else 1)
        self.rounds_per_dispatch = rpd
        # auto: overlap exactly when there is a fused batched block to
        # overlap; forcing "on" without a batched engine degrades to the
        # serial block loop inside run_pipelined
        if pipe is None:
            pipe = self._engine is not None and rpd > 1
        self.pipeline_blocks = bool(pipe)
        self.rounds_completed = 0
        self._update = None
        if self._engine is None:
            self._update = jax.jit(make_client_update(task, hp, strategy.mh))
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
        with jax.profiler.TraceAnnotation("Server.run_round"), \
                self._counting_paths():
            keys = jax.random.split(self.rng, self.n_clients + 2)
            self.rng, sel_key, ckeys = keys[0], keys[1], keys[2:]
            self.rounds_completed += 1
            if self._engine is not None:
                return self._run_round_batched(sel_key, ckeys)
            return self._run_round_sequential(sel_key, ckeys)

    def _record_counters(self, participants: Optional[Sequence[int]]
                         = None):
        """Log one round's real and computed local SGD steps: the
        participants' (default: every client's) valid batches, and on
        the batched engine every participant's padded row.  Under BWO
        also log the population rows that the participants' generations
        and initial draws hashed, against the rows of full draws."""
        if participants is None:
            participants = range(self.n_clients)
        epochs = self.hp.local_epochs
        real = epochs * sum(self._client_batches[k] for k in participants)
        computed = real
        if self._engine is not None:
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
        ONE fused device dispatch (engine="batched") and return one info
        dict per round, in ``run_round``'s format plus ``eval_loss`` /
        ``eval_acc`` entries on rounds the ``eval_every`` cadence (and
        the block's final round) evaluated on device.

        The fused program carries ``(global_params, rng)`` across rounds
        with the server's exact host key schedule derived on device, so
        a block is bit-identical to ``n_rounds`` ``run_round`` calls —
        including the CommMeter ledger, reconstructed per round by
        ``CommMeter.record_rounds``.  The whole block costs one
        device->host sync (the stacked round logs).

        On the sequential engine this degrades gracefully to a loop of
        ``run_round`` + cadenced ``evaluate`` with the same return
        shape.
        """
        n_rounds = int(n_rounds or self.rounds_per_dispatch)
        if self._engine is None:
            infos = []
            for i in range(n_rounds):
                info = self.run_round()
                if eval_data is not None and eval_every > 0 and (
                        self.rounds_completed % eval_every == 0
                        or i == n_rounds - 1):
                    loss, acc = self.evaluate(eval_data)
                    info["eval_loss"], info["eval_acc"] = loss, acc
                infos.append(info)
            return infos
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
        Requires the batched engine.
        """
        if self._engine is None:
            raise RuntimeError(
                "dispatch_block requires the batched engine; the "
                "sequential fallback has no async block dispatch to "
                "pipeline — use run_block, which degrades gracefully")
        n_rounds = int(n_rounds or self.rounds_per_dispatch)
        with jax.profiler.TraceAnnotation("Server.dispatch_block"), \
                self._counting_paths():
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
        n_rounds = pending.n_rounds
        with jax.profiler.TraceAnnotation("Server.finish_block"):
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("Server.finish_block.sync"):
                # the block's single device->host sync
                out = jax.device_get(pending.logs)
            t1 = time.perf_counter()
            with jax.profiler.TraceAnnotation("Server.finish_block.process"):
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
                infos = self._block_infos(out, n_rounds)
            t2 = time.perf_counter()
            self.meter.record_block_timing(BlockTiming(
                n_rounds=n_rounds, dispatch_s=pending.dispatch_s,
                sync_s=t1 - t0, process_s=t2 - t1,
                total_s=t2 - pending.t_dispatched))
            return infos

    def _block_infos(self, out, n_rounds: int) -> List[dict]:
        """Host-side reconstruction of ``run_round``-shaped info dicts
        from a fused block's fetched log arrays."""
        infos = []
        for r in range(n_rounds):
            scores = out["scores"][r]
            if self.strategy.is_fedx:
                best = int(out["best"][r])
                info = {"best_client": best, "score": float(scores[best]),
                        "scores": [float(s) for s in scores],
                        "engine": "fused"}
            else:
                # FedAvg scores align with the participants list
                info = {"participants": [int(k)
                                         for k in out["participants"][r]],
                        "scores": [float(s) for s in scores],
                        "engine": "fused"}
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

        On the sequential engine this degrades to a serial ``run_block``
        loop: same result shape, no overlap and no overshoot.
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
        if self._engine is None:
            infos, stopped = [], False
            for n in sizes:
                out = self.run_block(n, eval_data, eval_every)
                infos.extend(out)
                if should_stop is not None and should_stop(out):
                    stopped = True
                    break
            return PipelineResult(infos=infos, kept=len(infos),
                                  stopped=stopped)
        results, kept_blocks, stopped = pipeline_blocks(
            lambda n: self.dispatch_block(n, eval_data, eval_every),
            self.finish_block, sizes, depth=depth,
            should_stop=should_stop)
        return PipelineResult(
            infos=[i for blk in results for i in blk],
            kept=sum(len(blk) for blk in results[:kept_blocks]),
            stopped=stopped)

    def _run_round_batched(self, sel_key, ckeys) -> dict:
        if self.strategy.is_fedx:
            new_params, scores, best, *slots = self._engine.fedx_round(
                self.global_params, ckeys)
            self.global_params = new_params
            self.meter.record_fedx_round(fetched_model=True)
            self._record_counters()
            with jax.profiler.TraceAnnotation("Server.run_round.sync"):
                # the round's single device->host sync
                scores, best, slots = jax.device_get((scores, best, slots))
            for s in slots:
                self.meter.record_expert_slots(s)
            best = int(best)
            return {"best_client": best, "score": float(scores[best]),
                    "scores": [float(s) for s in scores],
                    "engine": "batched"}
        new_params, scores, sel, *slots = self._engine.fedavg_round(
            self.global_params, sel_key, ckeys)
        self.global_params = new_params
        self.meter.record_fedavg_round(self._engine.n_participants)
        with jax.profiler.TraceAnnotation("Server.run_round.sync"):
            # the round's single device->host sync; scores align with the
            # participants list (FedX scores cover all clients)
            sel, scores, slots = jax.device_get((sel, scores, slots))
        for s in slots:
            self.meter.record_expert_slots(s)
        self._record_counters(sel)
        return {"participants": [int(k) for k in sel],
                "scores": [float(s) for s in scores],
                "engine": "batched"}

    def _run_round_sequential(self, sel_key, ckeys) -> dict:
        if self.strategy.is_fedx:
            # every client trains + refines, uploads only its score
            scores, params_list, slots = [], [], []
            for k in range(self.n_clients):
                score, params, *counts = self._update(
                    self.global_params, self.client_data[k], ckeys[k])
                scores.append(score)
                params_list.append(params)
                slots += counts
            # one host sync per round, after all clients have dispatched
            with jax.profiler.TraceAnnotation("Server.run_round.sync"):
                scores, slots = jax.device_get((jnp.stack(scores), slots))
                scores = np.asarray(scores)
            if slots:
                self.meter.record_expert_slots(sum(slots))
            best = int(scores.argmin())
            # GetBestModel: one full-model transfer from the winner only
            self.global_params = params_list[best]
            self.meter.record_fedx_round(fetched_model=True)
            self._record_counters()
            return {"best_client": best, "score": float(scores[best]),
                    "scores": [float(s) for s in scores],
                    "engine": "sequential"}
        # ---- FedAvg ----
        m = max(int(self.strategy.client_ratio * self.n_clients), 1)
        sel = jax.random.choice(sel_key, self.n_clients, (m,), replace=False)
        scores, new_params, slots = [], [], []
        for k in sel.tolist():
            score, params, *counts = self._update(
                self.global_params, self.client_data[k], ckeys[k])
            scores.append(score)
            new_params.append(params)
            slots += counts
        self.global_params = jax.tree.map(
            lambda *xs: jnp.mean(jnp.stack(xs), 0), *new_params)
        # one host sync for the participants' scores, after all have
        # dispatched; aligned with the participants list
        with jax.profiler.TraceAnnotation("Server.run_round.sync"):
            scores, slots = jax.device_get((jnp.stack(scores), slots))
            scores = np.asarray(scores)
        if slots:
            self.meter.record_expert_slots(sum(slots))
        self.meter.record_fedavg_round(m)
        self._record_counters(sel.tolist())
        return {"participants": sel.tolist(),
                "scores": [float(s) for s in scores],
                "engine": "sequential"}

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
