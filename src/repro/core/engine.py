"""The FL round engine: every round runs as a block of a fused program.

:func:`make_fused_rounds` compiles a block of ``R`` rounds — every
selected client's local update plus the server aggregation, for each
round — into one XLA program; ``Server.run_round`` is a block of one.

* client datasets are stacked along a leading ``(n_clients, ...)`` axis
  (:func:`stack_clients`); ragged datasets (Dirichlet splits) are
  zero-padded to the longest client and a ``(n_clients, n_batches)``
  validity mask rides along, threaded through ``make_client_update`` so
  padded batches contribute no SGD step and no fitness term
  (DESIGN.md §5);
* ``make_client_update`` runs across that axis under ``jax.vmap`` or a
  ``lax.scan`` device loop, selected by the ``vectorize`` knob on
  :class:`~repro.core.client.ClientHP` (:func:`resolve_vectorize`;
  ``"scan:k"`` chunks the scan so compile time stays flat in the client
  count);
* FedAvg with ``client_ratio < 1`` draws its ``m`` participants on
  device and gathers their shards inside the program, so the block is
  compiled for shape ``(m, ...)`` — one cached executable per
  participant count;
* under ``scan`` the FedX winner is selected with a ``jnp.where``
  streaming reduction — the carry holds only ``(best_score,
  best_params)``, so peak weight memory is O(2 x model) instead of
  O(n_clients x model); FedAvg accumulates a running mean the same way;
* the block donates its incoming ``(global_params, rng)`` buffers
  (``donate_argnums``) on backends that support aliasing.

:func:`make_sharded_fedx_round` / :func:`make_sharded_fedavg_round`
place the same round bodies on a device mesh under ``shard_map``.
"""
from __future__ import annotations

from collections import deque
from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import tracing
from repro.core.client import ClientHP, Task, make_client_update
from repro.core.knobs import parse_vectorize
from repro.metaheuristics import Metaheuristic


def resolve_vectorize(mode: str, backend: Optional[str] = None) -> str:
    """Resolve the ``vectorize`` knob to a concrete client-axis strategy.

    ``vmap``   — one batched program over the client axis.  Fastest on
                 TPU/GPU, but vmapping *conv weights* lowers to grouped
                 convolutions that are slow on XLA:CPU.
    ``scan``   — ``lax.scan`` device loop, O(2 x model) weight memory,
                 compact compile.  A ``"scan:k"`` suffix unrolls k scan
                 iterations per loop step (repro.core.knobs).
    ``auto``   — ``scan`` on CPU, ``vmap`` elsewhere.
    """
    base, _ = parse_vectorize(mode)
    if base != "auto":
        return base
    backend = backend or jax.default_backend()
    return "scan" if backend == "cpu" else "vmap"


def _scan_unroll(vectorize: str, n: int) -> int:
    """lax.scan ``unroll`` for a client-axis scan of length ``n``: the
    ':k' chunk."""
    _, chunk = parse_vectorize(vectorize)
    return max(1, min(chunk, max(n, 1)))


def stack_clients(client_data: Sequence[Any]):
    """Stack per-client pytrees along a new leading client axis.

    Returns ``(stacked, mask)``.  Ragged *leading* (batch-count) axes —
    e.g. a Dirichlet split — are zero-padded to the longest client, and
    ``mask`` is a ``(n_clients, max_batches)`` bool array marking the
    valid rows (all-True when the clients were already uniform).  A
    zero-length leading axis (a client that received no batches at all,
    possible under extreme Dirichlet skew) is handled like any other
    ragged length: padded up to the longest client with an all-``False``
    mask row — callers that cannot train an empty client (e.g.
    :class:`BatchedRoundEngine`) detect those rows and raise.
    ``(None, None)`` when the clients are genuinely unstackable:
    mismatched tree structures, trailing batch shapes, dtypes, or
    inconsistent leading dims within one client.
    """
    empty = (None, None)
    if not client_data:
        return empty
    ref = jax.tree.structure(client_data[0])
    ref_leaves = jax.tree.leaves(client_data[0])
    lens = []
    for d in client_data:
        if jax.tree.structure(d) != ref:
            return empty
        leaves = jax.tree.leaves(d)
        heads = {l.shape[0] if l.ndim else None for l in leaves}
        if len(heads) != 1 or None in heads:
            return empty
        lens.append(heads.pop())
        if any(a.shape[1:] != b.shape[1:] or a.dtype != b.dtype
               for a, b in zip(leaves, ref_leaves)):
            return empty
    max_len = max(lens)

    def pad_to(a):
        if a.shape[0] == max_len:
            return a
        width = [(0, max_len - a.shape[0])] + [(0, 0)] * (a.ndim - 1)
        return jnp.pad(a, width)

    stacked = jax.tree.map(lambda *xs: jnp.stack([pad_to(x) for x in xs]),
                           *client_data)
    mask = jnp.arange(max_len)[None, :] < jnp.asarray(lens)[:, None]
    return stacked, mask


def _tree_where(pred, a, b):
    return jax.tree.map(lambda x, y: jnp.where(pred, x, y), a, b)


def _donate_argnums(enabled: bool = True, argnums: Tuple[int, ...] = (0,),
                    backend: Optional[str] = None):
    """Donation argnums for the round/block jits on ``backend``.

    Buffer donation is a no-op (plus a warning per call) on CPU, so it
    is only enabled elsewhere.  The backend is resolved *here, per
    build* — callers that know their target backend pass it explicitly
    (mirroring :func:`resolve_vectorize`), so a round function built
    under a non-default backend context doesn't bake in the donation
    decision of whatever ``jax.default_backend()`` said at build time.
    """
    backend = backend or jax.default_backend()
    return argnums if enabled and backend != "cpu" else ()


# ------------------------------------------------------------ batched --
def _fedx_round_body(task: Task, hp: ClientHP, mh: Metaheuristic,
                     vectorize: str = "auto", masked: bool = False,
                     backend: Optional[str] = None):
    """Un-jitted FedX round: ``round_fn(global_params, data, mask, keys)
    -> (best_params, scores, best_idx)``, and the clients' summed expert
    slots last where the task counts them (``Task.slot_loss``).  Traced
    inline by :func:`make_fused_rounds`, so one XLA program spans a
    whole block of rounds, and per mesh slice by
    :func:`make_sharded_fedx_round`."""
    mode = resolve_vectorize(vectorize, backend)
    client_update = make_client_update(task, hp, mh, masked=masked,
                                       backend=backend)
    update = (client_update if masked
              else lambda p, d, m, k: client_update(p, d, k))

    if mode == "vmap":
        def round_fn(global_params, data, mask, keys):
            out = jax.vmap(update, in_axes=(None, 0, 0, 0))(
                global_params, data, mask, keys)
            scores, new = out[:2]
            with jax.named_scope(tracing.SERVER_REDUCE):
                best = jnp.argmin(scores)
                winner = jax.tree.map(lambda a: a[best], new)
            return (winner, scores, best) + tuple(s.sum(0) for s in out[2:])
    else:
        def round_fn(global_params, data, mask, keys):
            n = keys.shape[0]

            def step(carry, xs):
                best_fit, best_params = carry
                d, msk, k = xs
                score, params, *slots = update(global_params, d, msk, k)
                with jax.named_scope(tracing.SERVER_REDUCE):
                    take = score < best_fit
                    # streaming winner reduction: carry holds one model
                    best_params = _tree_where(take, params, best_params)
                    best_fit = jnp.minimum(score, best_fit)
                return (best_fit, best_params), (score, slots)

            init = (jnp.asarray(jnp.inf, jnp.float32), global_params)
            (_, winner), (scores, slots) = jax.lax.scan(
                step, init, (data, mask, keys),
                unroll=_scan_unroll(vectorize, n))
            with jax.named_scope(tracing.SERVER_REDUCE):
                best = jnp.argmin(scores)
            return (winner, scores, best) + tuple(s.sum(0) for s in slots)

    return round_fn


def _fedavg_round_body(task: Task, hp: ClientHP, vectorize: str = "auto",
                       masked: bool = False,
                       on_trace: Optional[Callable[[int], None]] = None,
                       backend: Optional[str] = None):
    """Un-jitted FedAvg round: ``round_fn(global_params, data, mask,
    keys) -> (avg_params, scores)`` over the (already gathered)
    participant axis, and the summed expert slots last where the task
    counts them.  ``on_trace`` is called with the participant count
    each time the body is traced.  See :func:`_fedx_round_body`."""
    mode = resolve_vectorize(vectorize, backend)
    client_update = make_client_update(task, hp, None, masked=masked,
                                       backend=backend)
    update = (client_update if masked
              else lambda p, d, m, k: client_update(p, d, k))

    def round_fn(global_params, data, mask, keys):
        m = keys.shape[0]
        if on_trace is not None:
            on_trace(m)
        if mode == "vmap":
            out = jax.vmap(update, in_axes=(None, 0, 0, 0))(
                global_params, data, mask, keys)
            scores, new = out[:2]
            with jax.named_scope(tracing.SERVER_REDUCE):
                avg = jax.tree.map(lambda a: jnp.mean(a, axis=0), new)
            return (avg, scores) + tuple(s.sum(0) for s in out[2:])

        def step(acc, xs):
            d, msk, k = xs
            score, params, *slots = update(global_params, d, msk, k)
            # running mean accumulated in place (carry buffer)
            with jax.named_scope(tracing.SERVER_REDUCE):
                acc = jax.tree.map(lambda s, p: s + p / m, acc, params)
            return acc, (score, slots)

        with jax.named_scope(tracing.SERVER_REDUCE):
            acc0 = jax.tree.map(jnp.zeros_like, global_params)
        avg, (scores, slots) = jax.lax.scan(
            step, acc0, (data, mask, keys),
            unroll=_scan_unroll(vectorize, m))
        return (avg, scores) + tuple(s.sum(0) for s in slots)

    return round_fn


# -------------------------------------------------------------- fused --
def make_fused_rounds(task: Task, strategy, hp: ClientHP,
                      rounds_per_dispatch: int, *, n_clients: int,
                      vectorize: str = "auto", masked: bool = False,
                      eval_every: int = 0, donate: bool = True,
                      on_trace: Optional[Callable[[int], None]] = None,
                      backend: Optional[str] = None):
    """Fuse ``rounds_per_dispatch`` FL rounds into one XLA dispatch.

    The one round program of the single-chip engine: a block of one is
    ``Server.run_round``, a block of ``R`` is ``Server.run_block``.
    Wraps the single-round bodies (:func:`_fedx_round_body` /
    :func:`_fedavg_round_body`, looked up on this module at build time)
    in an outer ``lax.scan`` over the round axis, carrying
    ``(global_params, rng)``.  FedBWO's protocol has no per-round host
    decision at full participation — clients upload a 4-byte score and
    the server adopts the winner on device — so entire blocks of rounds
    are fusible: the host pays one dispatch and one device->host log
    sync per ``R`` rounds instead of per round.

    Returns jit'd ``block_fn(global_params, rng, data, mask, eval_batch,
    round_offset) -> (new_params, new_rng, logs)`` where ``logs`` holds
    stacked per-round device arrays:

    * FedX:   ``{"scores": (R, n), "best": (R,)}``
    * FedAvg: ``{"scores": (R, m), "participants": (R, m)}``
    * plus ``{"expert_slots": (R, ...)}`` where the task counts expert
      slots (``Task.slot_loss``)
    * plus ``{"eval_loss": (R,), "eval_acc": (R,)}`` when ``eval_every
      > 0`` and an ``eval_batch`` is passed — ``task.loss_fn`` on the
      held-out batch folded into the scan under ``lax.cond``, NaN on
      rounds the cadence skips, so accuracy curves no longer force a
      per-round sync.

    Key schedule: each round derives its keys with ``jax.random.split(
    rng, n_clients + 2) -> (rng, sel_key, client_keys)`` on device, so a
    block of ``R`` is bit-identical to ``R`` blocks of one.  FedAvg
    ``client_ratio < 1`` draws its participants with ``jax.random.
    choice(sel_key, n, (m,), replace=False)`` at fixed ``m`` and gathers
    their shards/mask rows/keys in the program — the executable is
    compiled for the participant count ``m`` only.

    ``round_offset`` (traced scalar) anchors the eval cadence globally:
    round ``round_offset + i`` evaluates when ``(round_offset + i + 1) %
    eval_every == 0`` — and always on the block's last round, so the
    driver has a fresh accuracy at every sync point for its stopping
    conditions.  ``eval_batch`` may be ``None`` (empty pytree) when
    ``eval_every == 0``.

    The params/rng carries are donated across the block
    (``donate_argnums``) on backends that support aliasing.
    """
    n_rounds = int(rounds_per_dispatch)
    if n_rounds < 1:
        raise ValueError(
            f"rounds_per_dispatch={rounds_per_dispatch!r} must be >= 1")
    backend = backend or jax.default_backend()
    is_fedx = getattr(strategy, "is_fedx", False)
    if is_fedx:
        round_body = _fedx_round_body(task, hp, strategy.mh, vectorize,
                                      masked, backend)
        m = n_clients
    else:
        round_body = _fedavg_round_body(task, hp, vectorize, masked,
                                        on_trace, backend)
        m = max(int(strategy.client_ratio * n_clients), 1)

    def block_fn(global_params, rng, data, mask, eval_batch, round_offset):
        do_eval = eval_every > 0 and eval_batch is not None

        def one_round(carry, i):
            params, rng = carry
            # the round's key schedule, derived on device
            keys = jax.random.split(rng, n_clients + 2)
            rng, sel_key, ckeys = keys[0], keys[1], keys[2:]
            if is_fedx:
                new_params, scores, best, *slots = round_body(
                    params, data, mask, ckeys)
                log = {"scores": scores, "best": best}
            else:
                # draw the participants on device and gather their
                # shards inside the program at fixed m
                sel = jax.random.choice(sel_key, n_clients, (m,),
                                        replace=False)
                sub = jax.tree.map(lambda a: jnp.take(a, sel, axis=0),
                                   data)
                msk = (None if mask is None
                       else jnp.take(mask, sel, axis=0))
                new_params, scores, *slots = round_body(
                    params, sub, msk, jnp.take(ckeys, sel, axis=0))
                log = {"scores": scores, "participants": sel}
            if slots:
                log["expert_slots"] = slots[0]
            if do_eval:
                due = (round_offset + i + 1) % eval_every == 0
                with jax.named_scope(tracing.EVAL):
                    loss, acc = jax.lax.cond(
                        due | (i == n_rounds - 1),
                        lambda p: tuple(jnp.asarray(v, jnp.float32)
                                        for v in task.loss_fn(p,
                                                              eval_batch)),
                        lambda p: (jnp.full((), jnp.nan, jnp.float32),) * 2,
                        new_params)
                log["eval_loss"], log["eval_acc"] = loss, acc
            return (new_params, rng), log

        (params, rng), logs = jax.lax.scan(
            one_round, (global_params, rng), jnp.arange(n_rounds))
        return params, rng, logs

    return jax.jit(block_fn,
                   donate_argnums=_donate_argnums(donate, argnums=(0, 1),
                                                  backend=backend))


class BatchedRoundEngine:
    """The round executor of :class:`repro.core.Server`: the stacked
    client data on device and the fused block programs
    (:func:`make_fused_rounds`) over it, one per block shape.

    Ragged client datasets are padded to the longest client with a
    validity mask (``self.padded``); genuinely unstackable datasets
    (mismatched structures / trailing shapes / dtypes) and empty shards
    raise ``ValueError`` at construction.  FedAvg's ``m = max(C * n,
    1)`` participants are drawn and gathered inside the block.
    ``traced_participant_counts`` records the participant count of every
    block trace.
    """

    def __init__(self, task: Task, strategy, hp: ClientHP,
                 client_data: Sequence[Any],
                 vectorize: Optional[str] = None,
                 backend: Optional[str] = None):
        stacked, mask = stack_clients(client_data)
        if stacked is None:
            raise ValueError(
                "client datasets are not stackable: tree structures, "
                "trailing batch shapes, and dtypes must match across "
                "clients (ragged batch counts alone are fine — they are "
                "padded and masked)")
        if not bool(mask.any(axis=1).all()):
            empty = jnp.where(~mask.any(axis=1))[0].tolist()
            raise ValueError(
                f"client shards {empty} are empty (0 batches): an "
                f"all-padded client has no data to train or score on — "
                f"extreme Dirichlet skew can starve clients; drop empty "
                f"shards or repartition before building the engine")
        self.n_clients = len(client_data)
        self.data = stacked
        # batches each client row computes, padding included
        self.n_batches = int(mask.shape[1])
        self.padded = not bool(mask.all())
        self.mask = mask if self.padded else None
        self.n_participants = (self.n_clients if strategy.is_fedx else max(
            int(strategy.client_ratio * self.n_clients), 1))
        # the target backend is resolved once, here, and passed through
        # every block build so vectorize + donation decisions can't
        # drift with a later jax.default_backend() change
        self.backend = backend or jax.default_backend()
        self._task, self._strategy, self._hp = task, strategy, hp
        self._spec = vectorize if vectorize is not None else hp.vectorize
        self._fused = {}
        self.traced_participant_counts: List[int] = []

    def fused_rounds(self, rounds_per_dispatch: int, eval_every: int = 0):
        """The R-round fused block function (:func:`make_fused_rounds`)
        for this engine's task/strategy/data layout, cached per
        ``(rounds_per_dispatch, eval_every)`` so each block shape
        compiles once."""
        key = (int(rounds_per_dispatch), int(eval_every))
        fn = self._fused.get(key)
        if fn is None:
            fn = make_fused_rounds(
                self._task, self._strategy, self._hp, key[0],
                n_clients=self.n_clients, vectorize=self._spec,
                masked=self.padded, eval_every=key[1],
                on_trace=self.traced_participant_counts.append,
                backend=self.backend)
            self._fused[key] = fn
        return fn

    def run_block(self, global_params, rng, rounds_per_dispatch: int,
                  eval_batch=None, eval_every: int = 0,
                  round_offset: int = 0):
        """Dispatch one fused block: ``-> (params, rng, logs)`` with
        ``logs`` the stacked per-round device arrays (one host sync for
        the whole block when the caller fetches them)."""
        block = self.fused_rounds(
            rounds_per_dispatch,
            eval_every if eval_batch is not None else 0)
        return block(global_params, rng, self.data, self.mask,
                     eval_batch, jnp.asarray(round_offset, jnp.int32))


# ----------------------------------------------------------- pipeline --
def pipeline_blocks(dispatch: Callable[[Any], Any],
                    finish: Callable[[Any], Any],
                    schedule, depth: int = 2,
                    should_stop: Optional[Callable[[Any], bool]] = None):
    """Generic double-buffered dispatch/finish driver (DESIGN.md §7).

    Pulls block specs lazily from ``schedule``, keeps up to ``depth``
    dispatched blocks in flight, and finishes them in dispatch order:
    with ``depth=2`` (classic double buffering) block ``k+1`` is
    dispatched *before* block ``k`` is finished, so — with an
    asynchronous dispatch like JAX's — the host work inside ``finish``
    (device->host sync + log processing) overlaps block ``k+1``'s
    device execution.

    ``should_stop(result)`` is consulted after each finish; once it
    returns True no further block is dispatched, but already-dispatched
    blocks are still finished (their side effects — device state, meter
    entries — have already happened), giving a worst-case overshoot of
    ``depth - 1`` blocks.  Returns ``(results, kept, stopped)`` where
    ``results`` covers every dispatched block in order and ``kept``
    counts the leading results up to and including the one that
    triggered the stop (``kept == len(results)`` when nothing did) —
    callers trim their logs to ``results[:kept]``.
    """
    if depth < 1:
        raise ValueError(f"depth={depth} must be >= 1")
    pending = deque()
    results: List[Any] = []
    it = iter(schedule)
    stopped = False
    kept: Optional[int] = None
    while True:
        while not stopped and len(pending) < depth:
            try:
                spec = next(it)
            except StopIteration:
                break
            pending.append(dispatch(spec))
        if not pending:
            break
        res = finish(pending.popleft())
        results.append(res)
        if not stopped and should_stop is not None and should_stop(res):
            stopped, kept = True, len(results)
    return results, len(results) if kept is None else kept, stopped


# ------------------------------------------------------------ sharded --
def _mesh_backend(mesh: Mesh) -> str:
    return mesh.devices.flat[0].platform


def make_sharded_fedx_round(task: Task, hp: ClientHP, mh: Metaheuristic,
                            mesh: Mesh, axis: str = "clients"):
    """Mesh placement of the FedX round: the N clients split into equal
    slices of ``axis``, and each slice runs the single-host round body
    (:func:`_fedx_round_body`) over its own clients with zero
    collectives, down to its local winner.  The cross-slice traffic is
    one fp32 all_gather of every score (N x 4 bytes) plus one
    masked-psum fetch of the global winner's weights (M bytes).  JAX has
    no dynamic-source broadcast, so the fetch is ``psum(where(my_id ==
    winner, w, 0))`` — physically an all-reduce of M bytes, logically
    the paper's single model transfer (DESIGN.md §3).
    """
    backend = _mesh_backend(mesh)
    local_round = _fedx_round_body(task, hp, mh, hp.vectorize,
                                   backend=backend)

    def per_shard(params, data, keys):
        local_best, local_scores = local_round(params, data, None, keys)[:2]
        with jax.named_scope(tracing.SERVER_REDUCE):
            k = local_scores.shape[0]
            scores = jax.lax.all_gather(local_scores, axis, tiled=True)
            # the first global minimum lies on shard winner // k, where it
            # is that shard's own (first) local minimum
            owner = jnp.argmin(scores) // k
            me = jax.lax.axis_index(axis)
            mask = (me == owner).astype(jnp.float32)
            flat, unravel = ravel_pytree(local_best)
            best = jax.lax.psum(flat * mask, axis)          # winner fetch
            return unravel(best), scores

    fn = jax.shard_map(per_shard, mesh=mesh,
                       in_specs=(P(), P(axis), P(axis)),
                       out_specs=(P(), P()),
                       check_vma=False)
    return jax.jit(fn)


def make_sharded_fedavg_round(task: Task, hp: ClientHP, mesh: Mesh,
                              axis: str = "clients"):
    """Mesh placement of FedAvg: each slice averages its own clients
    (:func:`_fedavg_round_body`), then one full-model all-reduce per
    round averages the slices."""
    backend = _mesh_backend(mesh)
    local_round = _fedavg_round_body(task, hp, hp.vectorize,
                                     backend=backend)

    def per_shard(params, data, keys):
        local_avg, local_scores = local_round(params, data, None, keys)[:2]
        with jax.named_scope(tracing.SERVER_REDUCE):
            n = jax.lax.psum(1.0, axis)
            avg = jax.tree.map(
                lambda w: (jax.lax.psum(w.astype(jnp.float32), axis) / n
                           ).astype(w.dtype),
                local_avg)                                  # M bytes x N
            scores = jax.lax.all_gather(local_scores, axis, tiled=True)
            return avg, scores

    fn = jax.shard_map(per_shard, mesh=mesh,
                       in_specs=(P(), P(axis), P(axis)),
                       out_specs=(P(), P()),
                       check_vma=False)
    return jax.jit(fn)
