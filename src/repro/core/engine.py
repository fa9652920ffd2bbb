"""Batched FL round engine: one jit'd device dispatch per round.

The sequential ``Server`` loop dispatches one jit call per client and
synchronizes with the host in between; for FedX it also materializes a
full model copy per client before the argmin.  This module compiles the
*entire round* — every selected client's local update plus the server
aggregation — into a single XLA program:

* client datasets are stacked along a leading ``(n_clients, ...)`` axis
  (:func:`stack_clients`); ragged datasets (Dirichlet splits) are
  zero-padded to the longest client and a ``(n_clients, n_batches)``
  validity mask rides along, threaded through ``make_client_update`` so
  padded batches contribute no SGD step and no fitness term
  (DESIGN.md §5);
* ``make_client_update`` runs across that axis under ``jax.vmap``, a
  ``lax.scan`` device loop, or a Python-unrolled streaming loop,
  selected by the ``vectorize`` knob on :class:`~repro.core.client.
  ClientHP` (see :func:`resolve_vectorize` for the CPU/TPU tradeoff;
  ``"scan:k"`` chunks the scan so compile time stays flat in the
  client count);
* FedAvg with ``client_ratio < 1`` samples its ``m`` participants on
  host and gathers only their shards before dispatch
  (sample-then-stack), so the round executable is compiled for shape
  ``(m, ...)`` — one cached executable per participant count — instead
  of tracing all ``n_clients``;
* the FedX argmin runs **on device** and the winner's weights are
  selected with a ``jnp.where`` streaming reduction — the scan carry
  holds only ``(best_score, best_params)``, so peak weight memory is
  O(2 x model) instead of O(n_clients x model);
* FedAvg accumulates a running parameter sum in the carry the same way,
  and the round function donates the incoming global-params buffer
  (``donate_argnums``) on backends that support aliasing.

``repro.core.distributed`` builds the same per-client update into
shard_map collective schedules; its round builders live here
(:func:`make_sharded_fedx_round` / :func:`make_sharded_fedavg_round`)
so the single-host batched engine and the mesh engine are two
placements of one round-builder.
"""
from __future__ import annotations

from collections import deque
from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree
from jax.sharding import Mesh, PartitionSpec as P

from repro.analysis.walker import (CONV_PRIMITIVES, jaxpr_has_primitive,
                                   loss_uses_conv)
from repro.core import tracing
from repro.core.client import ClientHP, Task, make_client_update
from repro.core.knobs import VECTORIZE_MODES, parse_vectorize
from repro.metaheuristics import Metaheuristic


def resolve_vectorize(mode: str, backend: Optional[str] = None) -> str:
    """Resolve the ``vectorize`` knob to a concrete client-axis strategy.

    ``vmap``   — one batched program over the client axis.  Fastest on
                 TPU/GPU, but vmapping *conv weights* lowers to grouped
                 convolutions that are pathologically slow on XLA:CPU.
    ``scan``   — ``lax.scan`` device loop, O(2 x model) weight memory,
                 compact compile.  Measured fastest batched mode on CPU
                 for dense models (GEMMs are loop-body-safe); XLA:CPU
                 lacks fast conv thunks inside loop bodies, so conv
                 models are ~5x slower here (DESIGN.md §4).  A
                 ``"scan:k"`` suffix unrolls k scan iterations per loop
                 step (repro.core.knobs).
    ``unroll`` — the scan unrolled in Python: still one dispatch and
                 the same streaming reduction.  Keeps CPU convs on the
                 fast conv thunk, but compile time grows ~linearly with
                 n_clients and the measured steady state still trails
                 the sequential loop for conv models.
    ``auto``   — ``scan`` on CPU, ``vmap`` elsewhere.  (Whether to
                 batch *at all* on CPU is the server's engine="auto"
                 decision, which checks the task for convolutions —
                 see :func:`task_uses_conv`.)
    """
    base, _ = parse_vectorize(mode)
    if base != "auto":
        return base
    backend = backend or jax.default_backend()
    return "scan" if backend == "cpu" else "vmap"


def _scan_unroll(vectorize: str, mode: str, n: int) -> int:
    """lax.scan ``unroll`` for a client-axis scan of length ``n``:
    the full length for mode="unroll", else the ':k' chunk."""
    _, chunk = parse_vectorize(vectorize)
    return n if mode == "unroll" else max(1, min(chunk, max(n, 1)))


_CONV_PRIMITIVES = CONV_PRIMITIVES

# One walker, two callers (DESIGN.md §8): the recursive jaxpr traversal
# used here for the conv-on-CPU auto policy is the same one flcheck's
# rules run over full round programs — re-exported so existing engine
# call sites keep working.
_jaxpr_has_primitive = jaxpr_has_primitive


def task_uses_conv(task: Task, params, sample_batch) -> bool:
    """Abstractly trace ``task.loss_fn`` and report whether it lowers to
    convolutions.  Drives the CPU engine="auto" decision: XLA:CPU runs
    convolutions slower under every batched traversal (grouped convs
    under vmap, no fast conv thunk in loop bodies, and measured ~1.5x
    slower even fully unrolled) than as per-client dispatches, so conv
    tasks stay on the sequential engine on CPU.  Returns True (the
    conservative answer) when the trace fails.  Thin wrapper over
    :func:`repro.analysis.walker.loss_uses_conv` (the shared walker).
    """
    return loss_uses_conv(task.loss_fn, params, sample_batch)


def stack_clients(client_data: Sequence[Any], pad: bool = False):
    """Stack per-client pytrees along a new leading client axis.

    With ``pad=False`` (legacy): returns the stacked pytree, or ``None``
    when the clients are not exactly stackable (ragged batch counts or
    mismatched structures).

    With ``pad=True``: returns ``(stacked, mask)``.  Ragged *leading*
    (batch-count) axes — e.g. a Dirichlet split — are zero-padded to the
    longest client, and ``mask`` is a ``(n_clients, max_batches)`` bool
    array marking the valid rows (all-True when the clients were already
    uniform).  A zero-length leading axis (a client that received no
    batches at all, possible under extreme Dirichlet skew) is handled
    like any other ragged length: padded up to the longest client with
    an all-``False`` mask row — callers that cannot train an empty
    client (e.g. :class:`BatchedRoundEngine`) detect those rows and
    raise.  ``(None, None)`` when the clients are genuinely
    unstackable: mismatched tree structures, trailing batch shapes,
    dtypes, or inconsistent leading dims within one client.
    """
    empty = (None, None) if pad else None
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
    if not pad:
        if len(set(lens)) > 1:
            return None
        return jax.tree.map(lambda *xs: jnp.stack(xs), *client_data)
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
    slots last where the task counts them (``Task.slot_loss``).  Jitted
    standalone by :func:`make_batched_fedx_round`; traced inline by the
    multi-round fusion (:func:`make_fused_rounds`) so one XLA program
    spans a whole block of rounds."""
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
                unroll=_scan_unroll(vectorize, mode, n))
            with jax.named_scope(tracing.SERVER_REDUCE):
                best = jnp.argmin(scores)
            return (winner, scores, best) + tuple(s.sum(0) for s in slots)

    return round_fn


def make_batched_fedx_round(task: Task, hp: ClientHP, mh: Metaheuristic,
                            vectorize: str = "auto", donate: bool = True,
                            masked: bool = False,
                            backend: Optional[str] = None):
    """Returns jit'd ``round_fn(global_params, data, mask, keys) ->
    (best_params, scores, best_idx)``.

    ``data``: client datasets stacked to ``(n_clients, ...)`` leaves.
    ``mask``: ``(n_clients, n_batches)`` bool validity rows from
    ``stack_clients(..., pad=True)``, or ``None`` for uniform data
    (``masked=False`` — an empty pytree arg, so both builds share one
    signature).
    ``keys``: ``(n_clients, 2)`` uint32 PRNG keys, one per client.
    ``backend``: target backend for the vectorize/donation decisions
    (default: resolved once here via ``jax.default_backend()``).
    """
    backend = backend or jax.default_backend()
    return jax.jit(_fedx_round_body(task, hp, mh, vectorize, masked,
                                    backend),
                   donate_argnums=_donate_argnums(donate, backend=backend))


def _fedavg_round_body(task: Task, hp: ClientHP, vectorize: str = "auto",
                       masked: bool = False,
                       on_trace: Optional[Callable[[int], None]] = None,
                       backend: Optional[str] = None):
    """Un-jitted FedAvg round: ``round_fn(global_params, data, mask,
    keys) -> (avg_params, scores)`` over the (already gathered)
    participant axis, and the summed expert slots last where the task
    counts them.  See :func:`_fedx_round_body`."""
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
            unroll=_scan_unroll(vectorize, mode, m))
        return (avg, scores) + tuple(s.sum(0) for s in slots)

    return round_fn


def make_batched_fedavg_round(task: Task, hp: ClientHP,
                              vectorize: str = "auto", donate: bool = True,
                              masked: bool = False,
                              on_trace: Optional[Callable[[int], None]]
                              = None,
                              backend: Optional[str] = None):
    """Returns jit'd ``round_fn(global_params, data, mask, keys) ->
    (avg_params, scores)``.

    Shape-polymorphic over the leading participant axis (sample-then-
    stack): the caller samples the ``m`` participants on host, gathers
    their ``(m, ...)`` shards (plus mask rows and keys), and jit caches
    one executable per distinct ``m`` — a round at ``client_ratio < 1``
    never traces or compiles for the full ``n_clients``.  ``on_trace``
    is called with ``m`` each time a new participant count is traced
    (compile-cache accounting/tests).  ``backend`` as in
    :func:`make_batched_fedx_round`.
    """
    backend = backend or jax.default_backend()
    return jax.jit(_fedavg_round_body(task, hp, vectorize, masked, on_trace,
                                      backend),
                   donate_argnums=_donate_argnums(donate, backend=backend))


# -------------------------------------------------------------- fused --
def make_fused_rounds(task: Task, strategy, hp: ClientHP,
                      rounds_per_dispatch: int, *, n_clients: int,
                      vectorize: str = "auto", masked: bool = False,
                      eval_every: int = 0, donate: bool = True,
                      on_trace: Optional[Callable[[int], None]] = None,
                      backend: Optional[str] = None):
    """Fuse ``rounds_per_dispatch`` FL rounds into one XLA dispatch.

    Wraps the single-round bodies (:func:`_fedx_round_body` /
    :func:`_fedavg_round_body`) in an outer ``lax.scan`` over the round
    axis, carrying ``(global_params, rng)``.  FedBWO's protocol has no
    per-round host decision at full participation — clients upload a
    4-byte score and the server adopts the winner on device — so entire
    blocks of rounds are fusible: the host pays one dispatch and one
    device->host log sync per ``R`` rounds instead of per round.

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

    Bit-exactness with ``Server.run_round``: the scan body derives each
    round's keys with the same ``jax.random.split(rng, n_clients + 2)
    -> (rng, sel_key, client_keys)`` schedule the server runs on host —
    threefry is deterministic across the host/device boundary, so the
    key sequence (and everything downstream) is identical.  FedAvg
    ``client_ratio < 1`` moves the sample-then-stack participant choice
    on device: the same ``jax.random.choice(sel_key, n, (m,),
    replace=False)`` at fixed ``m``, followed by an in-program gather of
    the participants' shards/mask rows/keys — the block executable is
    still compiled for the participant count ``m`` only (one cached
    program per distinct ``m``, like the single-round path).

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
            # Server.run_round's host key schedule, derived on device
            keys = jax.random.split(rng, n_clients + 2)
            rng, sel_key, ckeys = keys[0], keys[1], keys[2:]
            if is_fedx:
                new_params, scores, best, *slots = round_body(
                    params, data, mask, ckeys)
                log = {"scores": scores, "best": best}
            else:
                # on-device sample-then-stack: same choice op and key as
                # the host path, gather inside the program at fixed m
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
    """Compiled whole-round executor used by :class:`repro.core.Server`.

    Holds the stacked client data on device and one jit'd round function
    per (task, strategy).  Ragged client datasets are padded to the
    longest client with a validity mask (``self.padded``); genuinely
    unstackable datasets (mismatched structures / trailing shapes /
    dtypes) raise ``ValueError`` at construction and the server falls
    back to its sequential loop.

    FedAvg participation is sample-then-stack: ``fedavg_round`` samples
    the ``m = max(C * n, 1)`` participants on host, gathers their shards
    and dispatches an executable compiled for shape ``(m, ...)``.
    ``traced_participant_counts`` records every participant count the
    round function was traced for (it should stay at one entry).
    """

    def __init__(self, task: Task, strategy, hp: ClientHP,
                 client_data: Sequence[Any],
                 vectorize: Optional[str] = None,
                 backend: Optional[str] = None):
        stacked, mask = stack_clients(client_data, pad=True)
        if stacked is None:
            raise ValueError(
                "client datasets are not stackable: tree structures, "
                "trailing batch shapes, and dtypes must match across "
                "clients (ragged batch counts alone are fine — they are "
                "padded and masked)")
        if mask is not None and not bool(mask.any(axis=1).all()):
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
        self.is_fedx = strategy.is_fedx
        # the target backend is resolved once, here, and passed through
        # every round/block build so vectorize + donation decisions
        # can't drift with a later jax.default_backend() change
        self.backend = backend or jax.default_backend()
        spec = vectorize if vectorize is not None else hp.vectorize
        self.vectorize = resolve_vectorize(spec, self.backend)
        self._task, self._strategy, self._hp, self._spec = (
            task, strategy, hp, spec)
        self._fused = {}
        self.traced_participant_counts: List[int] = []
        if self.is_fedx:
            self.n_participants = self.n_clients
            self._round = make_batched_fedx_round(
                task, hp, strategy.mh, vectorize=spec, masked=self.padded,
                backend=self.backend)
        else:
            self.n_participants = max(
                int(strategy.client_ratio * self.n_clients), 1)
            self._round = make_batched_fedavg_round(
                task, hp, vectorize=spec, masked=self.padded,
                on_trace=self.traced_participant_counts.append,
                backend=self.backend)

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

    def fedx_round(self, global_params, keys):
        """-> (winner_params, scores, best_idx[, expert slots]); one
        dispatch, no sync."""
        return self._round(global_params, self.data, self.mask, keys)

    def fedavg_round(self, global_params, sel_key, keys):
        """-> (avg_params, scores, sel[, expert slots]).

        Sample-then-stack: the participant choice is materialized on
        host, the ``(m, ...)`` shards are gathered outside the round
        program, and the dispatch is one executable shaped for ``m``.
        """
        sel = jax.random.choice(sel_key, self.n_clients,
                                (self.n_participants,), replace=False)
        sub = jax.tree.map(lambda a: jnp.take(a, sel, axis=0), self.data)
        mask = (None if self.mask is None
                else jnp.take(self.mask, sel, axis=0))
        avg, scores, *slots = self._round(global_params, sub, mask,
                                          jnp.take(keys, sel, axis=0))
        return (avg, scores, sel) + tuple(slots)


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
    masked-psum fetch of the global winner's weights (M bytes) — see
    repro.core.distributed.
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
