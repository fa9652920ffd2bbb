"""Population meta-heuristic interface.

A :class:`Metaheuristic` evolves a population of flat parameter vectors
``(P, D)`` against a batched fitness function ``fit_fn: (P, D) -> (P,)``
(lower is better).  ``init``/``step`` are pure and jit-friendly; the
population lives on-device and per-generation work is fully vectorized
(no Python GA loops).
"""
from __future__ import annotations

import functools
import math
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend.random import threefry2x32_p

FitFn = Callable[[jnp.ndarray], jnp.ndarray]
State = Dict[str, Any]


class Metaheuristic(NamedTuple):
    name: str
    init: Callable[[jax.Array, jnp.ndarray, int, FitFn], State]
    step: Callable[[jax.Array, State, FitFn], State]
    # (pop, dim) -> (rows, pop): population rows one step draws its
    # mutation for, of the pop rows a full draw covers (BWO only)
    mutation_rows: Optional[Callable[[int, int], Tuple[int, int]]] = None


def rows_drawable(shape, key=None, dtype=jnp.float32) -> bool:
    """Whether :func:`draw_rows` draws only the rows asked for.

    Under ``jax_threefry_partitionable`` element ``(r, j)`` of a
    threefry draw of shape ``(P, D)`` hashes the 64-bit counter
    ``r * D + j`` and nothing else, so any row can be drawn alone.  That
    needs a threefry key (``key``'s own implementation, or the default
    one for raw ``uint32`` keys and when ``key`` is None), a float32
    result, and ``P * D < 2**32`` (the counter's high word is then 0).
    """
    if not jax.config.jax_threefry_partitionable:
        return False
    if key is not None and jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        impl = str(jax.random.key_impl(key))
    else:
        impl = jax.config.jax_default_prng_impl
    return (impl == "threefry2x32" and jnp.dtype(dtype) == jnp.float32
            and math.prod(shape) < 2 ** 32)


def _uniform_bits(bits, lo, hi):
    """``jax.random.uniform``'s float32 arithmetic on 32 random bits."""
    lo, hi = jnp.float32(lo), jnp.float32(hi)
    one = jax.lax.bitcast_convert_type(jnp.float32(1.0), jnp.uint32)
    floats = jax.lax.bitcast_convert_type(
        (bits >> jnp.uint32(9)) | one, jnp.float32) - jnp.float32(1.0)
    return jax.lax.max(lo, floats * (hi - lo) + lo)


def _full_draw(key, shape, kind, dtype, p):
    if kind == "bits":
        return jax.random.bits(key, shape, jnp.uint32)
    if kind == "uniform":
        return jax.random.uniform(key, shape, dtype)
    if kind == "normal":
        return jax.random.normal(key, shape, dtype)
    if kind == "bernoulli":
        return jax.random.bernoulli(key, p, shape)
    raise ValueError(f"unknown draw {kind!r}")


def draw_rows(key, rows, shape, kind: str, dtype=jnp.float32, p=None):
    """``draw(key, shape)[rows]`` of ``jax.random.<kind>``, bit for bit.

    ``kind`` is ``"bits"`` (uint32), ``"uniform"`` on [0, 1),
    ``"normal"`` or ``"bernoulli"`` with probability ``p``.  Where
    :func:`rows_drawable` holds, only ``len(rows)`` rows are hashed: row
    ``r``'s counters are ``r * D + j`` for ``j < D``, the layout
    ``jax.random`` itself uses, and bits become values by jax's own
    arithmetic (uniform: mantissa bits under exponent 0, minus 1;
    normal: ``sqrt(2) * erf_inv`` of a uniform on
    ``(nextafter(-1, 0), 1)``; Bernoulli: uniform ``< p``).  Elsewhere
    the full draw is made and indexed.
    """
    if not rows_drawable(shape, key, dtype):
        return _full_draw(key, shape, kind, dtype, p)[rows]
    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        key = jax.random.key_data(key)
    return _hash_rows(key, jnp.asarray(rows), shape[1], kind, p)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _hash_rows(key, rows, d: int, kind: str, p):
    counts = (rows.astype(jnp.uint32)[:, None] * jnp.uint32(d)
              + jax.lax.iota(jnp.uint32, d)[None, :])
    hi_bits, lo_bits = threefry2x32_p.bind(key[0], key[1],
                                           jnp.zeros_like(counts), counts)
    bits = hi_bits ^ lo_bits
    if kind == "bits":
        return bits
    if kind == "uniform":
        return _uniform_bits(bits, 0.0, 1.0)
    if kind == "normal":
        lo = np.nextafter(np.float32(-1.0), np.float32(0.0))
        u = _uniform_bits(bits, lo, 1.0)
        return jnp.float32(np.sqrt(2)) * jax.lax.erf_inv(u)
    if kind == "bernoulli":
        return _uniform_bits(bits, 0.0, 1.0) < jnp.float32(p)
    raise ValueError(f"unknown draw {kind!r}")


def init_population(rng, x0: jnp.ndarray, pop: int, fit_fn: FitFn,
                    spread: float = 0.02) -> State:
    """Seed a population around x0 (member 0 is x0 itself).

    Members 1.. are rows 1.. of one ``(pop, D)`` normal draw; row 0,
    whose noise is zeroed, is not drawn (:func:`draw_rows`)."""
    d = x0.shape[0]
    noise = draw_rows(rng, jnp.arange(1, pop), (pop, d), "normal", x0.dtype)
    noise = noise * spread * (jnp.abs(x0)[None, :] + 1e-3)
    noise = jnp.concatenate([jnp.zeros((1, d), noise.dtype), noise])
    population = x0[None, :] + noise
    return {"pop": population, "fit": fit_fn(population),
            "t": jnp.zeros((), jnp.int32)}


def init_rows(pop: int, dim: int) -> int:
    """Rows of the ``(pop, dim)`` draw that :func:`init_population`
    hashes."""
    return pop - 1 if rows_drawable((pop, dim)) else pop


def best_member(state: State):
    i = jnp.argmin(state["fit"])
    return state["pop"][i], state["fit"][i]
