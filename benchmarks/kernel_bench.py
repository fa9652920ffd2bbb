"""Kernel micro-benchmarks: the jnp reference compositions of the Pallas
kernels, timed on whatever backend runs them (steady call and first call
with compilation)."""
from __future__ import annotations

import time
from typing import List

import jax
import jax.numpy as jnp

from repro.kernels.bwo_evolve.ops import bwo_evolve, bwo_evolve_reference
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.ssm_scan.ops import ssm_scan
from repro.kernels.ssm_scan.ref import ssm_scan_ref


def _time(fn, *args, n=5):
    """(first_call_us, steady_us): first call pays compilation; both are
    blocked on the result before the timer stops."""
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    first = (time.perf_counter() - t0) * 1e6
    t0 = time.perf_counter()
    for _ in range(n):
        jax.block_until_ready(fn(*args))
    return first, (time.perf_counter() - t0) / n * 1e6   # us


def bench_kernels() -> List[tuple]:
    rows = []
    rng = jax.random.PRNGKey(0)

    # bwo_evolve: fused kernel vs jnp reference composition
    P, D = 8, 1 << 16
    pop = jax.random.normal(rng, (P, D))
    fit = jax.random.uniform(rng, (P,))
    us_first, us_ref = _time(lambda: bwo_evolve_reference(pop, fit, rng))
    rows.append(("kernel/bwo_evolve_ref_jnp", us_ref, f"P={P},D={D}"))
    rows.append(("kernel/bwo_evolve_ref_jnp_compile", us_first,
                 f"P={P},D={D}"))

    # flash attention vs blockwise jnp (CPU, small shape)
    q = jax.random.normal(rng, (1, 512, 4, 64))
    k = jax.random.normal(rng, (1, 512, 2, 64))
    v = jax.random.normal(rng, (1, 512, 2, 64))
    us_first, us_ref = _time(lambda: flash_attention_ref(q, k, v,
                                                         causal=True))
    rows.append(("kernel/flash_attention_ref_jnp", us_ref, "B1 S512 H4 d64"))
    rows.append(("kernel/flash_attention_ref_jnp_compile", us_first,
                 "B1 S512 H4 d64"))

    # ssm scan: pallas-interpret vs lax.scan reference
    B, S, Dm, N = 2, 256, 64, 16
    ks = jax.random.split(rng, 5)
    x = jax.random.normal(ks[0], (B, S, Dm))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, Dm))) * 0.1
    A = -jnp.exp(jax.random.normal(ks[2], (Dm, N)) * 0.3)
    Bc = jax.random.normal(ks[3], (B, S, N))
    Cc = jax.random.normal(ks[4], (B, S, N))
    us_first, us_ref = _time(lambda: ssm_scan_ref(x, dt, A, Bc, Cc))
    rows.append(("kernel/ssm_scan_ref_jnp", us_ref, f"B{B} S{S} D{Dm} N{N}"))
    rows.append(("kernel/ssm_scan_ref_jnp_compile", us_first,
                 f"B{B} S{S} D{Dm} N{N}"))
    return rows
