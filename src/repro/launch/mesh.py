"""Production mesh construction (TPU v5e pods).

Single pod: 256 chips as (data=16, model=16).
Multi-pod:  2 pods = 512 chips as (pod=2, data=16, model=16); the ``pod``
axis is the federation axis in FedX mode (params replicated per pod,
cross-pod traffic = scores + winner weights).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh(n: int = 1, axis: str = "clients"):
    """Small host-device mesh for FL shard_map tests/examples; raises
    when the host has fewer than ``n`` devices."""
    devs = jax.devices()
    if len(devs) < n:
        raise ValueError(f"a {n}-device mesh needs {n} devices, but the "
                         f"{devs[0].platform} host has {len(devs)}")
    devs = devs[:n]
    return jax.make_mesh((len(devs),), (axis,), devices=devs)
