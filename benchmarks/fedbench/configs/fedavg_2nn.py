"""Plain reference of McMahan et al.'s "2NN" (arXiv:1602.05629 §3).

flatten -> dense 200 -> ReLU -> dense 200 -> ReLU -> dense 10, on
CIFAR-shaped 32x32x3 input.  Initial weights: one key per layer,
N(0, 1/fan_in) weights, zero biases.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _dense_init(key, fan_in, fan_out, dtype):
    w = jax.random.normal(key, (fan_in, fan_out), jnp.float32)
    w = w * (1.0 / fan_in) ** 0.5
    return {"b": jnp.zeros((fan_out,), dtype), "w": w.astype(dtype)}


def _d_in(cfg) -> int:
    return cfg["image_size"] ** 2 * cfg["channels"]


def init(key, cfg, dtype=jnp.float32):
    k = jax.random.split(key, 3)
    h = cfg["hidden"]
    return {"fc1": _dense_init(k[0], _d_in(cfg), h, dtype),
            "fc2": _dense_init(k[1], h, h, dtype),
            "out": _dense_init(k[2], h, cfg["num_classes"], dtype)}


def logits(params, images, cfg, dropout_key=None):
    x = images.reshape(images.shape[0], -1)
    x = jax.nn.relu(x @ params["fc1"]["w"] + params["fc1"]["b"])
    x = jax.nn.relu(x @ params["fc2"]["w"] + params["fc2"]["b"])
    return x @ params["out"]["w"] + params["out"]["b"]


def forward_macs(cfg) -> int:
    h = cfg["hidden"]
    return _d_in(cfg) * h + h * h + h * cfg["num_classes"]
