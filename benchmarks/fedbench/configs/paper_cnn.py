"""Plain reference of the FedBWO paper's CNN (arXiv:2505.04435 §IV-A).

conv 5x5x32 -> conv 3x3x32 -> maxpool 2 -> conv 5x5x64 -> conv 3x3x64
-> maxpool 2 -> dense 4096->512 (dropout 0.2 in training) -> dense
512->512 -> dense 512->10, ReLU after every layer but the last, SAME
padding.  Initial weights are drawn as the published experiment draws
them: one key per layer, N(0, 1/fan_in) weights, zero biases.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _conv_init(key, kh, kw, cin, cout, dtype):
    w = jax.random.normal(key, (kh, kw, cin, cout), jnp.float32)
    w = w * (kh * kw * cin) ** -0.5
    return {"b": jnp.zeros((cout,), dtype), "w": w.astype(dtype)}


def _dense_init(key, fan_in, fan_out, dtype):
    w = jax.random.normal(key, (fan_in, fan_out), jnp.float32) * fan_in ** -0.5
    return {"b": jnp.zeros((fan_out,), dtype), "w": w.astype(dtype)}


def _flat(cfg) -> int:
    return (cfg["image_size"] // 4) ** 2 * cfg["conv2_filters"]


def init(key, cfg, dtype=jnp.float32):
    k = jax.random.split(key, 7)
    c, f1, f2 = cfg["channels"], cfg["conv1_filters"], cfg["conv2_filters"]
    ka, kb, h = cfg["kernel"], cfg["kernel_b"], cfg["dense_hidden"]
    return {
        "conv1a": _conv_init(k[0], ka, ka, c, f1, dtype),
        "conv1b": _conv_init(k[1], kb, kb, f1, f1, dtype),
        "conv2a": _conv_init(k[2], ka, ka, f1, f2, dtype),
        "conv2b": _conv_init(k[3], kb, kb, f2, f2, dtype),
        "fc1": _dense_init(k[4], _flat(cfg), h, dtype),
        "fc2": _dense_init(k[5], h, h, dtype),
        "out": _dense_init(k[6], h, cfg["num_classes"], dtype),
    }


def _conv(p, x):
    y = jax.lax.conv_general_dilated(
        x, p["w"], window_strides=(1, 1), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return jax.nn.relu(y + p["b"])


def _pool(x):
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                 (1, 2, 2, 1), (1, 2, 2, 1), "VALID")


def logits(params, images, cfg, dropout_key=None):
    """images (B, H, W, C) -> logits (B, classes); dropout when a key is
    given (training)."""
    x = _conv(params["conv1b"], _conv(params["conv1a"], images))
    x = _pool(x)
    x = _conv(params["conv2b"], _conv(params["conv2a"], x))
    x = _pool(x)
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(x @ params["fc1"]["w"] + params["fc1"]["b"])
    rate = cfg["dropout"]
    if dropout_key is not None and rate > 0:
        keep = jax.random.bernoulli(dropout_key, 1 - rate, x.shape)
        x = jnp.where(keep, x / (1 - rate), 0).astype(x.dtype)
    x = jax.nn.relu(x @ params["fc2"]["w"] + params["fc2"]["b"])
    return x @ params["out"]["w"] + params["out"]["b"]


def forward_macs(cfg) -> int:
    """Multiply-accumulates of one image's forward pass."""
    s, c = cfg["image_size"], cfg["channels"]
    f1, f2 = cfg["conv1_filters"], cfg["conv2_filters"]
    ka, kb, h = cfg["kernel"], cfg["kernel_b"], cfg["dense_hidden"]
    convs = (s * s * (ka * ka * c * f1 + kb * kb * f1 * f1)
             + (s // 2) ** 2 * (ka * ka * f1 * f2 + kb * kb * f2 * f2))
    dense = _flat(cfg) * h + h * h + h * cfg["num_classes"]
    return convs + dense
