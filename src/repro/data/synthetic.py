"""Synthetic datasets (the container is offline — no CIFAR-10 download).

``make_cifar_like`` builds a *learnable* 10-class 32x32x3 image problem:
each class has a random smooth template; samples are the template plus
pixel noise and random brightness/shift augmentation.  A CNN that learns
real features separates the classes; a broken optimizer does not — which
is exactly the discriminative power the FL reproduction needs.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro import scopes
from repro.configs.base import ArchConfig
from repro.configs.paper_cnn import CNNConfig
from repro.core.client import Task
from repro.models import cnn as cnn_lib


def _smooth(rng, shape, passes: int = 3):
    x = jax.random.normal(rng, shape)
    for _ in range(passes):
        x = (x + jnp.roll(x, 1, 0) + jnp.roll(x, -1, 0)
             + jnp.roll(x, 1, 1) + jnp.roll(x, -1, 1)) / 5.0
    return x


def make_cifar_like(rng, n_train: int = 10000, n_test: int = 2000,
                    num_classes: int = 10, image_size: int = 32,
                    noise: float = 0.35) -> Tuple[dict, dict]:
    """Returns (train, test) dicts of images (N,32,32,3) fp32 / labels."""
    rt, rl, rn, rlt, rnt, rb = jax.random.split(rng, 6)
    templates = jax.vmap(
        lambda k: _smooth(k, (image_size, image_size, 3)))(
            jax.random.split(rt, num_classes))
    templates = templates / (jnp.std(templates, axis=(1, 2, 3),
                                     keepdims=True) + 1e-6)

    def build(rng_lbl, rng_noise, n):
        labels = jax.random.randint(rng_lbl, (n,), 0, num_classes)
        base = templates[labels]
        k1, k2 = jax.random.split(rng_noise)
        imgs = base + noise * jax.random.normal(k1, base.shape)
        bright = 1.0 + 0.1 * jax.random.normal(k2, (n, 1, 1, 1))
        return {"images": (imgs * bright).astype(jnp.float32),
                "labels": labels.astype(jnp.int32)}

    return build(rl, rn, n_train), build(rlt, rnt, n_test)


def cnn_task(cfg: CNNConfig = CNNConfig()) -> Task:
    def init_params(rng):
        return cnn_lib.cnn_init(rng, cfg)

    def loss_fn(params, batch):
        rng = batch.get("rng") if isinstance(batch, dict) else None
        return cnn_lib.cnn_loss(params, batch["images"], batch["labels"],
                                train=rng is not None, dropout_rng=rng)

    return Task(init_params, loss_fn)


def mlp_task(hidden: int = 200, image_size: int = 32, channels: int = 3,
             num_classes: int = 10) -> Task:
    """The original FedAvg paper's "2NN" model: flatten -> two hidden
    dense layers -> softmax, on the same CIFAR-like images.

    Dense-only clients stay fast under the batched round engine's
    vmap/unroll paths on every backend (vmapped matmuls are just bigger
    GEMMs), unlike the conv CNN whose vmapped/looped convolutions hit
    XLA:CPU slow paths — see DESIGN.md §4.
    """
    d_in = image_size * image_size * channels

    def init_params(rng):
        r1, r2, r3 = jax.random.split(rng, 3)

        def dense(r, m, n):
            return {"w": jax.random.normal(r, (m, n)) * (1.0 / m) ** 0.5,
                    "b": jnp.zeros((n,))}

        return {"fc1": dense(r1, d_in, hidden),
                "fc2": dense(r2, hidden, hidden),
                "out": dense(r3, hidden, num_classes)}

    def loss_fn(params, batch):
        x = batch["images"].reshape(batch["images"].shape[0], -1)
        x = jax.nn.relu(x @ params["fc1"]["w"] + params["fc1"]["b"])
        x = jax.nn.relu(x @ params["fc2"]["w"] + params["fc2"]["b"])
        logits = x @ params["out"]["w"] + params["out"]["b"]
        lp = jax.nn.log_softmax(logits)
        labels = batch["labels"]
        nll = -jnp.take_along_axis(lp, labels[:, None], -1).mean()
        acc = (logits.argmax(-1) == labels).mean()
        return nll, acc

    return Task(init_params, loss_fn)


def make_token_dataset(rng, n_seqs: int, seq_len: int, vocab: int,
                       order: int = 2):
    """Synthetic Markov token streams (learnable LM data for examples)."""
    rk, rs = jax.random.split(rng)
    # sparse transition preference: each context prefers a few tokens
    pref = jax.random.randint(rk, (vocab,), 0, vocab)

    def gen_seq(key):
        def step(tok, k):
            knext, kchoice = jax.random.split(k)
            greedy = pref[tok]
            rand = jax.random.randint(kchoice, (), 0, vocab)
            nxt = jnp.where(jax.random.uniform(knext) < 0.7, greedy, rand)
            return nxt, nxt

        k0, kseq = jax.random.split(key)
        t0 = jax.random.randint(k0, (), 0, vocab)
        _, toks = jax.lax.scan(step, t0, jax.random.split(kseq, seq_len))
        return toks

    toks = jax.vmap(gen_seq)(jax.random.split(rs, n_seqs))
    return {"tokens": toks.astype(jnp.int32),
            "labels": jnp.concatenate(
                [toks[:, 1:], jnp.full((n_seqs, 1), -1, toks.dtype)],
                axis=1).astype(jnp.int32)}


def make_packed_tokens(rng, n_train: int, n_test: int, seq_len: int,
                       vocab: int) -> Tuple[dict, dict]:
    """(train, test) dicts of packed sequences for :func:`lm_task`:
    ``tokens`` (N, seq_len + 1) from :func:`make_token_dataset` and
    ``segments``, two documents a row split at a random position."""
    r_tok, r_cut = jax.random.split(rng)
    n = n_train + n_test
    tokens = make_token_dataset(r_tok, n, seq_len + 1, vocab)["tokens"]
    cut = jax.random.randint(r_cut, (n, 1), 1, seq_len + 1)
    segments = (jnp.arange(seq_len + 1)[None, :] >= cut).astype(jnp.int32)
    data = {"segments": segments, "tokens": tokens}
    return (jax.tree.map(lambda a: a[:n_train], data),
            jax.tree.map(lambda a: a[n_train:], data))


def lm_task(cfg: ArchConfig) -> Task:
    """Next-token language modelling with a registry architecture.

    A batch holds packed sequences ``{"tokens", "segments"}`` of shape
    (B, T + 1): token ids and the document id of each position.  The
    model reads positions ``0..T-1`` (positions restart at each document
    and no query attends outside its document) and predicts the next
    token; only targets in the same document as their input count.  The
    loss and accuracy are means over those targets of the whole batch.
    ``slot_loss``, the training loss where the model's expert layers
    hold a share of the experts, also returns the expert slots of its
    forward pass, ``(expert layers, held + 1)``.  No auxiliary routing
    loss enters the loss.
    """
    from repro.models.transformer import build_model
    model = build_model(cfg)

    def init_params(rng):
        return model.init(rng)

    def sums(params, tokens, segments):
        """(nll sum, hits, targets, slots) of sequences (B, T + 1)."""
        logits, _, _, slots = model.apply(
            params, {"tokens": tokens[:, :-1], "segments": segments[:, :-1]},
            mode="train", with_slots=True)
        with jax.named_scope(scopes.LM_HEAD):
            target = tokens[:, 1:]
            same = (segments[:, 1:] == segments[:, :-1]).astype(jnp.float32)
            logp = jax.nn.log_softmax(logits)
            nll = -jnp.take_along_axis(logp, target[..., None], -1)[..., 0]
            hit = (logits.argmax(-1) == target).astype(jnp.float32)
            return (nll * same).sum(), (hit * same).sum(), same.sum(), slots

    def loss_fn(params, batch):
        # one sequence at a time: a test set of any size costs one
        # sequence's activations
        def one(carry, seq):
            return carry + jnp.stack(sums(params, seq[0][None],
                                          seq[1][None])[:3]), None
        (nll, hit, n), _ = jax.lax.scan(
            one, jnp.zeros((3,), jnp.float32),
            (batch["tokens"], batch["segments"]))
        n = jnp.maximum(n, 1.0)
        return nll / n, hit / n

    def slot_loss(params, batch):
        # the whole batch at once: a scan would carry a gradient
        # accumulator of the weights it closes over
        nll, _, n, slots = sums(params, batch["tokens"], batch["segments"])
        return nll / jnp.maximum(n, 1.0), slots

    counted = cfg.moe is not None and cfg.moe.experts_held is not None
    return Task(init_params, loss_fn, slot_loss if counted else None)
