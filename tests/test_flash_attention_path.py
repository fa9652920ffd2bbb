"""Latent attention's fused flash path: the splash wrapper against its
oracle ``blockwise_attention``, the rule that picks the path, and the
trace-time count of the paths taken.

The kernel runs in interpret mode on the CPU; its TPU compile at the
token cell's widths is in ``test_tpu_compile.py``."""
import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, MLAConfig
from repro.configs.deepseek_v2_lite import SHARE
from repro.core.api import FLConfig, build_experiment
from repro.data.synthetic import lm_task
from repro.kernels.flash_attention import splash
from repro.models import attention
from repro.models.attention import (BLOCKWISE, FLASH, attention_path,
                                    blockwise_attention,
                                    count_attention_paths)
from repro.models.transformer import build_model

H, S, DQK, DV = 2, 512, 192, 128
SCALE = 0.1173                   # not DQK ** -0.5
# bfloat16 operands (8 bits of mantissa) against float32 at `highest`
ATOL, RTOL = 3e-2, 2e-2

TINY = dataclasses.replace(
    SHARE, name="deepseek-v2-lite-tiny", num_layers=3, d_model=32,
    num_heads=2, num_kv_heads=2, d_ff=48, vocab_size=64, head_dim=24,
    moe=dataclasses.replace(SHARE.moe, num_experts=16, top_k=3,
                            num_shared_experts=2, expert_d_ff=16,
                            experts_held=4),
    mla=MLAConfig(kv_lora_rank=16, q_lora_rank=None, qk_rope_head_dim=8,
                  qk_nope_head_dim=16, v_head_dim=16))

# sha256 of the tiny model's train-mode logits and loss gradients on the
# CPU (`_model_bits`), computed on the tree before the flash path came in
PARENT_BITS = ("7f35ecaf828968ce03fc2d4ea9b529abe5023dba49ffabfa439dd428"
               "55aede5c")


def _documents(lengths):
    return jnp.concatenate([jnp.full((1, n), i, jnp.int32)
                            for i, n in enumerate(lengths)], axis=1)


@pytest.mark.parametrize("lengths", [
    (S,),                          # one document
    (200, 100, 212),               # boundaries inside 128-blocks
    (255, 1, 256),                 # a one-token document
], ids=["one", "three_mid_block", "one_token"])
def test_flash_matches_blockwise_forward_and_gradients(lengths):
    """Four blocks of 128 a side, so the kernel skips the blocks above
    the causal diagonal and masks the documents inside the others."""
    r = jax.random.split(jax.random.PRNGKey(len(lengths)), 4)
    q = jax.random.normal(r[0], (1, S, H, DQK))
    k = jax.random.normal(r[1], (1, S, H, DQK))
    v = jax.random.normal(r[2], (1, S, H, DV))
    w = jax.random.normal(r[3], (1, S, H, DV))
    segments = _documents(lengths)

    def flash(q, k, v):
        return splash.causal_flash_attention(q, k, v, segments, scale=SCALE,
                                             block=128, interpret=True)

    def oracle(q, k, v):
        return blockwise_attention(q, k, v, causal=True, window=None,
                                   scale=SCALE, segments=segments)

    def value_and_grads(f):
        return jax.value_and_grad(lambda *x: (f(*x) * w).sum(),
                                  argnums=(0, 1, 2))(q, k, v)

    out = flash(q, k, v)
    assert out.shape == (1, S, H, DV) and out.dtype == jnp.float32
    with jax.default_matmul_precision("highest"):
        want = oracle(q, k, v)
        _, want_grads = value_and_grads(oracle)
    np.testing.assert_allclose(out, want, atol=ATOL, rtol=RTOL)
    _, grads = value_and_grads(flash)
    for g, gw in zip(grads, want_grads):
        scale = float(jnp.abs(gw).max())
        np.testing.assert_allclose(g / scale, gw / scale, atol=ATOL)


def test_flash_refuses_a_sequence_off_the_block():
    x = jnp.zeros((1, 384, 1, 128))
    with pytest.raises(ValueError):
        splash.causal_flash_attention(x, x, x, jnp.zeros((1, 384), jnp.int32),
                                      scale=1.0, block=256, interpret=True)


@pytest.mark.parametrize("backend,mode,seq_len,precision,path", [
    ("tpu", "train", 4096, None, FLASH),
    ("tpu", "prefill", 2 * splash.BLOCK, "default", FLASH),
    ("tpu", "train", 4096, "bfloat16", FLASH),
    ("cpu", "train", 4096, None, BLOCKWISE),
    ("gpu", "train", 4096, None, BLOCKWISE),
    ("tpu", "decode", 4096, None, BLOCKWISE),
    ("tpu", "encode", 4096, None, BLOCKWISE),
    ("tpu", "train", 4096 + 8, None, BLOCKWISE),
    ("tpu", "train", splash.BLOCK // 2, None, BLOCKWISE),
    ("tpu", "train", 4096, "highest", BLOCKWISE),
    ("tpu", "train", 4096, "float32", BLOCKWISE),
])
def test_path_rule(backend, mode, seq_len, precision, path):
    assert attention_path(backend, mode, seq_len, precision) == path


def _model_bits():
    """The tiny model's train-mode logits of two packed sequences and
    the loss gradients of ``lm_task``, hashed; and the paths traced."""
    task = lm_task(TINY)
    params = task.init_params(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 33), 0, 64)
    segments = (jnp.arange(33)[None, :]
                >= jnp.array([[12], [30]])).astype(jnp.int32)
    batch = {"tokens": tokens, "segments": segments}
    with count_attention_paths() as paths:
        logits = build_model(TINY).apply(
            params, {"tokens": tokens[:, :-1],
                     "segments": segments[:, :-1]}, mode="train")[0]
        grads = jax.grad(lambda p: task.slot_loss(p, batch)[0])(params)
    h = hashlib.sha256()
    for a in [logits] + jax.tree.leaves(grads):
        h.update(np.asarray(a).tobytes())
    return h.hexdigest(), paths


def test_cpu_forward_takes_blockwise_with_the_parents_bits():
    """On the CPU every latent-attention call stays on the oracle, and
    the logits and gradients are those of the tree before the flash
    path, bit for bit: one trace of the dense layer and one of the
    scanned expert layers, for the forward and for the gradient."""
    bits, paths = _model_bits()
    assert dict(paths) == {BLOCKWISE: 4}
    assert bits == PARENT_BITS


def test_round_records_the_paths_on_the_meter(monkeypatch):
    monkeypatch.setitem(ARCHS, TINY.name, TINY)
    cfg = FLConfig(task="lm", arch=TINY.name, n_clients=2, n_train=8,
                   n_test=4, batch_size=2, local_epochs=1, mh_pop=3,
                   mh_generations=1, fitness_batches=1, genome="tensor",
                   vectorize="scan", lr=0.1, max_rounds=2, patience=5,
                   tau=2.0)
    result = build_experiment(cfg).run()
    paths = result.server.meter.attention_paths
    assert set(paths) == {BLOCKWISE} and paths[BLOCKWISE] > 0
    assert result.summary()["attention_paths"] == paths
    assert "attention_paths" not in result.server.meter.summary()
    # a compiled program traces nothing when it runs again
    before = dict(paths)
    result.server.run_round()
    assert result.server.meter.attention_paths == before


def test_model_on_the_flash_path_follows_blockwise(monkeypatch):
    """The flash branch of the latent attention, forced on the CPU (the
    kernel in interpret mode), gives the oracle's logits within bfloat16
    rounding, with and without documents."""
    cfg = dataclasses.replace(TINY, first_k_dense=0, num_layers=1,
                              moe=None)
    n = splash.BLOCK
    model = build_model(cfg, max_seq=n)
    params = model.init(jax.random.PRNGKey(2))
    tokens = jax.random.randint(jax.random.PRNGKey(3), (1, n), 0, 64)
    for docs in (None, _documents((300, 1, n - 301))):
        batch = {"tokens": tokens}
        if docs is not None:
            batch["segments"] = docs
        with jax.default_matmul_precision("highest"):
            want = model.apply(params, batch, mode="train")[0]
        with monkeypatch.context() as m:
            m.setattr(attention, "attention_path", lambda *a: FLASH)
            with count_attention_paths() as paths:
                got = model.apply(params, batch, mode="train")[0]
        assert dict(paths) == {FLASH: 1}
        np.testing.assert_allclose(got, want, atol=ATOL, rtol=RTOL)
