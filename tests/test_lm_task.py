"""The language-model task through the FL path: ``FLConfig(task="lm",
arch=...)`` -> ``build_experiment`` -> ``Server``, with a model whose
expert layers hold a share of the experts (DeepSeek-V2-Lite's layout at
a small size: latent attention without a query low-rank, YaRN, a dense
first layer, 16 experts of which 4 are held, top-3, 2 shared)."""
import contextlib
import dataclasses
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import scopes
from repro.configs import ARCHS, MLAConfig
from repro.configs.deepseek_v2_lite import CONFIG, SHARE
from repro.core import tracing
from repro.core.api import LM_SEQ_LEN, FLConfig, build_experiment
from repro.core.comm import SCORE_BYTES

TINY = dataclasses.replace(
    SHARE, name="deepseek-v2-lite-tiny", num_layers=3, d_model=32,
    num_heads=2, num_kv_heads=2, d_ff=48, vocab_size=64, head_dim=24,
    moe=dataclasses.replace(SHARE.moe, num_experts=16, top_k=3,
                            num_shared_experts=2, expert_d_ff=16,
                            experts_held=4),
    mla=MLAConfig(kv_lora_rank=16, q_lora_rank=None, qk_rope_head_dim=8,
                  qk_nope_head_dim=16, v_head_dim=16))
CLIENTS, ROUNDS, BATCH, TRAIN = 2, 2, 2, 8


@pytest.fixture
def tiny_arch(monkeypatch):
    monkeypatch.setitem(ARCHS, TINY.name, TINY)
    return TINY.name


def _cfg(arch, **kw):
    base = dict(task="lm", arch=arch, n_clients=CLIENTS, n_train=TRAIN,
                n_test=4, batch_size=BATCH, local_epochs=1, mh_pop=3,
                mh_generations=1, fitness_batches=1, genome="tensor",
                vectorize="scan", lr=0.1, max_rounds=ROUNDS, patience=5,
                tau=2.0)
    return FLConfig(**dict(base, **kw))


def _slots_per_layer():
    """Slots one round routes per expert layer: every step's tokens,
    top-3 each, summed over the clients."""
    steps = CLIENTS * (TRAIN // CLIENTS // BATCH)
    return steps * BATCH * LM_SEQ_LEN * 3


def test_lm_round_runs_from_the_config_alone(tiny_arch):
    result = build_experiment(_cfg(tiny_arch)).run()
    server = result.server
    assert server.engine == "batched" and len(result.logs) == ROUNDS
    meter = server.meter
    for log in result.logs:
        assert log.info["best_client"] == int(np.argmin(log.info["scores"]))
    m = meter.model_bytes
    assert meter.uplink == [CLIENTS * SCORE_BYTES + m] * ROUNDS   # Eq. 2
    assert meter.downlink == [CLIENTS * m] * ROUNDS
    assert len(meter.expert_slots) == ROUNDS
    for slots in meter.expert_slots:
        assert slots.shape == (2, 5)
        np.testing.assert_array_equal(slots.sum(1),
                                      [_slots_per_layer()] * 2)
    s = result.summary()["expert_slots"]
    assert s["rounds"] == ROUNDS
    assert sum(s["held"]) + s["absent"] == ROUNDS * 2 * _slots_per_layer()
    assert s["held_share"] == pytest.approx(
        sum(s["held"]) / (sum(s["held"]) + s["absent"]))
    assert s["max_over_mean"] >= 1.0


def test_every_engine_counts_the_same_slots(tiny_arch):
    """A fused block of two rounds records the slots and scores of two
    blocks of one."""
    runs = [build_experiment(_cfg(tiny_arch, **kw)).run()
            for kw in ({}, dict(rounds_per_dispatch=2))]
    a, b = (r.server.meter.expert_slots for r in runs)
    assert len(a) == len(b) == ROUNDS
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    for la, lb in zip(*(r.logs for r in runs)):
        np.testing.assert_allclose(la.info["scores"], lb.info["scores"],
                                   rtol=1e-5)


def _operations(text):
    """Compiled HLO text less its source tables and op metadata, with
    every instruction and computation renamed by order of appearance
    (XLA derives fusion names from the names it fused): the operations
    and their wiring alone."""
    if "StackFrames" in text:
        text = text[text.index("\n\n", text.index("StackFrames")):]
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    names = {}
    return re.sub(r"%[\w.\-]+",
                  lambda m: names.setdefault(m.group(0), f"%v{len(names)}"),
                  text)


def test_model_scopes_change_no_operation(tiny_arch, monkeypatch):
    """The round program names the model's layers, and without the
    scopes compiles to the same operations and returns the same bits."""
    def round_of():
        server = build_experiment(_cfg(tiny_arch)).server
        eng = server._engine
        block = eng.fused_rounds(1)
        args = (server.global_params, server.rng, eng.data, eng.mask, None,
                jnp.asarray(0, jnp.int32))
        text = block.lower(*args).compile().as_text()
        return text, jax.device_get(block(*args))

    text, out = round_of()
    paths = re.findall(r'op_name="([^"]*)"', text)
    assert {s for s in tracing.MODEL_SCOPES
            if any(s in p for p in paths)} == set(tracing.MODEL_SCOPES)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain_text, plain_out = round_of()
    assert _operations(text) == _operations(plain_text)
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(plain_out)):
        np.testing.assert_array_equal(a, b)


def test_model_layer_imports_nothing_of_the_fl_stack():
    """``models/`` names its layers from the leaf ``repro.scopes``, which
    ``core.tracing`` re-exports: importing the model layer loads nothing
    of ``repro.core``."""
    code = ("import sys, repro.models, repro.scopes; "
            "print(sorted(m for m in sys.modules if m.startswith("
            "('repro.core', 'repro.analysis'))))")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.path.join(
        os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env, check=True,
                         capture_output=True, text=True)
    assert out.stdout.strip() == "[]"
    assert tracing.MODEL_SCOPES is scopes.MODEL_SCOPES


def test_flconfig_checks_the_lm_fields():
    with pytest.raises(ValueError):
        FLConfig(task="lm")
    with pytest.raises(ValueError):
        FLConfig(task="cnn", arch="deepseek-v2-lite-ep8")
    with pytest.raises(ValueError):
        FLConfig(task="lm", arch="deepseek-v2-lite-ep8",
                 partition="dirichlet")
    with pytest.raises(ValueError):
        FLConfig(genome="rows")
    hp = FLConfig(task="lm", arch="deepseek-v2-lite-ep8", genome="tensor",
                  genome_scale=0.1, fitness_batches=1).client_hp()
    assert (hp.subspace, hp.subspace_scale, hp.fitness_batches) == (
        True, 0.1, 1)
    assert FLConfig().client_hp().subspace is False


def test_published_and_share_configs():
    assert CONFIG.num_groups == 26 and SHARE.num_groups == 4
    assert SHARE.moe.experts_held == 8 and SHARE.moe.num_experts == 64
    assert (SHARE.d_model, SHARE.d_ff, SHARE.moe.expert_d_ff) == (
        CONFIG.d_model, CONFIG.d_ff, CONFIG.moe.expert_d_ff)
    assert SHARE.param_dtype == jnp.float32
    # expected active routed experts of the share: 6 x 8 / 64 per token
    routed = 3 * SHARE.d_model * SHARE.moe.expert_d_ff
    assert SHARE.num_params() - SHARE.num_active_params() == int(
        4 * (8 - 0.75) * routed)


@pytest.mark.parametrize("name", ["deepseek-v2-lite", "deepseek-v2-lite-ep8",
                                  "deepseek-v2-236b", "granite-8b"])
def test_reduced_keeps_the_new_fields_valid(name):
    full = ARCHS[name]
    cfg = full.reduced()
    assert cfg.first_k_dense == min(full.first_k_dense, 1)
    assert cfg.num_groups >= 1
    if full.mla is not None:
        assert (cfg.mla.q_lora_rank is None) == (full.mla.q_lora_rank is None)
    if cfg.moe is not None and cfg.moe.experts_held is not None:
        assert cfg.moe.experts_held <= cfg.moe.num_experts
    assert (cfg.head_dim is None) == (full.head_dim is None)
    assert cfg.resolved_head_dim == cfg.d_model // cfg.num_heads


def _share_layer(key):
    d, f = TINY.d_model, TINY.moe.expert_d_ff
    H, E = TINY.moe.experts_held, TINY.moe.num_experts
    k = jax.random.split(key, 4)
    return {"router": {"w": jax.random.normal(k[0], (d, E))},
            "wg": jax.random.normal(k[1], (H, d, f)) * 0.2,
            "wi": jax.random.normal(k[2], (H, d, f)) * 0.2,
            "wo": jax.random.normal(k[3], (H, f, d)) * 0.2}


@pytest.mark.parametrize("favour", [(), (0, 1, 2), (13, 14, 15)])
def test_share_products_run_at_least_a_row_a_token(monkeypatch, favour):
    """The grouped products run over max(held slots, tokens) rows: a
    router that favours no expert, one that puts every slot on a held
    expert (more held slots than tokens) and one that puts every slot on
    an absent expert (no held slot)."""
    from repro.models import moe
    p = _share_layer(jax.random.PRNGKey(3))
    p["router"]["w"] = p["router"]["w"].at[:, list(favour)].add(50.0)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(4),
                                  (40, TINY.d_model))) + 0.5
    rows = []
    real = jax.lax.ragged_dot

    def spy(lhs, rhs, group_sizes, **kw):
        rows.append(int(group_sizes.sum()))
        return real(lhs, rhs, group_sizes, **kw)

    monkeypatch.setattr(jax.lax, "ragged_dot", spy)
    _, slots = moe.share_routed(p, x, TINY)
    held = int(np.asarray(slots)[:-1].sum())
    if favour:
        assert held == (120 if favour[0] < 4 else 0)
    assert rows == [max(held, 40)] * 3


def _primitives(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for j in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(j, "jaxpr", j)
                if hasattr(inner, "eqns"):
                    yield from _primitives(inner)


def test_share_layer_gradient_moves_slot_rows_by_gathers():
    """The training gradient of the share layer scatters no row of the
    model's width: the slot permutation transposes to the inverse
    gather, so the layer's time does not follow where the router writes."""
    from repro.models import moe
    p = _share_layer(jax.random.PRNGKey(5))
    x = jax.random.normal(jax.random.PRNGKey(6), (40, TINY.d_model))
    grad = jax.make_jaxpr(jax.grad(
        lambda p, x: moe.share_routed(p, x, TINY)[0].sum(),
        argnums=(0, 1)))(p, x)
    rows = [e for e in _primitives(grad.jaxpr)
            if e.primitive.name.startswith("scatter")
            and e.invars[0].aval.shape[-1:] == (TINY.d_model,)]
    gathers = [e for e in _primitives(grad.jaxpr)
               if e.primitive.name == "gather"
               and e.outvars[0].aval.shape[-1:] == (TINY.d_model,)]
    assert rows == [] and len(gathers) >= 4
