"""DeepSeek-V2-Lite's share against its plain reference
(``configs/deepseek_v2_lite.py``), at a small size on the CPU with every
mechanism present: latent attention without a query low-rank (2 heads,
latent 16, rope 8, no-rope 16, value 16) with YaRN, a dense first layer,
2 expert layers of 16 experts of which 4 are held, top-3, 2 shared
experts, vocabulary 64.  The program runs at ``highest`` precision here,
as the reference does, so the two differ only in how XLA orders and
fuses their sums.

The configuration files are the cell's own, copied with small sizes
(``tiny`` below): the same reference module, the same keys."""
import dataclasses
import json
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedbench import check, gen, harness, spec
from fedbench.reference import Reference
from repro.configs import ARCHS, MLAConfig
from repro.configs.deepseek_v2_lite import SHARE
from repro.core.api import build_experiment
from repro.core.protocol import StopConditions, run_federated
from repro.data.synthetic import lm_task
from repro.models import moe as moe_lib
from repro.models.attention import mla_softmax_scale, rope_inv_freq
from repro.models.transformer import build_model

NAME = "deepseek_v2_lite"
TINY = dataclasses.replace(
    SHARE, name="deepseek-v2-lite-tiny", num_layers=3, d_model=32,
    num_heads=2, num_kv_heads=2, d_ff=48, vocab_size=64, head_dim=24,
    moe=dataclasses.replace(SHARE.moe, num_experts=16, top_k=3,
                            num_shared_experts=2, expert_d_ff=16,
                            experts_held=4),
    mla=MLAConfig(kv_lora_rank=16, q_lora_rank=None, qk_rope_head_dim=8,
                  qk_nope_head_dim=16, v_head_dim=16))
SIZES = dict(name="tiny_dsv2", seq_len=32, hidden_size=32,
             num_attention_heads=2, num_key_value_heads=2,
             intermediate_size=48, moe_intermediate_size=16,
             n_routed_experts=4, router_outputs=16, num_experts_per_tok=3,
             n_shared_experts=2, num_hidden_layers=3,
             first_k_dense_replace=1, kv_lora_rank=16, qk_rope_head_dim=8,
             qk_nope_head_dim=16, v_head_dim=16, vocab_size=64)
# Program and reference both in float32 at ``highest`` precision on the
# CPU: they differ by the order of their sums, a few 1e-7 of a value
# (5e-7 read on gradients).  A wrong gate, mask, rope or scale moves the
# loss by 1e-3 or more.
RTOL = 2e-5


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """The cell's configuration files at the small size: the JSON with
    the sizes above, the reference module beside it."""
    root = tmp_path_factory.mktemp("tiny_dsv2")
    cfg = dict(spec.config(NAME), **SIZES)
    path = root / "tiny_dsv2.json"
    path.write_text(json.dumps(cfg))
    shutil.copy(spec.HERE / "configs" / f"{NAME}.py", root / "tiny_dsv2.py")
    return str(path)


@pytest.fixture(scope="module")
def ref(tiny):
    return spec.model(tiny)


@pytest.fixture(scope="module")
def cfg(tiny):
    return spec.config(tiny)


@pytest.fixture(scope="module")
def weights(ref, cfg):
    key = jax.random.PRNGKey(5)
    return build_model(TINY).init(key), ref.init(key, cfg)


def packed(seed, rows=3, length=33, docs=3):
    rng = np.random.default_rng(seed)
    return {"segments": jnp.asarray(np.sort(rng.integers(0, docs,
                                                         (rows, length)), 1),
                                    jnp.int32),
            "tokens": jnp.asarray(rng.integers(0, 64, (rows, length)),
                                  jnp.int32)}


def close(a, b, rtol=RTOL):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    scale = max(np.abs(b).max(), 1e-30)
    assert np.abs(a - b).max() <= rtol * scale, np.abs(a - b).max() / scale


# ------------------------------------------------------------ weights --
def test_init_gives_the_programs_leaves_in_order(weights):
    prog, plain = weights
    a = jax.tree_util.tree_flatten_with_path(prog)[0]
    b = jax.tree_util.tree_flatten_with_path(plain)[0]
    assert [p for p, _ in a] == [p for p, _ in b]
    for (path, x), (_, y) in zip(a, b):
        np.testing.assert_array_equal(x, y, err_msg=jax.tree_util.keystr(
            path))


def test_share_config_matches_the_cell_file():
    c = spec.config(NAME)
    m, mla = SHARE.moe, SHARE.mla
    assert (c["hidden_size"], c["num_attention_heads"], c["vocab_size"],
            c["num_hidden_layers"], c["first_k_dense_replace"],
            c["intermediate_size"], c["rms_norm_eps"], c["rope_theta"]) == (
        SHARE.d_model, SHARE.num_heads, SHARE.vocab_size, SHARE.num_layers,
        SHARE.first_k_dense, SHARE.d_ff, SHARE.norm_eps, SHARE.rope_theta)
    assert (c["moe_intermediate_size"], c["n_routed_experts"],
            c["router_outputs"], c["num_experts_per_tok"],
            c["n_shared_experts"], c["norm_topk_prob"]) == (
        m.expert_d_ff, m.experts_held, m.num_experts, m.top_k,
        m.num_shared_experts, m.renormalize)
    assert (c["kv_lora_rank"], c["q_lora_rank"], c["qk_nope_head_dim"],
            c["qk_rope_head_dim"], c["v_head_dim"]) == (
        mla.kv_lora_rank, mla.q_lora_rank, mla.qk_nope_head_dim,
        mla.qk_rope_head_dim, mla.v_head_dim)
    y = c["rope_scaling"]
    assert (y["factor"], y["original_max_position_embeddings"],
            y["beta_fast"], y["beta_slow"], y["mscale"],
            y["mscale_all_dim"]) == dataclasses.astuple(SHARE.rope_scaling)
    shapes = jax.eval_shape(build_model(SHARE).init, jax.random.PRNGKey(0))
    assert sum(l.size for l in jax.tree.leaves(shapes)) == c["params"]
    assert 4 * c["params"] == 2140243968


# -------------------------------------------------------- the forward --
def test_logits_loss_and_gradients_match(weights, ref, cfg):
    prog, plain = weights
    batch = packed(0)
    task = lm_task(TINY)
    model = build_model(TINY)
    with jax.default_matmul_precision("highest"):
        logits = model.apply(prog, {"tokens": batch["tokens"][:, :-1],
                                    "segments": batch["segments"][:, :-1]},
                             mode="train")[0]
        want = jax.vmap(lambda t, s: ref.hidden(plain, t, s, cfg))(
            batch["tokens"][:, :-1], batch["segments"][:, :-1])
        close(logits, want @ plain["lm_head"]["w"])
        got, exp = task.loss_fn(prog, batch), ref.loss(plain, batch, cfg)
        close(got[0], exp[0])
        assert float(got[1]) == float(exp[1])
        g = jax.grad(lambda p: task.slot_loss(p, batch)[0])(prog)
        g_ref = jax.grad(lambda p: ref.loss(p, batch, cfg)[0])(plain)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(g_ref)):
        close(a, b, 1e-5)
    assert float(ref.count(batch, cfg)) == float(
        (batch["segments"][:, 1:] == batch["segments"][:, :-1]).sum())


def test_share_parts_sum_to_the_uncut_layer(ref, cfg):
    """Four chips each hold 4 of the 16 experts: their routed parts, with
    the shared experts counted once, equal the reference layer holding
    all 16."""
    whole = dict(cfg, n_routed_experts=16)
    p = ref.init(jax.random.PRNGKey(7), whole)["groups"]["sub0"]["moe"]
    p = jax.tree.map(lambda a: a[0], p)
    x = jax.random.normal(jax.random.PRNGKey(8), (40, 32))
    with jax.default_matmul_precision("highest"):
        parts = [moe_lib.share_routed(
            jax.tree.map(lambda a: a[4 * c:4 * c + 4] if a.ndim == 3 else a,
                         p), x, TINY, first_expert=4 * c) for c in range(4)]
        total = sum(y for y, _ in parts) + ref.swiglu(p["shared"], x)
        close(total, ref.experts(p, x, whole))
    slots = np.stack([np.asarray(s) for _, s in parts])
    assert slots[:, :4].sum() == 40 * 3
    np.testing.assert_array_equal(slots.sum(1), [40 * 3] * 4)


def test_no_slot_is_dropped_when_every_token_picks_one_expert(ref, cfg):
    """A router forced onto held expert 1 (then 0 and 2): every token's
    three slots land on held experts, none is dropped, and the layer is
    the reference's."""
    p = jax.tree.map(lambda a: a[0], ref.init(jax.random.PRNGKey(9), cfg)
                     ["groups"]["sub0"]["moe"])
    bias = jnp.zeros((32, 16)).at[:, :3].set(jnp.array([50.0, 60.0, 40.0]))
    p = dict(p, router={"w": p["router"]["w"] * 0.0 + bias})
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(10), (64, 32))) + 0.5
    with jax.default_matmul_precision("highest"):
        y, slots = moe_lib.share_routed(p, x, TINY)
        close(y, ref.routed(p, x, cfg))
    np.testing.assert_array_equal(slots, [64, 64, 64, 0, 0])


def test_gates_are_not_renormalised(ref, cfg):
    p = jax.tree.map(lambda a: a[0], ref.init(jax.random.PRNGKey(11), cfg)
                     ["groups"]["sub0"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(12), (20, 32))
    gate, idx = moe_lib.route(p, x, TINY)
    probs = jax.nn.softmax(x @ p["router"]["w"], -1)
    np.testing.assert_allclose(gate, jnp.take_along_axis(probs, idx, -1),
                               rtol=1e-6)
    assert float(gate.sum(-1).max()) < 0.99
    held = ref.gates(p, x, cfg)
    np.testing.assert_allclose(held.sum(-1), jnp.where(
        idx < 4, gate, 0).sum(-1), rtol=1e-6)


def test_yarn_frequencies_and_scale_follow_the_config():
    """DeepSeek-V2's YaRN at the published sizes: rope 64, base 1e4,
    factor 40 over 4,096 original positions, beta 32 / 1, mscale 0.707."""
    inv = np.asarray(rope_inv_freq(SHARE, 64), np.float64)
    base = 1.0 / 10000.0 ** (np.arange(0, 64, 2) / 64)
    # correction dims: floor(64 ln(4096 / (32 * 2 pi)) / (2 ln 1e4)) = 10,
    # ceil(64 ln(4096 / (2 pi)) / (2 ln 1e4)) = 23
    ramp = np.clip((np.arange(32) - 10) / (23 - 10), 0, 1)
    want = base / 40 * ramp + base * (1 - ramp)
    np.testing.assert_allclose(inv, want, rtol=1e-6)
    assert inv[0] == base[0] and inv[-1] == pytest.approx(base[-1] / 40)
    mscale = 0.1 * 0.707 * np.log(40) + 1
    assert mla_softmax_scale(SHARE) == pytest.approx(192 ** -0.5
                                                     * mscale ** 2)
    assert mscale ** 2 == pytest.approx(1.5896, abs=1e-4)


def test_other_documents_are_not_moved(weights):
    """Changing every token of one document leaves the other documents'
    loss terms as they were."""
    prog, _ = weights
    model = build_model(TINY)
    seg = jnp.asarray([[0] * 10 + [1] * 12 + [2] * 11], jnp.int32)
    tok = packed(3, rows=1)["tokens"]
    other = jnp.where(seg == 1, (tok + 17) % 64, tok)

    def per_position(tokens):
        logits = model.apply(prog, {"tokens": tokens[:, :-1],
                                    "segments": seg[:, :-1]},
                             mode="train")[0]
        logp = jax.nn.log_softmax(logits)
        return jnp.take_along_axis(logp, tokens[:, 1:, None], -1)[0, :, 0]

    with jax.default_matmul_precision("highest"):
        a, b = per_position(tok), per_position(other)
    keep = np.asarray((seg[0, :-1] != 1) & (seg[0, 1:] == seg[0, :-1]))
    # equal up to the order of sums (the expert sort moves rows)
    np.testing.assert_allclose(np.asarray(a)[keep], np.asarray(b)[keep],
                               rtol=1e-6)
    moved = np.asarray((seg[0, :-1] == 1) & (seg[0, 1:] == 1))
    assert np.abs(np.asarray(a - b))[moved].min() > 0


# ------------------------------------------------------------ the FL --
CLIENTS, ROUNDS = 3, 2
FL = dict(strategy="fedbwo", local_epochs=1, lr=0.3, mh_pop=4,
          mh_generations=2, fitness_batches=1, eval_every=1,
          vectorize="scan", genome="tensor", genome_scale=0.05)
TRAFFIC = dict(kind="tokens", n_clients=CLIENTS, batch_size=2, n_batches=2,
               n_test=4, seq_len=32, vocab_size=64, topics=4,
               dirichlet_alpha=0.5, doc_len_median=12, doc_len_sigma=0.8,
               p_successor=0.8, successors=2, zipf_s=1.1, partition_seed=1)


@pytest.fixture(scope="module")
def fl_run(tiny):
    """Two FedBWO rounds of the program through ``build_experiment`` on
    the harness's own FLConfig and data, and the reference's run."""
    mp = pytest.MonkeyPatch()
    mp.setitem(ARCHS, TINY.name, TINY)
    bwo = spec.workload("mlp2nn_fedbwo_noniid")["protocol"]["bwo"]
    cell = {"config": tiny, "traffic": TRAFFIC,
            "fl": dict(FL, arch=TINY.name),
            "protocol": dict(fitness_batches=1, genome="tensor",
                             genome_scale=0.05, bwo=bwo)}
    data = gen.generate(gen.traffic(TRAFFIC), 2**33 + 21)
    try:
        flcfg = harness.fl_config(cell, spec.config(tiny), data.server_seed)
        with jax.default_matmul_precision("highest"):
            exp = build_experiment(
                flcfg, client_data=[jax.device_put(c) for c in data.clients],
                eval_data=jax.device_put(data.test))
            logs = []
            for _ in range(ROUNDS):
                logs += [harness.round_log(rl) for rl in run_federated(
                    exp.server, exp.eval_data, StopConditions(
                        max_rounds=1, patience=2, tau=2.0), eval_every=1)]
    finally:
        mp.undo()
    ref = Reference(spec.model(tiny), spec.config(tiny), harness.protocol(cell),
                    data.clients, data.test, data.server_seed)
    return exp, logs, ref.run(ROUNDS, keep_after=[ROUNDS])


def test_fl_winner_bytes_and_expert_slots(fl_run):
    exp, logs, _ = fl_run
    meter = exp.server.meter
    assert exp.server.engine == "batched"
    assert check.winner_not_argmin(logs) == 0
    assert meter.model_bytes == 4 * sum(
        l.size for l in jax.tree.leaves(exp.server.global_params))
    assert check.bytes_rounds_off(meter.uplink, meter.downlink, True,
                                  CLIENTS, CLIENTS, meter.model_bytes,
                                  ROUNDS) == 0
    # every step routes each of its tokens' 3 slots once per expert layer
    steps = CLIENTS * TRAFFIC["n_batches"]
    tokens = TRAFFIC["batch_size"] * TRAFFIC["seq_len"]
    assert len(meter.expert_slots) == ROUNDS
    for slots in meter.expert_slots:
        assert slots.shape == (2, 5)
        np.testing.assert_array_equal(slots.sum(1), [steps * tokens * 3] * 2)
    s = meter.expert_slot_summary()
    assert s["rounds"] == ROUNDS and len(s["held"]) == 4
    assert 0 < s["held_share"] < 1


def test_reference_follows_the_fl_run(fl_run):
    """The same winner every round, scores and test losses within the
    tolerance of summation order."""
    _, logs, ref_run = fl_run
    assert [l["best"] for l in logs] == [l["best"] for l in ref_run.logs]
    for got, want in zip(logs, ref_run.logs):
        np.testing.assert_allclose(got["scores"], want["scores"], rtol=RTOL)
        np.testing.assert_allclose(got["eval_loss"], want["eval_loss"],
                                   rtol=RTOL)


def test_forward_macs_count_the_held_share():
    c = spec.config(NAME)
    macs = spec.model(NAME).forward_macs(c)
    t, d = 4096, 2048
    square = 5 * t * t * 16 * (192 + 128)
    mla = 2048 * 3072 + 2048 * 576 + 512 * 4096 + 2048 * 2048
    routed = 0.75 * 3 * d * 1408
    per_token = (5 * mla + 3 * d * 10944 + 4 * (d * 64 + routed
                                                + 2 * 3 * d * 1408)
                 + d * 12800)
    assert macs == int(t * per_token + square)


def test_calibration_driver_runs_one_client_a_chunk():
    """``calibrate_chunk1.py`` caps the reference's chunks at one client
    whatever its memory estimate allows."""
    from fedbench import calibrate_chunk1
    fit = calibrate_chunk1._one_client(
        lambda self, lower, budget, counts: max(counts))
    assert fit(None, None, 1e12, range(1, 5)) == 1
