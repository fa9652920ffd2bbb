"""The token path, from files alone: a token workload and its
configuration module (``testdata/tiny_lm_tokens.json``,
``testdata/tiny_lm.json`` with ``tiny_lm.py``) run through ``gen``,
``Reference``, ``check`` and ``calibrate`` with no harness code of
their own.  The program has no token task yet, so the fp32 reference
stands in the program's place for ``calibrate``: it reads 0 against
itself, and the control and the half-batch fault read apart from it."""
import types

import jax
import numpy as np
import pytest

from fedbench import calibrate, check, flops, gen, harness, spec

CELL = str(spec.HERE / "testdata" / "tiny_lm_tokens.json")
SEED = 2**33 + 11
TRAJECTORY = ("score_gap_r0", "score_bias_r0", "loss_gap",
              "update_norm_gap", "change_norm_gap")


def cell() -> dict:
    return spec.workload(CELL)


def tokens(seed=SEED, **over) -> gen.Dataset:
    return gen.generate(gen.traffic(dict(cell()["traffic"], **over)), seed)


def arrays(d: gen.Dataset):
    return [c[k] for c in d.clients + [d.test] for k in sorted(c)]


def test_traffic_kind_selects_the_generator():
    assert isinstance(gen.traffic(cell()["traffic"]), gen.TokenTraffic)
    images = spec.workload("cnn_fedbwo_paper")["traffic"]
    assert gen.traffic(images) == gen.Traffic.from_dict(images)
    with pytest.raises(ValueError):
        gen.traffic({"kind": "audio"})


def test_same_seed_gives_the_same_arrays():
    a, b = tokens(), tokens()
    assert a.server_seed == b.server_seed
    for x, y in zip(arrays(a), arrays(b)):
        assert x.dtype == np.int32
        np.testing.assert_array_equal(x, y)


def test_other_seeds_give_the_same_shapes():
    t = gen.traffic(cell()["traffic"])
    a, b = tokens(1), tokens(2**33 + 7)
    assert a.n_batches == b.n_batches == [t.n_batches] * t.n_clients
    assert a.clients[0]["tokens"].shape == (t.n_batches, t.batch_size,
                                            t.seq_len + 1)
    assert a.test["tokens"].shape == (t.n_test, t.seq_len + 1)
    assert [x.shape for x in arrays(a)] == [x.shape for x in arrays(b)]
    assert not all((x == y).all() for x, y in zip(arrays(a), arrays(b)))
    for x in arrays(a):
        assert x.min() >= 0 and x.max() < t.vocab_size


@pytest.mark.parametrize("sigma", [0.0, 1.0])
def test_segments_follow_the_document_lengths(sigma):
    rng = np.random.default_rng(3)
    length = 200
    lengths = gen.doc_lengths(rng, 50, length, 17.0, sigma)
    segments = gen.pack(lengths, length)
    for row, lens in zip(segments, lengths):
        runs = np.bincount(row)
        assert (np.diff(row) >= 0).all() and row[0] == 0
        np.testing.assert_array_equal(runs[:-1], lens[:len(runs) - 1])
        assert 1 <= runs[-1] <= lens[len(runs) - 1]
    if sigma == 0.0:
        assert (lengths == 17).all()


def test_generated_segments_have_the_median_length():
    d = tokens(doc_len_median=12, doc_len_sigma=0.8, n_batches=40)
    whole = []
    for seg in d.clients[0]["segments"].reshape(-1, 33):
        whole += list(np.bincount(seg)[:-1])   # the last one is cut
    assert 9 <= np.median(whole) <= 15


def test_topic_skew_shows_in_unigram_counts():
    def spread(alpha):
        d = tokens(dirichlet_alpha=alpha, n_clients=6, n_batches=60,
                   partition_seed=4)
        v = gen.traffic(cell()["traffic"]).vocab_size
        p = np.stack([np.bincount(c["tokens"].ravel(), minlength=v)
                      for c in d.clients]).astype(float)
        p /= p.sum(1, keepdims=True)
        return np.mean([0.5 * np.abs(p[i] - p[j]).sum()
                        for i in range(6) for j in range(i)])
    assert spread(0.1) > 2 * spread(100.0)


def stand_in_prepare(cell, seed):
    """The fp32 reference's own first rounds in the program's place,
    with the uplink and downlink of Eq. 2."""
    cfg = spec.config(cell["config"])
    traffic = gen.traffic(cell["traffic"])
    data = gen.generate(traffic, seed)
    proto = harness.protocol(cell)
    n = cell["window"]["check_rounds"]
    first = harness.reference(types.SimpleNamespace(
        cell=cell, cfg=cfg, proto=proto, data=data)).run(
        n, keep_after=list(range(1, n + 1)))
    model_bytes = 4 * sum(np.size(a) for a in jax.tree.leaves(first.w0))
    k = traffic.n_clients
    meter = types.SimpleNamespace(
        uplink=[k * check.SCORE_BYTES + model_bytes] * n,
        downlink=[k * model_bytes] * n, model_bytes=model_bytes)
    return harness.Prepared(
        cell=cell, cfg=cfg, proto=proto, traffic=traffic, data=data,
        flcfg=None, exp=types.SimpleNamespace(
            server=types.SimpleNamespace(meter=meter)),
        eval_data=None, first=first, per_call=1)


@pytest.fixture(scope="module")
def readings():
    mp = pytest.MonkeyPatch()
    mp.setattr(harness, "prepare", stand_in_prepare)
    try:
        rows = calibrate.readings(cell(), SEED, control=True, fault=True)
    finally:
        mp.undo()
    return {r["kind"]: r for r in rows}


def test_calibrate_reads_the_token_cell(readings):
    assert set(readings) == {"program", "control_bf16", "fault_half_batch"}
    program = readings["program"]
    assert program["bytes_rounds_off"] == 0
    assert program["winner_not_argmin"] == 0
    for k in TRAJECTORY:
        assert program[k] == 0.0, k
    assert check.passed(check.compare(program, cell()["limits"]))


def test_fp32_loss_falls_over_three_rounds(readings):
    losses = readings["program"]["ref_eval_loss"]
    assert len(losses) == 3
    assert losses[0] > losses[1] > losses[2]


@pytest.mark.parametrize("kind", ["control_bf16", "fault_half_batch"])
def test_control_and_fault_read_apart(readings, kind):
    row = readings[kind]
    assert row["score_gap_r0"] > 1e-3
    assert row["change_norm_gap"] > 0.05
    assert not check.passed(check.compare(row, cell()["limits"]))


def test_forward_flops_of_a_sequence():
    c = cell()
    cfg = spec.config(c["config"])
    t = gen.traffic(c["traffic"])
    assert cfg["seq_len"] == t.seq_len
    fwd = 2.0 * spec.model(c["config"]).forward_macs(cfg)
    logs = [{"eval_loss": 1.0}]
    proto = harness.protocol(c)
    per_client = 3 * t.n_batches * t.batch_size + proto.mh_pop * 2 * \
        t.batch_size * (1 + proto.mh_generations)
    assert flops.rounds_flops(fwd, proto, [t.n_batches] * t.n_clients,
                              t.batch_size, logs, t.n_test) == \
        fwd * (t.n_clients * per_client + t.n_test)


def test_tensor_genome_chunks_from_memory_analysis():
    """A budget that holds one client and one test sequence gives chunks
    and blocks of one, and the same run: the test loss is the whole test
    set's mean whatever the blocks (blocks of 8 are the set in one
    call)."""
    from fedbench.reference import Reference
    c = cell()
    d = tokens()
    args = (spec.model(c["config"]), spec.config(c["config"]),
            harness.protocol(c), d.clients, d.test, d.server_seed)
    whole = Reference(*args)
    small = Reference(*args, chunk_bytes=1.0)
    assert whole.chunk == 3 and whole.eval_block == 8
    assert small.chunk == 1 and small.eval_block == 1
    a, b = whole.run(1, keep_after=[1]), small.run(1, keep_after=[1])
    assert a.logs[0]["best"] == b.logs[0]["best"]
    np.testing.assert_allclose(a.logs[0]["scores"], b.logs[0]["scores"],
                               rtol=1e-5)
    np.testing.assert_allclose(a.logs[0]["eval_loss"],
                               b.logs[0]["eval_loss"], rtol=1e-5)
