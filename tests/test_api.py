"""FLConfig -> build_experiment -> run facade and the shared knob
validation (repro.core.knobs)."""
import dataclasses

import jax
import pytest

from repro.core import (ClientHP, FLConfig, build_experiment,
                        normalized_cost)
from repro.core.knobs import (parse_vectorize,
                              validate_rounds_per_dispatch,
                              validate_vectorize)
from repro.data.loader import batch_dataset
from repro.data.partition import partition_iid

from conftest import make_toy_data, make_toy_task


# ------------------------------------------------------------- knobs --
def test_parse_vectorize():
    assert parse_vectorize("scan") == ("scan", 1)
    assert parse_vectorize("scan:4") == ("scan", 4)
    assert parse_vectorize("auto:2") == ("auto", 2)
    assert parse_vectorize("vmap") == ("vmap", 1)
    for bad in ("bogus", "scan:0", "scan:-1", "scan:x", "vmap:2",
                "unroll", "unroll:3"):
        with pytest.raises(ValueError):
            parse_vectorize(bad)


def test_validators_round_trip():
    assert validate_rounds_per_dispatch("auto") == "auto"
    assert validate_vectorize("scan:8") == "scan:8"
    with pytest.raises(ValueError):
        validate_rounds_per_dispatch("turbo")
    with pytest.raises(ValueError):
        validate_vectorize("scan:")


# ---------------------------------------------------------- FLConfig --
@pytest.mark.parametrize("bad", [
    {"rounds_per_dispatch": 0},
    {"vectorize": "bogus"},
    {"vectorize": "vmap:2"},
    {"task": "resnet"},
    {"partition": "pathological"},
    {"strategy": "fedxyz"},
    {"client_ratio": 0.0},
    {"client_ratio": 1.5},
])
def test_flconfig_validates_at_construction(bad):
    with pytest.raises(ValueError):
        FLConfig(**bad)


def test_flconfig_derives_hp_and_stop():
    cfg = FLConfig(local_epochs=3, lr=0.01, mh_pop=5, mh_generations=4,
                   vectorize="scan:2", max_rounds=11, patience=2, tau=0.9)
    hp = cfg.client_hp()
    assert (hp.local_epochs, hp.lr, hp.mh_pop, hp.mh_generations) == \
        (3, 0.01, 5, 4)
    assert hp.vectorize == "scan:2"
    stop = cfg.stop_conditions()
    assert (stop.max_rounds, stop.patience, stop.tau) == (11, 2, 0.9)


def test_build_experiment_smoke_mlp():
    """End-to-end through the facade on the dense task: extended
    CommMeter summary, meter-based normalized cost."""
    cfg = FLConfig(strategy="fedbwo", task="mlp", n_clients=3,
                   n_train=120, n_test=40, batch_size=10,
                   local_epochs=1, mh_pop=2, mh_generations=1,
                   max_rounds=1, tau=0.99)
    exp = build_experiment(cfg)
    result = exp.run()
    s = result.summary()
    assert s["strategy"] == "fedbwo" and s["rounds"] == 1
    comm = s["comm"]
    assert comm["uplink_bytes"] == 3 * 4 + comm["model_bytes"]
    assert comm["downlink_bytes"] == 3 * comm["model_bytes"]
    assert comm["rounds_detail"] == [
        {"round": 0, "uplink_bytes": comm["uplink_bytes"],
         "downlink_bytes": comm["downlink_bytes"]}]
    # meter-form normalized_cost == explicit Eq. 3 form
    assert s["normalized_cost_vs_fedavg30"] == pytest.approx(
        normalized_cost(1, 3, comm["model_bytes"], 30))


def test_summary_bwo_rows_half_drawn_at_pop_6():
    """BWO's defaults at pop 6 mutate its 3 parents: half the rows of a
    full mutation draw; the initial draw takes 5 of 6 rows."""
    cfg = FLConfig(strategy="fedbwo", task="mlp", n_clients=2,
                   n_train=80, n_test=20, batch_size=10, local_epochs=1,
                   mh_pop=6, mh_generations=2, max_rounds=1, tau=0.99)
    rows = build_experiment(cfg).run().summary()["bwo_rows"]
    assert rows == {"rounds": 1, "drawn": 2 * 2 * 3, "full": 2 * 2 * 6,
                    "drawn_frac": 0.5, "init_drawn": 2 * 5,
                    "init_full": 2 * 6}


def test_build_experiment_overrides():
    """task/client_data/eval_data/hp overrides bypass dataset synthesis
    (benchmarks share one dataset across a strategy sweep)."""
    task = make_toy_task()
    data = make_toy_data(jax.random.PRNGKey(0), 200)
    clients = [batch_dataset(d, 8) for d in
               partition_iid(jax.random.PRNGKey(1), data, 2)]
    eval_data = make_toy_data(jax.random.PRNGKey(2), 40)
    hp = ClientHP(local_epochs=1, mh_pop=2, mh_generations=1, lr=0.05)
    cfg = FLConfig(strategy="fedbwo", n_clients=2, max_rounds=1, tau=0.99)
    exp = build_experiment(cfg, task=task, client_data=clients,
                           eval_data=eval_data, hp=hp)
    assert exp.server.n_clients == 2
    assert exp.server.hp is hp
    result = exp.run()
    assert len(result.logs) == 1


def test_flconfig_is_frozen():
    cfg = FLConfig()
    with pytest.raises(dataclasses.FrozenInstanceError):
        cfg.strategy = "fedavg"


# ------------------------------------------------------- compile cache --
@pytest.mark.parametrize("env_dir", [None, "/cache/from/env"])
def test_compile_cache_dir(monkeypatch, env_dir):
    """The entry points' cache goes where JAX_COMPILATION_CACHE_DIR says,
    else to the fixed ``.jax_cache`` at the root of the checkout."""
    import pathlib
    from repro.launch.compile_cache import enable_compile_cache
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs")
    saved = {n: getattr(jax.config, n) for n in names}
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    try:
        path = enable_compile_cache()
        if env_dir is None:
            repo = pathlib.Path(__file__).resolve().parents[1]
            assert path == str(repo / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == path
        else:
            assert path == env_dir
            assert (jax.config.jax_compilation_cache_dir
                    == saved["jax_compilation_cache_dir"])
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    finally:
        for n, v in saved.items():
            jax.config.update(n, v)
