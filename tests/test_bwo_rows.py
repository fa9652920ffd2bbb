"""Row-restricted random draws and the BWO generation built on them.

``draw_rows`` must give ``jax.random``'s own numbers for the rows it
draws, and the BWO step that mutates only its parents must give the
plain formulation's population bit for bit; the plain formulation is
kept here verbatim as the oracle.
"""
import contextlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.walker import iter_sites
from repro.metaheuristics import REGISTRY, bwo
from repro.metaheuristics.base import draw_rows, rows_drawable


def sphere(pop):
    return jnp.sum((pop - 1.5) ** 2, axis=-1)


def tied(pop):
    """Fitness with many exact ties (cannibalism's tie-breaking)."""
    return jnp.floor(jnp.sum(pop, axis=-1) * 2.0)


# ---- oracle: the plain formulation, kept verbatim ----
def oracle_init_population(rng, x0, pop, fit_fn, spread=0.02):
    noise = jax.random.normal(rng, (pop, x0.shape[0]), x0.dtype) * spread
    noise = noise * (jnp.abs(x0)[None, :] + 1e-3)
    noise = noise.at[0].set(0.0)
    population = x0[None, :] + noise
    return {"pop": population, "fit": fit_fn(population),
            "t": jnp.zeros((), jnp.int32)}


def oracle_select_best(pop, fit, n):
    idx = jnp.argsort(fit)[:n]
    return pop[idx], fit[idx]


def oracle_bwo_step(rng, state, fit_fn, pm=0.4, pc=0.44, pm_gene=0.1,
                    mut_scale=0.05, procreate_frac=0.6):
    pop, fit = state["pop"], state["fit"]
    P, D = pop.shape
    r_mut, r_sel, r_sel2, r_alpha, r_mask, r_noise = jax.random.split(rng, 6)
    mut_ind = jax.random.bernoulli(r_mut, pm, (P, 1))
    mut_gene = jax.random.bernoulli(r_mask, pm_gene, (P, D))
    noise = jax.random.normal(r_noise, (P, D), pop.dtype) * mut_scale
    noise = noise * (jnp.abs(pop) + 1e-3)
    mutated = pop + noise * (mut_ind & mut_gene)
    n_par = max(2, int(P * procreate_frac))
    order = jnp.argsort(fit)
    ranked = mutated[order]
    p1 = ranked[jax.random.randint(r_sel, (P,), 0, n_par)]
    p2 = ranked[jax.random.randint(r_sel2, (P,), 0, n_par)]
    alpha = jax.random.uniform(r_alpha, (P, D), pop.dtype)
    children = alpha * p1 + (1 - alpha) * p2
    child_fit = fit_fn(children)
    n_surv = max(1, int(P * (1 - pc)))
    surv, surv_fit = oracle_select_best(children, child_fit, n_surv)
    all_pop = jnp.concatenate([pop, surv], 0)
    all_fit = jnp.concatenate([fit, surv_fit], 0)
    new_pop, new_fit = oracle_select_best(all_pop, all_fit, P)
    return {"pop": new_pop, "fit": new_fit, "t": state["t"] + 1}


@contextlib.contextmanager
def partitionable(on: bool):
    was = jax.config.jax_threefry_partitionable
    jax.config.update("jax_threefry_partitionable", on)
    try:
        yield
    finally:
        jax.config.update("jax_threefry_partitionable", was)


def full_draw(key, shape, kind, p=0.3):
    if kind == "bits":
        return jax.random.bits(key, shape, jnp.uint32)
    if kind == "uniform":
        return jax.random.uniform(key, shape)
    if kind == "normal":
        return jax.random.normal(key, shape)
    return jax.random.bernoulli(key, p, shape)


KINDS = ("bits", "uniform", "normal", "bernoulli")
ROWS = ([0, 1, 2, 3, 4, 5], [4, 1, 4], [5], [2, 0, 2, 0, 3])


def assert_same(a, b):
    a, b = np.asarray(a), np.asarray(b)
    assert a.dtype == b.dtype and a.shape == b.shape
    np.testing.assert_array_equal(a.view(np.uint8), b.view(np.uint8))


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("d", [1, 200, 300])
def test_draw_rows_equals_full_draw(kind, d):
    """Arbitrary, repeated and unsorted rows, D off the 128 lane grid."""
    assert rows_drawable((6, d))
    for seed, rows in enumerate(ROWS):
        key = jax.random.PRNGKey(seed + 11)
        rows = jnp.asarray(rows)
        got = jax.jit(lambda k, r: draw_rows(k, r, (6, d), kind, p=0.3))(
            key, rows)
        assert_same(got, full_draw(key, (6, d), kind)[rows])


@pytest.mark.parametrize("kind", KINDS)
def test_draw_rows_equals_full_draw_under_vmap(kind):
    keys = jax.random.split(jax.random.PRNGKey(5), 4)
    rows = jnp.asarray([[3, 0, 3], [1, 2, 5], [5, 4, 0], [2, 2, 2]])
    got = jax.vmap(lambda k, r: draw_rows(k, r, (6, 257), kind, p=0.3))(
        keys, rows)
    want = jax.vmap(lambda k, r: full_draw(k, (6, 257), kind)[r])(
        keys, rows)
    assert_same(got, want)


def test_draw_rows_typed_key():
    key = jax.random.key(9)
    rows = jnp.asarray([2, 0])
    assert_same(draw_rows(key, rows, (3, 130), "normal"),
                jax.random.normal(key, (3, 130))[rows])


@pytest.mark.parametrize("shape,impl,dtype,want", [
    ((6, 656_810), None, jnp.float32, True),
    ((6, 100), "threefry2x32", jnp.float32, True),
    ((2 ** 16, 2 ** 16), None, jnp.float32, False),
    ((6, 100), None, jnp.bfloat16, False),
    ((6, 100), "rbg", jnp.float32, False),
])
def test_rows_drawable_guards(shape, impl, dtype, want):
    key = None if impl is None else jax.random.key(0, impl=impl)
    assert rows_drawable(shape, key, dtype) is want


@pytest.mark.parametrize("kind", KINDS)
def test_draw_rows_fallback_off_partitionable(kind):
    """Without partitionable threefry a row depends on the whole draw's
    shape; the helper then indexes the full draw."""
    with partitionable(False):
        assert not rows_drawable((6, 200))
        key = jax.random.PRNGKey(3)
        rows = jnp.asarray([4, 1, 4])
        assert_same(draw_rows(key, rows, (6, 200), kind, p=0.3),
                    full_draw(key, (6, 200), kind)[rows])


def _run(step, init, x0, P, fit_fn, gens=3, seed=0):
    state = init(jax.random.PRNGKey(seed), x0, P, fit_fn)
    states = [state]
    for g in range(gens):
        state = step(jax.random.PRNGKey(seed * 100 + g), state, fit_fn)
        states.append(state)
    return states


def _assert_states_same(got, want):
    for a, b in zip(got, want):
        assert_same(a["pop"], b["pop"])
        assert_same(a["fit"], b["fit"])
        assert int(a["t"]) == int(b["t"])


@pytest.mark.parametrize("P", [2, 6, 8])
@pytest.mark.parametrize("procreate_frac", [0.6, 1.0])
@pytest.mark.parametrize("pc", [0.05, 0.44, 0.9])
@pytest.mark.parametrize("fit_fn", [sphere, tied], ids=["sphere", "tied"])
def test_bwo_step_equals_plain_formulation(P, procreate_frac, pc, fit_fn):
    mh = bwo(pc=pc, procreate_frac=procreate_frac)
    x0 = jnp.linspace(-2.0, 2.0, 131)
    step = jax.jit(mh.step, static_argnums=2)
    ref = jax.jit(lambda k, s, f: oracle_bwo_step(
        k, s, f, pc=pc, procreate_frac=procreate_frac), static_argnums=2)
    got = _run(step, mh.init, x0, P, fit_fn)
    want = _run(ref, oracle_init_population, x0, P, fit_fn)
    _assert_states_same(got, want)


def test_bwo_step_equals_plain_formulation_under_vmap():
    mh = bwo()
    x0 = jax.random.normal(jax.random.PRNGKey(1), (4, 300))
    keys = jax.random.split(jax.random.PRNGKey(2), 4)

    def run(init, step):
        def one(x, k):
            k0, k1, k2 = jax.random.split(k, 3)
            s = init(k0, x, 6, sphere)
            s = step(k1, s, sphere)
            return step(k2, s, sphere)
        return jax.jit(jax.vmap(one))(x0, keys)

    got = run(mh.init, mh.step)
    want = run(oracle_init_population,
               lambda k, s, f: oracle_bwo_step(k, s, f))
    assert_same(got["pop"], want["pop"])
    assert_same(got["fit"], want["fit"])


def test_bwo_step_fallback_equals_plain_formulation():
    with partitionable(False):
        mh = bwo()
        x0 = jnp.linspace(-1.0, 3.0, 200)
        got = _run(mh.step, mh.init, x0, 6, sphere)
        want = _run(oracle_bwo_step, oracle_init_population, x0, 6, sphere)
        _assert_states_same(got, want)
        assert mh.mutation_rows(6, 200) == (6, 6)


@pytest.mark.parametrize("name", ["pso", "gwo", "sca", "avo"])
def test_init_population_equals_plain_formulation(name):
    x0 = jnp.linspace(-1.0, 1.0, 129)
    got = REGISTRY[name]().init(jax.random.PRNGKey(4), x0, 7, sphere)
    want = oracle_init_population(jax.random.PRNGKey(4), x0, 7, sphere)
    assert_same(got["pop"], want["pop"])
    assert_same(got["fit"], want["fit"])


def _drawn_elements(fn, *args):
    """Elements of every random-bits draw in ``fn``'s jaxpr."""
    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    sizes = []
    for site in iter_sites(jaxpr):
        if site.primitive in ("random_bits", "threefry2x32"):
            sizes.append(math.prod(site.eqn.outvars[0].aval.shape)
                         * site.multiplier)
    return sizes


@pytest.mark.parametrize("P,D", [(6, 1000), (8, 333)])
def test_generation_draws_parent_rows_only(P, D):
    """Mask and noise over n_par x D, alpha over P x D; the other draws
    (gate, parent picks) are O(P)."""
    mh = bwo()
    n_par, full = mh.mutation_rows(P, D)
    assert (n_par, full) == (max(2, int(P * 0.6)), P)
    state = {"pop": jnp.zeros((P, D)), "fit": jnp.arange(P, dtype=jnp.float32),
             "t": jnp.zeros((), jnp.int32)}
    sizes = _drawn_elements(lambda k, s: mh.step(k, s, sphere),
                            jax.random.PRNGKey(0), state)
    assert sorted(s for s in sizes if s >= D) == sorted(
        [n_par * D, n_par * D, P * D])
    assert sum(s for s in sizes if s < D) <= 8 * P
    init = _drawn_elements(lambda k, x: mh.init(k, x, P, sphere),
                           jax.random.PRNGKey(0), jnp.zeros(D))
    assert init == [(P - 1) * D]
