"""Hypothesis property tests on system invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core.aggregation import fedavg_weights, sh_weights, weighted_average
from repro.core.selection import selection_probabilities
from repro.core.sh_score import (AccumulatedDistribution, label_distribution,
                                 sh_score, uniform_target)
from repro.core.pruning.masks import kept_count
from repro.core.pruning.groups import PruneGroup
from repro.fl.comm import CommModel


dists = st.lists(st.floats(0.001, 1.0), min_size=2, max_size=12).map(
    lambda xs: np.asarray(xs) / np.sum(xs))


@given(dists)
@settings(max_examples=100, deadline=None)
def test_sh_score_bounds(q):
    """mu in [2 - sqrt(2), 2] for any probability vector."""
    mu = sh_score(q)
    assert 2 - np.sqrt(2) - 1e-9 <= mu <= 2 + 1e-9


@given(dists)
@settings(max_examples=50, deadline=None)
def test_sh_uniform_dominates(q):
    assert sh_score(uniform_target(len(q))) >= sh_score(q) - 1e-12


@given(st.lists(st.integers(1, 10_000), min_size=2, max_size=8),
       st.lists(st.floats(0.6, 2.0), min_size=2, max_size=8),
       st.floats(0.0, 1e5), st.floats(0.0, 1e3))
@settings(max_examples=100, deadline=None)
def test_sh_weights_simplex(counts, mus, a, b):
    n = min(len(counts), len(mus))
    w = sh_weights(counts[:n], mus[:n], a=a, b=b)
    assert np.all(w >= -1e-12)
    assert np.isclose(w.sum(), 1.0)


@given(st.integers(0, 9), st.integers(1, 500))
@settings(max_examples=50, deadline=None)
def test_label_distribution_is_distribution(cls, n):
    labels = np.full(n, cls)
    q = label_distribution(labels, 10)
    assert np.isclose(q.sum(), 1.0)
    assert q[cls] == 1.0


@given(st.lists(st.tuples(dists, st.integers(1, 1000)), min_size=1,
                max_size=10))
@settings(max_examples=50, deadline=None)
def test_accumulated_distribution_matches_pooled(updates):
    """Eq. 19 accumulation == pooling all samples directly."""
    k = len(updates[0][0])
    updates = [(q, n) for q, n in updates if len(q) == k]
    acc = AccumulatedDistribution(k)
    total = np.zeros(k)
    n_tot = 0
    for q, n in updates:
        acc.update(q, n)
        total += q * n
        n_tot += n
    np.testing.assert_allclose(acc.q, total / n_tot, rtol=1e-9)


@given(st.integers(2, 6), st.floats(1.0, 1e5))
@settings(max_examples=50, deadline=None)
def test_selection_probabilities_simplex(n_edges, a):
    edges = []
    rng = np.random.default_rng(0)
    for _ in range(n_edges):
        e = AccumulatedDistribution(4)
        e.update(rng.dirichlet(np.ones(4)), int(rng.integers(1, 1000)))
        edges.append(e)
    p = selection_probabilities(edges, rng.dirichlet(np.ones(4)), 100,
                                a=a, b=0.0)
    assert np.isclose(p.sum(), 1.0)
    assert np.all(p >= 0)


@given(st.integers(8, 4096), st.floats(0.0, 0.95))
@settings(max_examples=100, deadline=None)
def test_kept_count_valid(size, ratio):
    g = PruneGroup(name="g", size=size, members=(), unit="channel")
    k = kept_count(g, ratio)
    assert 1 <= k <= size


@given(st.floats(1e3, 1e9), st.integers(1, 100))
@settings(max_examples=50, deadline=None)
def test_comm_cost_monotone(volume, clients):
    cm = CommModel()
    assert cm.flat_fl_round(volume, clients) > 0
    assert cm.hfl_round(volume, clients, 2, cloud_round=False) \
        < cm.hfl_round(volume, clients, 2, cloud_round=True)
    # HFL round without cloud sync is cheaper than flat FL (the paper's
    # core efficiency claim: d_e << d_c)
    assert cm.hfl_round(volume, clients, 2, cloud_round=False) \
        < cm.flat_fl_round(volume, clients)


@given(st.lists(st.floats(0.1, 10.0), min_size=2, max_size=5))
@settings(max_examples=50, deadline=None)
def test_weighted_average_convexity(weights):
    """Aggregated scalar lies in the convex hull of the inputs."""
    vals = np.linspace(-1.0, 1.0, len(weights))
    trees = [{"x": np.full((3,), v, np.float32)} for v in vals]
    out = weighted_average(trees, weights)
    x = np.asarray(out["x"])
    assert np.all(x >= vals.min() - 1e-6) and np.all(x <= vals.max() + 1e-6)


# ---------------------------------------------------------------------------
# Round-engine substrate: ctx stacking, padding masks, persistent Adam.
# ---------------------------------------------------------------------------

_shapes = st.lists(st.tuples(st.integers(1, 3), st.integers(1, 4)),
                   min_size=1, max_size=3)


@given(_shapes, st.integers(1, 5))
@settings(max_examples=25, deadline=None)
def test_ctx_stacking_roundtrips(shapes, n):
    """stack_trees/unstack_tree round-trip arbitrary pytree shapes,
    including nesting — the substrate of the engine's stacked ctx."""
    from repro.fl.engine import stack_trees, unstack_tree
    trees = [{"a": {f"k{j}": np.full(s, 10 * i + j, np.float32)
                    for j, s in enumerate(shapes)},
              "b": np.full((2,), float(i), np.float32)}
             for i in range(n)]
    back = unstack_tree(stack_trees(trees), n)
    assert len(back) == n
    for t, r in zip(trees, back):
        for x, y in zip(jax.tree.leaves(t), jax.tree.leaves(r)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@given(st.integers(1, 6), st.integers(0, 4), st.integers(0, 2 ** 16))
@settings(max_examples=15, deadline=None)
def test_stacked_epochs_padding_never_leaks(n_real, pad, seed):
    """For random ragged client sizes, the masked scan yields the params
    of an unpadded run: padded steps never leak into params, opt state,
    or the loss mean.  The padding rows are NaN, so a leak poisons the
    result instead of hiding behind a near-copy of the last real row.
    The two scans are different compiled programs whose fp32 reductions
    may differ in the last ulp, hence a tolerance and not bitwise."""
    from repro.fl.engine import make_train_one
    from repro.optim import adam_init
    rng = np.random.default_rng(seed)
    xs = rng.normal(size=(n_real + pad, 4)).astype(np.float32)
    xs[n_real:] = np.nan                    # any leak is non-finite
    valid = np.arange(n_real + pad) < n_real
    params = {"w": jnp.asarray(rng.normal(size=(4,)), jnp.float32)}
    loss_fn = lambda p, batch, r, ctx: jnp.mean((batch["x"] - p["w"]) ** 2)
    train_one = make_train_one(loss_fn, lr=0.1)
    opt = adam_init(params)
    key = jax.random.PRNGKey(seed)
    p_pad, o_pad, l_pad = train_one(params, opt, {"x": jnp.asarray(xs)},
                                    jnp.asarray(valid), key, {}, True)
    p_ref, o_ref, l_ref = train_one(params, opt,
                                    {"x": jnp.asarray(xs[:n_real])},
                                    jnp.ones(n_real, bool), key, {}, False)
    # a few fp32 ulps of O(1) values
    tol = dict(rtol=1e-6, atol=1e-6)
    for got, want in ((p_pad["w"], p_ref["w"]), (o_pad.mu["w"], o_ref.mu["w"]),
                      (o_pad.nu["w"], o_ref.nu["w"])):
        assert np.isfinite(np.asarray(got)).all()
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), **tol)
    assert int(o_pad.step) == int(o_ref.step) == n_real
    assert np.isfinite(float(l_pad))
    np.testing.assert_allclose(float(l_pad), float(l_ref), rtol=1e-6)


@given(st.integers(2, 8), st.integers(1, 8), st.integers(0, 2 ** 16))
@settings(max_examples=25, deadline=None)
def test_persistent_adam_gather_scatter(n, k, seed):
    """Gather/scatter by a participation selection is (i) a no-op when
    rows are written back unchanged, (ii) invariant to permuting the
    selection, (iii) leaves non-participating clients untouched."""
    from repro.fl.engine import stacked_adam_init, tree_gather, tree_scatter
    rng = np.random.default_rng(seed)
    k = min(k, n)
    stack = stacked_adam_init({"w": np.zeros((3,), np.float32)}, n)
    fill = lambda leaf: (jnp.arange(np.prod(leaf.shape), dtype=leaf.dtype)
                         .reshape(leaf.shape))
    stack = jax.tree.map(fill, stack)
    idx = rng.choice(n, size=k, replace=False)

    rows = tree_gather(stack, idx)
    noop = tree_scatter(stack, idx, rows)
    for x, y in zip(jax.tree.leaves(stack), jax.tree.leaves(noop)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    new_rows = jax.tree.map(lambda leaf: leaf + 1, rows)
    perm = rng.permutation(k)
    out1 = tree_scatter(stack, idx, new_rows)
    out2 = tree_scatter(stack, idx[perm],
                        jax.tree.map(lambda leaf: leaf[perm], new_rows))
    others = np.setdiff1d(np.arange(n), idx)
    for x, y, base in zip(jax.tree.leaves(out1), jax.tree.leaves(out2),
                          jax.tree.leaves(stack)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        np.testing.assert_array_equal(np.asarray(x)[others],
                                      np.asarray(base)[others])
