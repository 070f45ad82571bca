"""Vectorized round engine: equivalence with the sequential reference,
stacked-epoch pipeline, and mixed-dtype aggregation regression."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import SMOKE_UNET
from repro.configs.base import FLConfig
from repro.core.aggregation import weighted_average, weighted_sum
from repro.core.hfl import FedPhD
from repro.data import SMOKE_DATA, ClientData, make_dataset, shards_per_client
from repro.fl.client import Client
from repro.fl.engine import uniform_batch_shape
from repro.obs import cache_size


def make_clients(n=4, batch_size=16):
    """Fresh clients each call: ClientData holds a stateful shuffle RNG,
    so both engines must consume it from the same starting state."""
    images, labels = make_dataset(SMOKE_DATA, seed=0)
    parts = shards_per_client(labels, num_clients=n, classes_per_client=1,
                              seed=0)
    return [Client(i, ClientData(images[p], labels[p],
                                 batch_size=batch_size, seed=i),
                   SMOKE_DATA.num_classes) for i, p in enumerate(parts)]


FL = FLConfig(num_clients=4, num_edges=2, local_epochs=1, edge_agg_every=1,
              cloud_agg_every=2, rounds=4, sparse_rounds=2, prune_ratio=0.44,
              sh_a=1000.0)


def test_engine_equivalence_through_prune():
    """2-edge/4-client: identical params (atol 1e-5) and identical
    comm_gb across the sparse -> prune -> plain transition at r = R_s."""
    seq = FedPhD(SMOKE_UNET, FL, make_clients(), rng_seed=0,
                 engine="sequential")
    h_seq, _ = seq.run(4)
    vec = FedPhD(SMOKE_UNET, FL, make_clients(), rng_seed=0,
                 engine="vectorized")
    h_vec, _ = vec.run(4)

    assert any(h.pruned for h in h_seq), "prune transition must be covered"
    for a, b in zip(h_seq, h_vec):
        assert a.comm_gb == b.comm_gb
        assert a.pruned == b.pruned
        assert np.isclose(a.loss, b.loss, atol=1e-4)
    for x, y in zip(jax.tree.leaves(seq.params), jax.tree.leaves(vec.params)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=1e-5)


def test_stacked_epochs_lockstep_with_epoch():
    images, labels = make_dataset(SMOKE_DATA, seed=3)
    a = ClientData(images[:40], labels[:40], batch_size=8, seed=7)
    b = ClientData(images[:40], labels[:40], batch_size=8, seed=7)
    ref = [bt for _ in range(2) for bt in a.epoch()]
    stacked, valid = b.stacked_epochs(2, steps=len(ref) + 3)
    assert valid.sum() == len(ref) and not valid[len(ref):].any()
    for i, bt in enumerate(ref):
        np.testing.assert_array_equal(stacked["images"][i], bt["images"])
        np.testing.assert_array_equal(stacked["labels"][i], bt["labels"])
    # padding repeats the last real batch (masked out by the engine)
    np.testing.assert_array_equal(stacked["images"][-1], ref[-1]["images"])
    with pytest.raises(ValueError):
        b.stacked_epochs(1, steps=1)


def test_uniform_batch_shape_detects_ragged():
    cls = make_clients(4, batch_size=16)
    assert uniform_batch_shape(cls) is not None
    ragged = make_clients(4, batch_size=16)
    ragged[0].data.batch_size = 8
    assert uniform_batch_shape(ragged) is None


def test_engine_vectorized_raises_on_ragged():
    cls = make_clients(4, batch_size=16)
    cls[0].data.batch_size = 8
    trainer = FedPhD(SMOKE_UNET, FL, cls, rng_seed=0, engine="vectorized")
    with pytest.raises(ValueError):
        trainer.run_round(1)


def test_engine_auto_falls_back_on_ragged():
    """Ragged clients silently route to the sequential path, with
    exactly one warning across all rounds (not one per round)."""
    import warnings
    cls = make_clients(4, batch_size=16)
    cls[0].data.batch_size = 8
    trainer = FedPhD(SMOKE_UNET, FL, cls, rng_seed=0, engine="auto")
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        rec1 = trainer.run_round(1)
        rec2 = trainer.run_round(2)
    ragged = [w for w in caught if "sequential" in str(w.message)]
    assert len(ragged) == 1
    assert np.isfinite(rec1.loss) and np.isfinite(rec2.loss)


def test_fedphd_persistent_opt_equivalence():
    """Stacked per-client Adam moments (gather/scatter by participation)
    match the sequential per-client dict threading."""
    seq = FedPhD(SMOKE_UNET, FL, make_clients(), rng_seed=0,
                 engine="sequential", persistent_opt=True, prune=False)
    seq.run(2)
    vec = FedPhD(SMOKE_UNET, FL, make_clients(), rng_seed=0,
                 engine="vectorized", persistent_opt=True, prune=False)
    vec.run(2)
    for a, b in zip(seq.history, vec.history):
        assert a.comm_gb == b.comm_gb
    for x, y in zip(jax.tree.leaves(seq.params), jax.tree.leaves(vec.params)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=1e-5)


MIXED = [{"w": jnp.asarray([1.0, 2.0], jnp.bfloat16),
          "t": jnp.asarray(7, jnp.int32),
          "f": np.asarray([0.5, 0.5], np.float32)},
         {"w": jnp.asarray([3.0, 6.0], jnp.bfloat16),
          "t": jnp.asarray(7, jnp.int32),
          "f": np.asarray([1.5, 2.5], np.float32)},
         {"w": jnp.asarray([-0.75, 5.5], jnp.bfloat16),
          "t": jnp.asarray(12, jnp.int32),
          "f": np.asarray([0.1, -3.0], np.float32)}]


def eager_weighted_average(trees, weights):
    """The per-leaf eager formula ``weighted_average`` replaced: stack
    each leaf's members, one fp32 einsum, cast back (integers rounded)."""
    w = np.asarray(weights, np.float64)
    w = jnp.asarray((w / w.sum()).astype(np.float32))

    def leaf(*xs):
        stacked = jnp.stack(xs)
        acc = jnp.einsum("n,n...->...", w, stacked.astype(jnp.float32))
        if jnp.issubdtype(stacked.dtype, jnp.integer):
            return jnp.round(acc).astype(stacked.dtype)
        return acc.astype(stacked.dtype)
    return jax.tree.map(leaf, *trees)


@pytest.mark.parametrize("n", [1, 2, 3])
def test_weighted_average_mixed_dtypes(n):
    """fp32 accumulation for low-precision leaves; integer leaves (Adam
    t) round-trip instead of truncating to zero; the same numbers as
    the eager per-leaf formula for every member count."""
    members = MIXED[:n]
    for weights in ([1.0] * n, [1.0, 3.0, 2.0][:n]):
        out = weighted_average(members, weights)
        ref = eager_weighted_average(members, weights)
        for key in ("w", "t", "f"):
            assert out[key].dtype == ref[key].dtype == MIXED[0][key].dtype
        assert int(out["t"]) == int(ref["t"])
        for key in ("w", "f"):
            np.testing.assert_allclose(np.asarray(out[key], np.float32),
                                       np.asarray(ref[key], np.float32),
                                       rtol=1e-6, atol=1e-7)
    if n == 2:
        out = weighted_average(members, [1.0, 1.0])
        np.testing.assert_allclose(np.asarray(out["w"], np.float32),
                                   [2.0, 4.0])
        # identical step counters survive averaging exactly
        assert int(out["t"]) == 7
        np.testing.assert_allclose(np.asarray(out["f"]), [1.0, 1.5])
        # skewed integer weights round to nearest, not truncate
        assert int(weighted_average(members, [1.0, 3.0])["t"]) == 7


def test_weighted_average_compiles_once_per_member_count():
    """Other weights, other members of the same layout, or a tuple in
    place of a list reuse the member-count program; a new count adds
    exactly one."""
    trees = [{"a": jnp.full((3,), float(i)),
              "b": jnp.full((2, 7), i, jnp.bfloat16)} for i in range(4)]
    weighted_average(trees[:2], [1.0, 2.0])
    programs = cache_size(weighted_sum)
    weighted_average(tuple(trees[:2]), [5.0, 0.5])
    weighted_average(trees[2:], [1.0, 1.0])
    assert cache_size(weighted_sum) == programs
    weighted_average(trees[1:], [1.0, 1.0, 1.0])
    assert cache_size(weighted_sum) == programs + 1
