"""Compute-backend dispatch layer (repro.models.ops): xla vs
pallas(interpret) vs ref parity per op on real UNet/pruning shapes,
under vmap + scan, through gradients, on the masked sparse-phase
forward, and end-to-end on a FedPhD run through the
sparse -> prune -> plain transition."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SMOKE_UNET
from repro.configs.base import FLConfig, InputShape
from repro.core import pruning as P
from repro.core.hfl import FedPhD
from repro.data import SMOKE_DATA, ClientData, make_dataset, shards_per_client
from repro.experiment import DataSpec, Experiment, ExperimentSpec
from repro.fl.client import Client
from repro.models import model, ops

BACKENDS = ("xla", "pallas", "ref")


def _allclose(got, want, atol=1e-5):
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=atol)


def _mask(key, n, ratio=0.44):
    return (jax.random.uniform(key, (n,)) >= ratio).astype(jnp.float32)


# ---------------------------------------------------------------------------
# per-op parity on real CIFAR-10 U-Net shapes (tile-aligned: the pallas
# leg actually runs the kernels, not the fallback oracles)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ("pallas", "ref"))
def test_masked_matmul_parity(backend, rng):
    ks = jax.random.split(rng, 4)
    x = jax.random.normal(ks[0], (512, 256))         # B*HW x C at 16x16
    # fan-in-scaled weights (conv_init / dense_p scale): outputs O(1),
    # so atol 1e-5 compares accumulate-order noise, not magnitude
    w = jax.random.normal(ks[1], (256, 768)) * (256 ** -0.5)
    cm, rm = _mask(ks[2], 768), _mask(ks[3], 256)
    want = ops.masked_matmul(x, w, cm, rm, backend="xla")
    _allclose(ops.masked_matmul(x, w, cm, rm, backend=backend), want)
    # None masks = plain matmul
    _allclose(ops.matmul(x, w, backend=backend),
              ops.matmul(x, w, backend="xla"))


@pytest.mark.parametrize("backend", ("pallas", "ref"))
@pytest.mark.parametrize("masked", (False, True))
def test_conv_parity_unet_shapes(backend, masked, rng):
    """3x3 res-conv (128->256 @16x16) and the 1x1 qkv conv (256->768):
    the paper model's two conv flavors, at im2col-tile-aligned sizes."""
    ks = jax.random.split(rng, 6)
    for (kh, cin, cout, hw) in ((3, 128, 256, 16), (1, 256, 768, 16)):
        p = {"w": jax.random.normal(ks[0], (kh, kh, cin, cout)) * 0.05,
             "b": jax.random.normal(ks[1], (cout,)) * 0.1}
        x = jax.random.normal(ks[2], (2, hw, hw, cin))
        cm = _mask(ks[3], cout) if masked else None
        rm = _mask(ks[4], cin) if masked else None
        want = ops.conv(p, x, backend="xla", col_mask=cm, row_mask=rm)
        got = ops.conv(p, x, backend=backend, col_mask=cm, row_mask=rm)
        _allclose(got, want)


@pytest.mark.parametrize("backend", ("pallas", "ref"))
def test_conv_masked_equals_prezeroed_weights(backend, rng):
    """The masked conv must equal a plain conv of apply_masks-style
    pre-zeroed weights — the sparse-phase contract."""
    ks = jax.random.split(rng, 4)
    p = {"w": jax.random.normal(ks[0], (3, 3, 128, 256)) * 0.05,
         "b": jax.random.normal(ks[1], (256,)) * 0.1}
    x = jax.random.normal(ks[2], (2, 16, 16, 128))
    cm = _mask(ks[3], 256)
    pz = {"w": p["w"] * cm[None, None, None, :], "b": p["b"] * cm}
    want = ops.conv(pz, x, backend="xla")
    _allclose(ops.conv(p, x, backend=backend, col_mask=cm), want)


@pytest.mark.parametrize("backend", ("pallas", "ref"))
@pytest.mark.parametrize("shape,causal,window", [
    ((2, 256, 1, 256), False, 0),    # U-Net attn block @16x16, C=256
    ((2, 256, 4, 64), True, 0),      # transformer causal heads
    ((2, 256, 4, 64), True, 128),    # sliding window
])
def test_attention_parity(backend, shape, causal, window, rng):
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], shape)
    k = jax.random.normal(ks[1], shape)
    v = jax.random.normal(ks[2], shape)
    want = ops.attention(q, k, v, causal=causal, window=window,
                         backend="xla")
    got = ops.attention(q, k, v, causal=causal, window=window,
                        backend=backend)
    _allclose(got, want)


@pytest.mark.parametrize("backend", ("pallas", "ref"))
def test_attention_parity_gqa(backend, rng):
    """Hkv < Hq: every backend must expand KV groups identically."""
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (2, 256, 4, 64))
    k = jax.random.normal(ks[1], (2, 256, 2, 64))
    v = jax.random.normal(ks[2], (2, 256, 2, 64))
    want = ops.attention(q, k, v, causal=True, backend="xla")
    got = ops.attention(q, k, v, causal=True, backend=backend)
    _allclose(got, want)


@pytest.mark.parametrize("backend", ("pallas", "ref"))
def test_group_sq_norms_parity_on_unet_members(backend, rng):
    """Eq. 17 reductions on the actual U-Net PruneGroup member layouts:
    conv1 out-channels (axis 3), conv2 in-channels (axis 2), and a
    chunked qkv member — routed through the group_l2_norms kernel."""
    params = model.init(rng, SMOKE_UNET)
    groups = P.build_groups(SMOKE_UNET, params)
    for g in groups:
        want = P.group_sq_norms(params, g, backend="xla")
        got = P.group_sq_norms(params, g, backend=backend)
        _allclose(got, want, atol=1e-4)
    # scores end-to-end
    sx = P.l2_scores(params, groups, backend="xla")
    sb = P.l2_scores(params, groups, backend=backend)
    for name in sx:
        _allclose(sb[name], sx[name], atol=1e-4)


# ---------------------------------------------------------------------------
# parity inside the round engine's program structure: vmap (client
# axis, weights batched) x lax.scan (step axis) x grad
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ("pallas", "ref"))
def test_ops_parity_under_vmap_and_scan(backend, rng):
    ks = jax.random.split(rng, 3)
    C, S = 3, 2                                    # clients x scan steps
    ws = jax.random.normal(ks[0], (C, 1, 1, 128, 128)) * 0.05
    bs = jnp.zeros((C, 128))
    xs = jax.random.normal(ks[1], (S, 2, 16, 16, 128))

    def one_client(w, b, bk):
        def body(carry, x):
            y = ops.conv({"w": w, "b": b}, x, backend=bk)
            return carry + jnp.sum(y), y
        return jax.lax.scan(body, 0.0, xs)

    def run(bk):
        return jax.jit(jax.vmap(lambda w, b: one_client(w, b, bk)))(ws, bs)

    tot_x, ys_x = run("xla")
    tot_b, ys_b = run(backend)
    _allclose(ys_b, ys_x)
    _allclose(tot_b, tot_x, atol=1e-2)             # (C,) sums over 2*16*16*128


@pytest.mark.parametrize("backend", ("pallas", "ref"))
def test_grad_parity_through_ops(backend, rng):
    """custom_vjp routes: masked matmul, attention, group reductions."""
    ks = jax.random.split(rng, 5)
    x = jax.random.normal(ks[0], (256, 128))
    w = jax.random.normal(ks[1], (128, 256)) * 0.1
    cm, rm = _mask(ks[2], 256), _mask(ks[3], 128)

    def f(bk):
        return lambda w_: jnp.sum(
            jnp.tanh(ops.masked_matmul(x, w_, cm, rm, backend=bk)))
    _allclose(jax.grad(f(backend))(w), jax.grad(f("xla"))(w))

    q = jax.random.normal(ks[4], (1, 256, 1, 128))

    def a(bk):
        return lambda q_: jnp.sum(
            ops.attention(q_, q_, q_, backend=bk) ** 2)
    _allclose(jax.grad(a(backend))(q), jax.grad(a("xla"))(q), atol=1e-4)

    def gsq(bk):
        return lambda w_: jnp.sum(
            ops.group_sq_norms_2d(w_, 16, backend=bk) ** 2)
    _allclose(jax.grad(gsq(backend))(w), jax.grad(gsq("xla"))(w), atol=1e-4)


# ---------------------------------------------------------------------------
# masked sparse-phase forward
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
def test_masked_forward_matches_prezeroed_reference(backend, rng):
    """apply_unet(..., masks=) == apply_unet(apply_masks(params)) — the
    block-masked sparse phase vs today's pre-zeroed weights, on every
    backend (incl. the loss gradient existing on the pallas route)."""
    cfg = SMOKE_UNET.replace(backend=backend)
    params = model.init(rng, SMOKE_UNET)
    groups = P.build_groups(SMOKE_UNET, params)
    masks = P.make_masks(P.l2_scores(params, groups), groups, 0.44)
    batch = model.make_inputs(rng, SMOKE_UNET, InputShape("t", 0, 4, "train"))
    want = model.loss_fn(P.apply_masks(params, groups, masks), cfg,
                         batch, rng)
    got = model.loss_fn(params, cfg, batch, rng, masks=masks)
    _allclose(got, want)
    g = jax.grad(lambda p: model.loss_fn(p, cfg, batch, rng, masks=masks))(
        params)
    assert all(bool(jnp.all(jnp.isfinite(x))) for x in jax.tree.leaves(g))


# ---------------------------------------------------------------------------
# end-to-end: backend equivalence of a FedPhD run through the
# sparse -> prune -> plain transition, and the selection/threading knobs
# ---------------------------------------------------------------------------

def _clients(n=4, batch_size=16):
    images, labels = make_dataset(SMOKE_DATA, seed=0)
    parts = shards_per_client(labels, num_clients=n, classes_per_client=1,
                              seed=0)
    return [Client(i, ClientData(images[p], labels[p],
                                 batch_size=batch_size, seed=i),
                   SMOKE_DATA.num_classes) for i, p in enumerate(parts)]


FL = FLConfig(num_clients=4, num_edges=2, local_epochs=1, edge_agg_every=1,
              cloud_agg_every=2, rounds=3, sparse_rounds=2, prune_ratio=0.44,
              sh_a=1000.0)


def test_fedphd_run_equivalent_across_backends():
    """xla vs ref over the sparse -> prune -> plain transition: params
    atol 1e-5, comm_gb bitwise, identical selections/prune rounds."""
    runs = {}
    for backend in ("xla", "ref"):
        t = FedPhD(SMOKE_UNET.replace(backend=backend), FL, _clients(),
                   rng_seed=0)
        hist, _ = t.run(3)
        runs[backend] = (t, hist)
    (tx, hx), (tr, hr) = runs["xla"], runs["ref"]
    assert any(h.pruned for h in hx), "prune transition must be covered"
    for a, b in zip(hx, hr):
        assert a.comm_gb == b.comm_gb
        assert a.selected == b.selected
        assert a.pruned == b.pruned
        assert np.isclose(a.loss, b.loss, atol=1e-4)
    assert tx.cfg.replace(backend="") == tr.cfg.replace(backend="")
    for x, y in zip(jax.tree.leaves(tx.params), jax.tree.leaves(tr.params)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=1e-5)


def test_backend_resolution_and_env_knob(monkeypatch):
    assert ops.resolve_backend("pallas") == "pallas"
    monkeypatch.delenv("FEDPHD_BACKEND", raising=False)
    assert ops.resolve_backend(None) == "xla"
    monkeypatch.setenv("FEDPHD_BACKEND", "ref")
    assert ops.resolve_backend(None) == "ref"
    assert ops.resolve_backend("xla") == "xla"      # explicit beats env
    with pytest.raises(ValueError):
        ops.resolve_backend("cuda")
    # trainers bake the resolved backend into their frozen config
    t = FedPhD(SMOKE_UNET, FL, _clients(), rng_seed=0, prune=False)
    assert t.cfg.backend == "ref"


def test_spec_threads_backend_to_trainer():
    spec = ExperimentSpec(
        name="bk", method="fedphd", model="ddpm-unet-smoke",
        fl=FL, backend="ref", engine="sequential",
        data=DataSpec(dataset="smoke", batch_size=16))
    loaded = ExperimentSpec.from_json(spec.to_json())
    assert loaded.backend == "ref"
    exp = Experiment(spec)
    assert exp.trainer.cfg.backend == "ref"


def test_kernel_routes_are_recorded_and_fallbacks_warn(rng):
    """Every kernel routing decision is visible: traced call sites are
    recorded (interpret off the TPU, fallback off the tiles), and a
    fallback from a compiled kernel warns instead of passing silently."""
    import warnings
    from repro.kernels import note_route, record_routes
    x = jax.random.normal(rng, (2, 16, 16, 128))
    p = {"w": jax.random.normal(rng, (3, 3, 128, 128)) * 0.05,
         "b": jnp.zeros((128,))}
    with record_routes() as routes:
        jax.jit(lambda p_, x_: ops.conv(p_, x_, backend="pallas"))(p, x)
        ops.dense({"w": jnp.ones((27, 128)), "b": jnp.zeros((128,))},
                  jnp.ones((32, 27)), backend="pallas")
    assert [(r.kernel, r.path, r.shape) for r in routes] == [
        ("block_masked_matmul", "interpret", (512, 1152, 128)),
        ("block_masked_matmul", "fallback", (32, 27, 128))]
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        note_route("block_masked_matmul", (32, 27, 128), fallback=True,
                   interpret=False)
        note_route("block_masked_matmul", (32, 27, 128), fallback=True,
                   interpret=True)
    assert len(caught) == 1 and "not tile-aligned" in str(caught[0].message)
