"""The U-Net local step's rematerialization setting
(``ModelConfig.remat_policy``): it changes what the backward pass keeps
and recomputes, never the numbers, and with the default setting the
step traces as it does without it."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SMOKE_UNET, get_config
from repro.configs.base import FLConfig, InputShape
from repro.core import pruning as P
from repro.fl.client import make_local_step, make_loss_fn
from repro.fl.engine import make_train_one
from repro.models import model
from repro.optim import adam_init

POLICY = "dots_with_no_batch_dims_saveable"
FL = FLConfig(num_clients=2, num_edges=1, lambda0=1e-2)


@pytest.fixture(scope="module")
def setup():
    rng = jax.random.PRNGKey(0)
    params = model.init(rng, SMOKE_UNET)
    groups = P.build_groups(SMOKE_UNET, params)
    masks = P.make_masks(P.l2_scores(params, groups), groups, 0.44)
    batch = model.make_inputs(rng, SMOKE_UNET, InputShape("t", 0, 4, "train"))
    return params, groups, masks, batch


def close(got, want, rtol=1e-6):
    """Every leaf of ``got`` within ``rtol`` of ``want``'s, relative to
    the larger of the leaf's norm and the median leaf's (a leaf whose
    gradient is near zero moves by round-off alone)."""
    got = [np.asarray(g, np.float64) for g in jax.tree.leaves(got)]
    want = [np.asarray(w, np.float64) for w in jax.tree.leaves(want)]
    norms = np.array([np.linalg.norm(w) for w in want])
    floor = np.median(norms)
    for g, w, n in zip(got, want, norms):
        assert np.linalg.norm(g - w) <= rtol * max(n, floor), (g, w)


def _kw(case, groups, masks):
    sparse = case != "plain"
    return dict(sparse=sparse, groups=groups if sparse else None,
                prune_masks=masks if case == "masked" else None)


@pytest.mark.parametrize("case", ["plain", "sparse", "masked", "train_one"])
def test_remat_changes_no_number(setup, case):
    """The loss, the gradient (Adam's moments after one step) and the
    updated parameters with the setting on equal those with it off: the
    sequential step, dense and sparse (the group-lasso term), on the
    masked sparse-phase path, and the vectorized engine's ``train_one``
    (one step of its scan; a second would amplify round-off through
    Adam's normalised update)."""
    params, groups, masks, batch = setup
    kw = _kw("masked" if case == "train_one" else case, groups, masks)
    rng = jax.random.PRNGKey(1)
    opt = adam_init(params)
    out = {}
    for policy in ("", POLICY):
        cfg = SMOKE_UNET.replace(remat_policy=policy)
        if case == "train_one":
            run = jax.jit(make_train_one(make_loss_fn(cfg, FL, **kw)),
                          static_argnums=6)
            out[policy] = run(params, opt, jax.tree.map(
                lambda x: x[None], batch), jnp.ones(1, bool), rng, {}, False)
        else:
            out[policy] = make_local_step(cfg, FL, **kw)(params, opt, batch,
                                                         rng, {})
    for got, want in zip(out[POLICY], out[""]):    # params, Adam, loss
        close(got, want)


def _equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


def _remat_equations(cfg, sparse):
    """The ``checkpoint`` equations of ``cfg``'s local step, traced on
    shapes alone (nothing is computed)."""
    params = jax.eval_shape(lambda k: model.init(k, cfg),
                            jax.random.PRNGKey(0))
    groups = P.build_groups(cfg, params) if sparse else None
    step = make_local_step(cfg, FL, sparse=sparse, groups=groups)
    s = cfg.image_size
    batch = {"images": jax.ShapeDtypeStruct((2, s, s, 3), jnp.float32),
             "labels": jax.ShapeDtypeStruct((2,), jnp.int32)}
    jaxpr = jax.make_jaxpr(step)(params, jax.eval_shape(adam_init, params),
                                 batch, jax.random.PRNGKey(0), {})
    return [e for e in _equations(jaxpr.jaxpr)
            if e.primitive.name in ("checkpoint", "remat", "remat2")]


@pytest.mark.parametrize("name,want", [("ddpm-unet-smoke", 0),
                                       ("ddpm-unet-cifar10", 0),
                                       ("ddpm-unet-celeba", 1)])
@pytest.mark.parametrize("sparse", [False, True])
def test_default_step_holds_no_remat(name, want, sparse):
    """With the default setting the smoke and CIFAR-10 steps hold no
    ``checkpoint`` equation; the CelebA model's step holds its one."""
    assert len(_remat_equations(get_config(name), sparse)) == want
