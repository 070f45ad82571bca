"""The benchmark's own MAC count, parameter layout and peak table."""
import jax
import numpy as np
import pytest

from lib import harness
from lib.macs import unet_macs
from lib.peaks import peaks

ref = harness.load_module(f"{harness.BENCH}/configs/ddpm_unet_ref.py")


def shapes(image_size, ratio):
    cfg = dict(harness.read_json(
        f"{harness.BENCH}/configs/ddpm-unet-cifar10.json"),
        image_size=image_size)
    tree = jax.eval_shape(lambda k: ref.init_params(k, cfg, ratio),
                          jax.random.PRNGKey(0))
    return cfg, tree


# GMAC per image of one forward pass, and millions of parameters, as
# the program's own analytic count (repro.metrics.flops.unet_macs) and
# its l2_scores -> make_masks -> compact give them: the CIFAR-10 net at
# 32x32, and the same net at 64x64 (CelebA, FedPhD Table IV)
@pytest.mark.parametrize("image_size,ratio,gmac,params_m", [
    (32, 0.0, 6.2215, 35.746307),
    (32, 0.44, 3.9959, 22.188035),
    (64, 0.0, 23.370, 35.746307),
    (64, 0.44, 15.131, 22.188035),
])
def test_macs_and_params_pinned(image_size, ratio, gmac, params_m):
    cfg, tree = shapes(image_size, ratio)
    assert unet_macs(tree, cfg["image_size"]) / 1e9 == pytest.approx(
        gmac, rel=5e-5)
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))
    assert n == round(params_m * 1e6)


def test_layout_is_the_programs():
    from repro.configs import get_config
    from repro.models import model
    cfg, tree = shapes(32, 0.0)
    prog = jax.eval_shape(lambda k: model.init(k, get_config(
        cfg["program_model"])), jax.random.PRNGKey(0))
    assert jax.tree.structure(prog) == jax.tree.structure(tree)
    assert [a.shape for a in jax.tree.leaves(prog)] == \
        [a.shape for a in jax.tree.leaves(tree)]


def test_peak_lookup():
    assert peaks("TPU v5 lite")["flops_bf16"] == 197e12
    with pytest.raises(KeyError):
        peaks("TPU v9 imaginary")
