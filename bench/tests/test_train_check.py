"""A training run on the CPU decides ``correct`` by the comparison with
the reference: a sound run passes; the control (the program's own bf16
path) and each fault planted in the timed path fail."""
import jax
import pytest

import tinyroot
from lib import harness

ref = harness.load_module(f"{harness.BENCH}/configs/ddpm_unet_ref.py")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    with tinyroot.compile_cache():
        yield tinyroot.make(str(tmp_path_factory.mktemp("tiny")),
                            cells=("train",))


def test_pruned_widths_are_the_programs():
    """The pruned widths the reference builds are those the program's own
    44% prune leaves (the small model, on the CPU; here because it
    shares the program's initialization with the runs below)."""
    from repro.configs import get_config
    from repro.core.pruning import build_groups, compact, l2_scores, \
        make_masks
    from repro.models import model
    tinyroot.register()
    conf = tinyroot.TINY_CONFIG
    pcfg = get_config(conf["program_model"])
    p = model.init(jax.random.PRNGKey(3), pcfg)
    groups = build_groups(pcfg, p)
    masks = make_masks(l2_scores(p, groups), groups, conf["prune_ratio"])
    got, _, _ = compact(p, pcfg, groups, masks)
    mine = jax.eval_shape(lambda k: ref.init_params(
        k, conf, conf["prune_ratio"]), jax.random.PRNGKey(0))
    assert [a.shape for a in jax.tree.leaves(got)] == \
        [a.shape for a in jax.tree.leaves(mine)]


def failed(nums, limits):
    return [n for n, v in nums.items() if not v <= limits[n]]


def test_sound_run_is_correct(root):
    out = tinyroot.run_cell(root, "train", readings=True)
    assert out["correct"], out["checks"]
    assert out["metrics"]["train_img_per_s"]["value"] > 0
    limits = {n: c["limit"] for n, c in out["checks"].items()}
    # the same numbers for the reference with half of each batch left out
    for name, nums in out["readings"].items():
        assert failed(nums, limits), (name, nums)


def test_control_bf16_is_not_correct(root):
    out = tinyroot.run_cell(root, "train", precision="bf16")
    assert not out["correct"], out["checks"]


def _patch_step(monkeypatch, fault):
    import repro.core.hfl as hfl
    real_make = hfl.make_local_step

    def make(*a, **kw):
        real = real_make(*a, **kw)

        def step(params, opt_state, batch, rng, ctx):
            if fault == "half_batch":
                half = batch["images"].shape[0] // 2
                batch = {k: v[:half] for k, v in batch.items()}
            p, o, loss = real(params, opt_state, batch, rng, ctx)
            if fault == "unchanged":
                return params, opt_state, loss
            return p, o, loss
        return step

    monkeypatch.setattr(hfl, "make_local_step", make)


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_planted_fault_is_not_correct(root, monkeypatch, fault):
    _patch_step(monkeypatch, fault)
    out = tinyroot.run_cell(root, "train")
    assert not out["correct"], out["checks"]
