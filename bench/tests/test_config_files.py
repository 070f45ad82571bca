"""Every configuration file of BENCHMARK.json states its program
model's widths, and the reference builds from it the parameter layout
the program's model holds, with the file's parameter and MAC counts."""
import jax
import numpy as np
import pytest

from lib import harness
from lib.macs import unet_macs

ref = harness.load_module(f"{harness.BENCH}/configs/ddpm_unet_ref.py")
CONFIGS = harness.read_json(f"{harness.ROOT}/BENCHMARK.json")["configs"]
WIDTHS = ("image_size", "in_channels", "base_channels", "channel_mults",
          "num_res_blocks", "attn_resolutions", "diffusion_steps")


@pytest.mark.parametrize("entry", CONFIGS, ids=[c["name"] for c in CONFIGS])
def test_config_file_is_the_programs_model(entry):
    from repro.configs import get_config
    from repro.models import model
    cfg = harness.read_json(f"{harness.ROOT}/{entry['file']}")
    prog_cfg = get_config(cfg["program_model"])
    assert {k: cfg[k] for k in WIDTHS} == {
        k: list(v) if isinstance(v, tuple) else v
        for k, v in ((k, getattr(prog_cfg, k)) for k in WIDTHS)}
    tree = jax.eval_shape(lambda k: ref.init_params(k, cfg),
                          jax.random.PRNGKey(0))
    prog = jax.eval_shape(lambda k: model.init(k, prog_cfg),
                          jax.random.PRNGKey(0))
    assert jax.tree.structure(prog) == jax.tree.structure(tree)
    assert [a.shape for a in jax.tree.leaves(prog)] == \
        [a.shape for a in jax.tree.leaves(tree)]
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))
    assert n == round(cfg["params_m"] * 1e6)
    assert unet_macs(tree, cfg["image_size"]) / 1e9 == pytest.approx(
        cfg["gmac_per_image"], rel=5e-5)


CELLS = harness.read_json(f"{harness.ROOT}/BENCHMARK.json")["workloads"]


@pytest.mark.parametrize("name", [w["name"] for w in CELLS])
def test_every_client_runs_the_checked_steps(name):
    """Each client of a cell's deployment runs at least the three local
    steps a round that the cell's correctness check follows."""
    from repro.experiment.data import make_clients
    cell = harness.find_cell(name)
    spec = harness.load_driver(cell).Cell(
        harness.Context(cell=cell, seed=2**31 + 11))._spec()
    clients, _, _ = make_clients(spec)
    assert len(clients) == spec.fl.num_clients
    assert min(spec.fl.local_epochs * c.data.steps_per_epoch
               for c in clients) >= 3
