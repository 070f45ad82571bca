"""Launch-layer tests: mesh construction, sharding rules, step builders,
roofline HLO parser."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke_variant, sharding_rules
from repro.configs.base import InputShape
from repro.launch.mesh import make_host_mesh
from repro.launch.sharding import _param_spec, _fit
from repro.launch.steps import (build_opt_init, build_serve_step,
                                build_train_step)
from repro.models import model
from repro.roofline.analysis import analyze_hlo, parse_hlo


def test_host_mesh():
    mesh = make_host_mesh()
    assert set(mesh.axis_names) == {"data", "model"}


def test_param_spec_megatron_rules():
    mesh = make_host_mesh()  # 1 device: every _fit -> None (divisibility)
    rules = sharding_rules(get_config("internlm2-20b"))
    spec = _param_spec("cycles/0/attn/wq", (48, 6144, 6144), mesh, rules)
    assert len(spec) == 3


def test_train_step_runs_and_learns(rng):
    cfg = smoke_variant("gemma2-2b")
    step = jax.jit(build_train_step(cfg, lr=1e-3))
    opt_init = build_opt_init(cfg)
    params = model.init(rng, cfg)
    opt = opt_init(params)
    batch = model.make_inputs(rng, cfg, InputShape("t", 64, 2, "train"))
    losses = []
    for i in range(5):
        params, opt, loss = step(params, opt, batch, i)
        losses.append(float(loss))
    assert losses[-1] < losses[0]          # memorizes a fixed batch


def test_serve_step_greedy(rng):
    cfg = smoke_variant("moonshot-v1-16b-a3b")
    serve = jax.jit(build_serve_step(cfg))
    params = model.init(rng, cfg)
    cache = model.init_cache(params, cfg, 2, 32)
    toks = jnp.ones((2, 1), jnp.int32)
    for _ in range(4):
        toks, cache = serve(params, cache, toks)
    assert toks.shape == (2, 1)
    assert int(cache["pos"][0]) == 4


def test_reset_cache_slots(rng):
    """Blending fresh state into one slot's rows restores init state
    there and leaves the other slots' rows untouched."""
    cfg = smoke_variant("gemma2-2b")
    serve = jax.jit(build_serve_step(cfg))
    params = model.init(rng, cfg)
    fresh = model.init_cache(params, cfg, 2, 16)
    cache = fresh
    toks = jnp.ones((2, 1), jnp.int32)
    for _ in range(3):
        toks, cache = serve(params, cache, toks)
    reset0 = model.reset_cache_slots(cache, fresh,
                                     jnp.asarray([True, False]))
    assert int(reset0["pos"][0]) == 0 and int(reset0["pos"][1]) == 3
    # resetting every slot restores init_cache exactly; resetting none
    # is the identity — including non-zero init leaves (ring kv_pos=-1)
    reset_all = model.reset_cache_slots(cache, fresh,
                                        jnp.asarray([True, True]))
    reset_none = model.reset_cache_slots(cache, fresh,
                                         jnp.asarray([False, False]))
    for got, want in ((reset_all, fresh), (reset_none, cache)):
        for leaf_g, leaf_w in zip(jax.tree.leaves(got),
                                  jax.tree.leaves(want)):
            np.testing.assert_array_equal(np.asarray(leaf_g),
                                          np.asarray(leaf_w))


def test_serve_requests_refill_isolated(rng):
    """Regression: a refilled slot must not leak the previous request's
    KV rows or token — request output is a function of its id only."""
    from repro.launch.serve import serve_requests
    cfg = smoke_variant("gemma2-2b")
    params = model.init(rng, cfg)
    kw = dict(requests=4, max_tokens=4, cache_len=16, seed=0)
    refilled = serve_requests(params, cfg, slots=2, **kw)
    isolated = serve_requests(params, cfg, slots=4, **kw)
    for rid in range(4):
        assert refilled["outputs"][rid] == isolated["outputs"][rid], \
            f"request {rid} output depends on slot history"


def test_master_weights_for_bf16(rng):
    cfg = smoke_variant("internlm2-20b").replace(param_dtype="bfloat16")
    params = model.init(rng, cfg)
    opt = build_opt_init(cfg)(params)
    assert opt.master is not None
    m_leaves = jax.tree.leaves(opt.master)
    assert all(l.dtype == jnp.float32 for l in m_leaves)


_HLO = """
HloModule test
%cond (p: (s32[], f32[8,8])) -> pred[] {
  %p = (s32[], f32[8,8]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %c = s32[] constant(7)
  ROOT %lt = pred[] compare(%i, %c), direction=LT
}
%body (p: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %p = (s32[], f32[8,8]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[8,8] get-tuple-element(%p), index=1
  %d = f32[8,8] dot(%x, %x), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %one = s32[] constant(1)
  %ni = s32[] add(%i, %one)
  ROOT %t = (s32[], f32[8,8]) tuple(%ni, %d)
}
ENTRY %main (a: f32[8,8]) -> f32[8,8] {
  %a = f32[8,8] parameter(0)
  %zero = s32[] constant(0)
  %tup = (s32[], f32[8,8]) tuple(%zero, %a)
  %w = (s32[], f32[8,8]) while(%tup), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"7"}}
  ROOT %out = f32[8,8] get-tuple-element(%w), index=1
}
"""


def test_roofline_parser_trip_counts():
    terms = analyze_hlo(_HLO)
    # 7 iterations x 2*8*8*8 flops
    assert terms.flops == pytest.approx(7 * 2 * 8 * 8 * 8)


def test_roofline_parser_computations():
    comps = parse_hlo(_HLO)
    assert {"cond", "body", "main"} <= set(comps)
    assert comps["main"].is_entry


def test_mesh_helpers_build_auto_axes():
    """The sharding code was written for auto axes; jax.make_mesh now
    defaults to explicit ones (the expert-parallel reshape breaks)."""
    from jax.sharding import AxisType
    from repro.launch.mesh import make_mesh, make_spec_mesh
    for mesh in (make_host_mesh(), make_mesh((1,), ("data",)),
                 make_spec_mesh({"data": 1})):
        assert set(mesh.axis_types) == {AxisType.Auto}


def test_peaks_keyed_by_device_kind():
    from repro.roofline import hw
    v5e = hw.peaks("TPU v5 lite")
    assert (v5e.flops_bf16, v5e.hbm_bw) == (197e12, 819e9)
    with pytest.raises(KeyError, match="no peak table"):
        hw.peaks("cpu")


@pytest.mark.parametrize("platforms,expected", [
    ("cpu", False), ("tpu", True), ("cpu,tpu", True), ("cuda", False)])
def test_tpu_expected_reads_platforms_without_a_backend(
        monkeypatch, platforms, expected):
    """The sweep parent decides its worker count from the environment
    alone: asking JAX would initialize a backend and claim the chip."""
    from repro.launch.env import tpu_expected
    monkeypatch.setenv("JAX_PLATFORMS", platforms)
    assert tpu_expected() is expected


def test_compile_cache_location(tmp_path):
    """$JAX_COMPILATION_CACHE_DIR wins; otherwise one fixed, in-checkout
    directory.  Run in children: tests never turn the cache on here."""
    import os
    import subprocess
    import sys
    from repro.launch.env import child_env
    code = ("import jax; from repro.launch.env import enable_compile_cache;"
            "p = enable_compile_cache();"
            "print(p, jax.config.jax_compilation_cache_dir)")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = child_env(pythonpath=os.path.join(root, "src"))
    got = subprocess.run([sys.executable, "-c", code], env=env, text=True,
                         capture_output=True, timeout=120).stdout.split()
    assert got == [os.path.join(root, ".jax_cache")] * 2
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    got = subprocess.run([sys.executable, "-c", code], env=env, text=True,
                         capture_output=True, timeout=120).stdout.split()
    assert got == [str(tmp_path)] * 2
