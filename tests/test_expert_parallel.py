"""Expert-parallel MoE (shard_map all-to-all) vs the pjit baseline.

Runs on a forced 8-device CPU mesh in a subprocess so the main test
process keeps its single-device view.
"""
import subprocess
import sys

import pytest

# JAX_PLATFORMS=cpu is load-bearing: on images that bundle libtpu,
# dropping it makes backend discovery poll the GCP metadata server with
# 30-retry backoff — the subprocess hangs for minutes before any test
# code runs.
_ENV = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "HOME": "/root",
        "JAX_PLATFORMS": "cpu"}


def _run(script: str) -> subprocess.CompletedProcess:
    return subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, timeout=600,
                          env=_ENV)

_SCRIPT = r"""
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
import jax, jax.numpy as jnp
from repro.configs.base import MoEConfig
from repro.models.moe import init_moe, apply_moe
from repro.launch.expert_parallel import apply_moe_ep
from repro.launch.mesh import make_mesh

mesh = make_mesh((2, 4), ('data', 'model'))
moe = MoEConfig(num_experts=8, experts_per_token=2, d_expert=32,
                capacity_factor=8.0)
rng = jax.random.PRNGKey(0)
p = init_moe(rng, 64, moe, activation='silu')
x = jax.random.normal(jax.random.fold_in(rng, 1), (2, 32, 64))

base, _ = apply_moe(p, x, moe, activation='silu')
run_ep = lambda p, x: apply_moe_ep(p, x, moe, mesh=mesh, ep_axes=('model',),
                                   token_axes=('data', 'model'),
                                   activation='silu', capacity_mult=8.0)
ep, _ = jax.jit(run_ep)(p, x)
diff = float(jnp.max(jnp.abs(base - ep)))
assert diff < 1e-5, f'EP mismatch: {diff}'

g = jax.grad(lambda p: jnp.sum(run_ep(p, x)[0] ** 2))(p)
gsum = float(sum(jnp.sum(jnp.abs(l)) for l in jax.tree.leaves(g)))
assert gsum > 0, 'no gradient through EP dispatch'

# E_loc == 1 path (one expert per device)
moe1 = MoEConfig(num_experts=8, experts_per_token=2, d_expert=32,
                 capacity_factor=8.0)
ep1, _ = jax.jit(lambda p, x: apply_moe_ep(
    p, x, moe1, mesh=mesh, ep_axes=('data', 'model'),
    token_axes=('data', 'model'), activation='silu',
    capacity_mult=8.0))(p, x)
diff1 = float(jnp.max(jnp.abs(base - ep1)))
assert diff1 < 1e-5, f'E_loc=1 mismatch: {diff1}'
print('EP_OK', diff, diff1)
"""


def test_expert_parallel_matches_baseline():
    res = _run(_SCRIPT)
    assert "EP_OK" in res.stdout, res.stdout + res.stderr


_FED_SCRIPT = r"""
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
import jax, jax.numpy as jnp, numpy as np
from repro.launch.federated import hierarchical_aggregate
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ('pod', 'data'))
params = {'w': jnp.arange(8, dtype=jnp.float32).reshape(8, 1)}
n = jnp.full((8,), 10.0)
mu = jnp.full((8,), 2.0)
# equal weights -> edge tier = per-pod mean; cloud tier = global mean
out_edge = jax.jit(lambda p: hierarchical_aggregate(
    p, n, mu, mesh=mesh, cloud_round=False))(params)
vals = np.unique(np.asarray(out_edge['w']))
assert len(vals) == 2, vals            # two pods, two distinct means
out_cloud = jax.jit(lambda p: hierarchical_aggregate(
    p, n, mu, mesh=mesh, cloud_round=True))(params)
vals_c = np.unique(np.asarray(out_cloud['w']).round(5))
assert len(vals_c) == 1 and abs(vals_c[0] - 3.5) < 1e-5, vals_c
print('FED_OK')
"""


def test_hierarchical_aggregate_tpu_mapping():
    res = _run(_FED_SCRIPT)
    assert "FED_OK" in res.stdout, res.stdout + res.stderr
