"""Compile the U-Net path's Pallas kernels for a described TPU v5e.

Nothing runs: the TPU compiler, installed with JAX, compiles for a
``v5e:2x2`` topology that is described, not attached, and refuses what
the chip would refuse (illegal block shapes, unlowerable primitives,
too much VMEM).  Shapes are the paper-width CIFAR-10 U-Net's, alone and
vmapped over two clients as the round engine runs them.  One whole
step is compiled too: the CelebA U-Net's batch-32 sparse-phase step as
the FedPhD trainer builds it, whose temporaries have to leave room on
the chip for what a round keeps resident.

The topology is described inside a module fixture, never at import, so
every pytest-xdist worker collects the same tests and only the worker
that runs this file loads the TPU library.  The persistent compile
cache is off here: a described-chip compile can be written to it but
not read back.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.block_masked_matmul.block_masked_matmul import (
    block_masked_matmul)
from repro.kernels.flash_attention.flash_attention import flash_attention_bhsd
from repro.kernels.group_l2_norms.group_l2_norms import group_l2_norms


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")     # no compiler logs in /tmp
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure: no compiler
            jax.config.update("jax_enable_compilation_cache", was)
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _compile(fn, shapes, sharding, clients):
    """Compiled text of ``fn`` over ``shapes`` (vmapped over a leading
    client axis when ``clients``)."""
    if clients:
        fn = jax.vmap(fn)
        shapes = [(clients,) + s for s in shapes]
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _keep(n, ratio, seed):
    """A host 0/1 channel mask pruning ``ratio`` of ``n`` channels."""
    return (np.random.default_rng(seed).random(n) >= ratio).astype(
        np.float32)


@pytest.mark.parametrize("clients", [None, 2])
def test_block_masked_matmul_compiles_at_conv_shape(one_chip, clients):
    # the 3x3 res conv 128 -> 256 at 32x32, batch 32, as im2col GEMM
    M, K, N = 32768, 1152, 256
    cm, rm = _keep(N, 0.44, 0), _keep(K, 0.44, 1)
    text = _compile(lambda x, w: block_masked_matmul(x, w, cm, rm,
                                                     interpret=False),
                    [(M, K), (K, N)], one_chip, clients)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("clients", [None, 2])
def test_group_l2_norms_compiles_at_conv_member(one_chip, clients):
    # Eq. 17 over a conv member: one column (chunk 1) per output channel
    text = _compile(lambda w: group_l2_norms(w, 256, interpret=False),
                    [(1152, 256)], one_chip, clients)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("clients", [None, 2])
def test_flash_attention_compiles_at_unet_attention(one_chip, clients):
    # the 16x16 attention block: batch 32 x 1 head, S = 256, hd = C = 256
    shape = (32, 256, 256)
    text = _compile(lambda q, k, v: flash_attention_bhsd(
        q, k, v, causal=False, interpret=False),
        [shape, shape, shape], one_chip, clients)
    assert "tpu_custom_call" in text


def test_celeba_step_fits_under_remat(one_chip):
    """The 64x64 U-Net's sparse-phase step (batch 32, float32, Adam and
    the group-lasso term), as ``FedPhD`` builds it, holds at most 7 GiB
    of temporaries on one v5e under its ``remat_policy`` (18.9 GiB
    without, more than the chip's 16)."""
    from repro.configs import CELEBA_UNET
    from repro.configs.base import FLConfig
    from repro.core.hfl import FedPhD
    from repro.data import ClientData
    from repro.fl.client import Client

    imgs = np.zeros((4, 64, 64, 3), np.float32)
    clients = [Client(i, ClientData(imgs, np.arange(4) % 2, batch_size=4,
                                    seed=i), 2) for i in range(2)]
    tr = FedPhD(CELEBA_UNET.replace(backend="xla"),
                FLConfig(num_clients=2, num_edges=1), clients,
                engine="sequential")
    shape = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=one_chip)
    batch = {"images": jax.ShapeDtypeStruct((32, 64, 64, 3), jnp.float32,
                                            sharding=one_chip),
             "labels": jax.ShapeDtypeStruct((32,), jnp.int32,
                                            sharding=one_chip)}
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    mem = tr.step_sparse.lower(
        jax.tree.map(shape, tr.params), jax.tree.map(shape, tr._opt_zero),
        batch, rng, {}).compile().memory_analysis()
    assert mem.temp_size_in_bytes <= 7 * 2**30, mem.temp_size_in_bytes
