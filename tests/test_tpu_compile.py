"""Compile the Pallas graph-filter kernel of SURF's main path for a
DESCRIBED TPU v5e chip, at the paper's widths, with no chip attached.

Interpret mode cannot catch what Mosaic refuses (unaligned slices, VMEM
over-use, a kernel the partitioner cannot place); the TPU compiler can,
and it runs here against a described topology. Nothing executes, so these
tests say nothing about results or times.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every test
worker imports every test file. Keep these tests in this one file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.surf_paper import PAPER
from repro.kernels.graph_filter import ops
from repro.kernels.graph_filter.kernel import graph_filter_pallas

# The paper's federation: n=100 agents, d = F·C + C = 5,130 per-agent
# parameters, K=2 taps; padded to the (8, 128) tile: 104 x 5,248.
N = PAPER.n_agents
D = PAPER.feature_dim * PAPER.n_classes + PAPER.n_classes
TAPS = PAPER.filter_taps + 1


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache off here
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:     # no TPU library, or it cannot describe
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
        compilation_cache.reset_cache()


def _shape(sharding, *dims):
    return jax.ShapeDtypeStruct(dims, jnp.float32, sharding=sharding)


def _compiled_hlo(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def test_graph_filter_kernel_compiles_at_paper_shape(one_chip):
    n_p, d_p = ops._padded(N, D)
    assert (n_p, d_p) == (104, 5248)
    hlo = _compiled_hlo(
        lambda S, W, h: graph_filter_pallas(S, W, h, block_d=128,
                                            interpret=False),
        _shape(one_chip, n_p, n_p), _shape(one_chip, n_p, d_p),
        _shape(one_chip, TAPS))
    assert "tpu_custom_call" in hlo


def test_graph_filter_grad_compiles_through_custom_vjp(one_chip):
    """The meta-gradient path: dW and dh through the kernel's custom VJP
    (the backward pass is a second kernel call with Sᵀ)."""
    def loss(S, W, h):
        return jnp.sum(ops.graph_filter(S, W, h, interpret=False) ** 2)
    hlo = _compiled_hlo(jax.grad(loss, argnums=(1, 2)),
                        _shape(one_chip, N, N), _shape(one_chip, N, D),
                        _shape(one_chip, TAPS))
    assert "tpu_custom_call" in hlo


def test_halo_pallas_resident_block_compiles(one_chip):
    """``mix="halo-pallas"`` on four chips: each shard's on-shard block is
    the kernel's 1-tap case h=[0, 1] over n/4 = 25 agents, padded to 32
    rows. ``topology.halo`` resolves interpret mode from the backend, which
    is the CPU here, so the test pins the compiled mode itself."""
    rows = N // 4
    one_hop = jnp.array([0.0, 1.0], jnp.float32)

    def resident(S0, Y):
        return ops.graph_filter(S0, Y, one_hop, impl="pallas",
                                interpret=False)
    assert ops._padded(rows, D)[0] == 32
    hlo = _compiled_hlo(
        lambda S0, Y: jax.grad(lambda y: jnp.sum(resident(S0, y) ** 2))(Y),
        _shape(one_chip, rows, rows), _shape(one_chip, rows, D))
    assert "tpu_custom_call" in hlo
