"""Correctness of the §Perf optimizations — every flag-gated fast path must
be numerically equivalent to the baseline it replaces."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import flags
from repro.configs.base import AttnConfig
from repro.models import attention as A


@pytest.fixture(autouse=True)
def reset_flags():
    yield
    flags.set_flags(blockwise_prefill=False, embed_d_sharded=False,
                    serve_weight_stationary=False, ssm_shard_hints=False,
                    microbatch_target=2)


@pytest.mark.parametrize("S,W,qc", [(64, 0, 16), (64, 12, 16),
                                    (96, 24, 32), (100, 7, 32)])
def test_blockwise_sdpa_equals_naive(S, W, qc, key):
    q = jax.random.normal(key, (2, S, 4, 16))
    k = jax.random.normal(jax.random.PRNGKey(1), (2, S, 2, 16))
    v = jax.random.normal(jax.random.PRNGKey(2), (2, S, 2, 16))
    mask = A.causal_window_mask(S, S, 0, W)[None]
    y1 = A.sdpa(q, k, v, mask, 2)
    y2 = A.blockwise_sdpa(q, k, v, 2, causal=True, window=W, q_chunk=qc)
    np.testing.assert_allclose(y1, y2, atol=2e-5)


@pytest.mark.slow
def test_blockwise_flag_preserves_model_output(key):
    """Full model forward with blockwise on/off must agree (Sq >= 2048
    triggers the flag path)."""
    from repro.configs import get_config
    from repro.models import model as M
    cfg = get_config("gemma3-27b").reduced()
    params = M.init_lm(cfg, key)
    tok = jax.random.randint(key, (1, 2048), 0, cfg.vocab)
    l1, _, _ = M.forward(cfg, params, tok)
    flags.set_flags(blockwise_prefill=True, q_chunk=256)
    l2, _, _ = M.forward(cfg, params, tok)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                               atol=2e-4, rtol=1e-4)


def test_ring_mix_equals_dense_metropolis():
    """The ppermute ring filter == dense metropolis circulant (1-device
    mesh wraps locally, same math as the P-shard halo exchange)."""
    from repro.core.ring import dense_equivalent, make_ring_mix
    from repro.core.unroll import graph_filter
    n, d, hops = 16, 12, 2
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    mix = make_ring_mix(mesh, "data", n, hops)
    S = jnp.asarray(dense_equivalent(n, hops), jnp.float32)
    W = jax.random.normal(jax.random.PRNGKey(0), (n, d))
    h = jnp.array([0.25, 0.6, 0.15])
    with jax.set_mesh(mesh):
        y_ring = mix(W, h)
    y_dense = graph_filter(S, W, h)
    np.testing.assert_allclose(np.asarray(y_ring), np.asarray(y_dense),
                               atol=1e-5)


def test_embed_d_sharded_rule():
    from repro.sharding.rules import param_spec

    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}
    m = FakeMesh()
    base = tuple(param_spec("embed/table", (152064, 8192), m))
    flags.set_flags(embed_d_sharded=True)
    opt = tuple(param_spec("embed/table", (152064, 8192), m))
    assert base != opt
    assert opt[1] == "model"     # d on model => local gather per shard


def test_microbatch_flag_changes_accumulation():
    from repro.configs.shapes import TRAIN_4K
    from repro.launch.steps import auto_microbatches

    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}
    m = FakeMesh()
    assert auto_microbatches(TRAIN_4K, m) == 8
    flags.set_flags(microbatch_target=8)
    assert auto_microbatches(TRAIN_4K, m) == 2


@pytest.mark.slow
def test_microbatched_train_step_matches_single(key):
    """Gradient accumulation must reproduce the single-batch step."""
    from repro.configs import get_config
    from repro.launch.steps import make_train_step
    from repro.models import model as M
    cfg = get_config("qwen3-4b").reduced()
    params = M.init_lm(cfg, key)
    tok = jax.random.randint(key, (4, 16), 0, cfg.vocab)
    batch = {"tokens": tok, "labels": tok}
    outs = {}
    for mb in (1, 2, 4):
        step, opt = make_train_step(cfg, lr=1e-3, remat=False,
                                    microbatches=mb)
        p2, _, m = jax.jit(step)(params, opt.init(params), batch)
        outs[mb] = (float(m["loss"]),
                    jax.tree_util.tree_leaves(p2)[0])
    assert outs[1][0] == pytest.approx(outs[2][0], rel=1e-4)
    assert outs[1][0] == pytest.approx(outs[4][0], rel=1e-4)
    np.testing.assert_allclose(outs[1][1], outs[4][1], atol=5e-5)
