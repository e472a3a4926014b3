"""The unified streaming engine (``repro.engine``): seed-batched training
parity with sequential runs, in-scan evaluation snapshots vs offline
recomputation, donate-through-checkpoint bit-exact resume, checkpoint-io
hardening, the scheduled halo mixer, and the lazy ``repro.core.surf``
attribute.

Multi-device tests (seed-axis-sharded engine, 8-shard scheduled halo)
carry the same skip marker as ``tests/test_sharded_engine.py`` and run in
the ``make test-sharded`` lane.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine as E
from repro.checkpoint import io as ckpt
from repro.configs.surf_paper import SMOKE
from repro.core import surf
from repro.core.unroll import graph_filter
from repro.data import synthetic
from repro.data.pipeline import stack_meta_datasets
from repro.launch.mesh import host_device_count, make_agent_mesh
from repro.topology import families as F
from repro.topology import schedule as SCH
from repro.topology.halo import (make_scheduled_halo_mix, halo_exchange_rows,
                                 halo_plan, scheduled_halo_plan)

NDEV = host_device_count()
multi_device = pytest.mark.skipif(
    NDEV < 8, reason="needs 8 devices: run via `make test-sharded` "
    "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")

CFG = SMOKE
STEPS = 20
BASE_A = F.regular_graph(CFG.n_agents, 3, seed=0)


@pytest.fixture(scope="module")
def mds():
    return synthetic.make_meta_dataset(CFG, 4, seed=0)


@pytest.fixture(scope="module")
def eval_ds():
    return synthetic.make_meta_dataset(CFG, 3, seed=99)


def _assert_trees_close(a, b, atol=1e-5, rtol=1e-5):
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   atol=atol, rtol=rtol)


# ------------------------------------------------ seed-batched training
def test_seed_batched_train_matches_sequential(mds):
    """Satellite acceptance: row i of the seed-batched train stack (state
    AND metrics history) matches the sequential seed=i run — the
    train-side mirror of the multi-seed evaluator guarantee."""
    seeds = [0, 1, 2]
    states, hist, S_stack = surf.train_surf(CFG, mds, steps=STEPS,
                                            seeds=seeds, log_every=8,
                                            engine="scan")
    assert int(S_stack.shape[0]) == len(seeds)
    for i, s in enumerate(seeds):
        st_i, hist_i, S_i = surf.train_surf(CFG, mds, steps=STEPS, seed=s,
                                            log_every=8, engine="scan")
        np.testing.assert_array_equal(np.asarray(S_stack[i]),
                                      np.asarray(S_i))
        _assert_trees_close(E.state_for_seed(states, i), st_i)
        assert [h["step"] for h in hist] == [h["step"] for h in hist_i]
        for hb, hs in zip(hist, hist_i):
            for k in hs:
                if k == "step":
                    continue
                np.testing.assert_allclose(hb[k][i], hs[k], atol=1e-4,
                                           rtol=1e-3)


def test_seed_batched_eval_rows_match_including_async_masks(mds):
    """The trained seed rows feed the eval stacks: evaluating row i's
    model (incl. evaluate_async with its per-seed masks) matches
    evaluating the sequentially-trained seed=i model."""
    seeds = [0, 1]
    states, _, S_stack = surf.train_surf(CFG, mds, steps=STEPS,
                                         seeds=seeds, log_every=0,
                                         engine="scan")
    for i, s in enumerate(seeds):
        st_i, _, S_i = surf.train_surf(CFG, mds, steps=STEPS, seed=s,
                                       log_every=0, engine="scan")
        row = E.state_for_seed(states, i)
        res_b = surf.evaluate_surf(CFG, row, S_stack[i], mds, seeds=[0, 1])
        res_s = surf.evaluate_surf(CFG, st_i, S_i, mds, seeds=[0, 1])
        for k in res_s:
            np.testing.assert_allclose(res_b[k], res_s[k], atol=1e-4,
                                       rtol=1e-3)
        asy_b = surf.evaluate_async(CFG, row, S_stack[i], mds, n_async=2,
                                    seeds=[0, 1])
        asy_s = surf.evaluate_async(CFG, st_i, S_i, mds, n_async=2,
                                    seeds=[0, 1])
        np.testing.assert_allclose(asy_b["loss_per_layer"],
                                   asy_s["loss_per_layer"], atol=1e-4,
                                   rtol=1e-3)


def test_seed_batched_schedule_matches_sequential_scenario(mds):
    """Per-seed perturbation streams: seed-batched scenario training
    equals the sequential scenario run seed by seed."""
    seeds = [0, 1]
    states, _, _ = surf.train_surf(CFG, mds, steps=STEPS, seeds=seeds,
                                   log_every=0, engine="scan",
                                   scenario="link-failure")
    for i, s in enumerate(seeds):
        st_i, _, _ = surf.train_surf(CFG, mds, steps=STEPS, seed=s,
                                     log_every=0, engine="scan",
                                     scenario="link-failure")
        _assert_trees_close(E.state_for_seed(states, i), st_i)


def test_seed_batched_scheduled_snapshot_run_traces_once(mds, eval_ds):
    """ISSUE acceptance: ONE compiled executable trains n_seeds=4 under a
    T=200 time-varying schedule with in-scan snapshots — meta_step traced
    EXACTLY once, snapshot rows are (n_seeds,)-stacked, and a same-shape
    rerun hits the engine cache with zero new traces."""
    seeds = (0, 1, 2, 3)
    E.TRACE_COUNTS["meta_step"] = 0
    states, hist, snaps, S_stack = surf.train_surf(
        CFG, mds, steps=200, seeds=seeds, log_every=50, engine="scan",
        scenario="link-failure", eval_every=50, eval_datasets=eval_ds)
    assert E.TRACE_COUNTS["meta_step"] == 1, \
        f"traced {E.TRACE_COUNTS['meta_step']}x"
    assert np.asarray(states.step).tolist() == [200] * 4
    assert [sn["step"] for sn in snaps] == [49, 99, 149, 199]
    assert snaps[-1]["final_acc"].shape == (len(seeds),)
    assert snaps[-1]["acc_per_layer"].shape == (len(seeds), CFG.n_layers)
    assert np.isfinite(snaps[-1]["final_acc"]).all()
    assert hist[-1]["test_acc"].shape == (len(seeds),)
    # same shapes, different seeds -> cache hit, zero new traces
    surf.train_surf(CFG, mds, steps=200, seeds=(4, 5, 6, 7), log_every=0,
                    engine="scan", scenario="link-failure", eval_every=50,
                    eval_datasets=eval_ds)
    assert E.TRACE_COUNTS["meta_step"] == 1


def test_seed_batched_rejects_bad_inputs(mds):
    with pytest.raises(ValueError, match="non-empty"):
        E.seed_keys([])
    with pytest.raises(ValueError, match="engine"):
        surf.train_surf(CFG, mds, steps=2, seeds=[0, 1], engine="python")
    with pytest.raises(ValueError, match="not both"):
        surf.train_surf(CFG, mds, steps=2, seed=7, seeds=[0, 1])
    with pytest.raises(ValueError, match="SEED-BATCHED"):
        surf.train_surf(CFG, mds, steps=2, seeds=[0, 1],
                        mix_fn=lambda W, h: W)
    with pytest.raises(ValueError, match="seed rows"):
        E.train_scan_seeds(CFG, jnp.zeros((3, 8, 8)), mds, 2, [0, 1])
    # a single (n, n) nominal matrix must be rejected, not vmapped over
    # its rows
    n = CFG.n_agents
    with pytest.raises(ValueError, match="PER SEED"):
        E.make_seed_train_scan(CFG, jnp.zeros((2, 5, n, n)), eval_every=2,
                               eval_stacked=stack_meta_datasets(mds),
                               S_eval_stack=jnp.eye(n))


# ------------------------------------------------- in-scan snapshots
def test_snapshots_match_offline_eval(mds, eval_ds):
    """Every in-scan snapshot equals the offline recomputation
    (``snapshot_reference``) on the θ the engine held after that step."""
    key = jax.random.PRNGKey(7)
    _, S = surf.make_problem(CFG, seed=0)
    state, _, snaps = E.train_scan(CFG, S, mds, 15, key, eval_every=5,
                                   eval_datasets=eval_ds)
    assert [sn["step"] for sn in snaps] == [4, 9, 14]
    stacked = stack_meta_datasets(mds)
    run = E.make_train_scan(CFG, S)
    for sn in snaps:
        t = sn["step"]
        st_t, _, _ = run(E.init_state(key, CFG), stacked, key, t + 1)
        ref = E.snapshot_reference(CFG, st_t.theta, S, eval_ds, key, t)
        for k in ref:
            np.testing.assert_allclose(sn[k], ref[k], atol=1e-5,
                                       rtol=1e-5)


def test_snapshot_run_requires_eval_pool(mds):
    _, S = surf.make_problem(CFG, seed=0)
    with pytest.raises(ValueError, match="eval"):
        E.train_scan(CFG, S, mds, 4, jax.random.PRNGKey(0), eval_every=2)
    sch = SCH.link_failure_schedule(BASE_A, 4, seed=0)
    with pytest.raises(ValueError, match="S_eval"):
        E.make_train_scan(CFG, sch, eval_every=2,
                          eval_stacked=stack_meta_datasets(mds))


def test_train_surf_snapshot_return_contract(mds, eval_ds):
    state, hist, snaps, S = surf.train_surf(CFG, mds, steps=10,
                                            log_every=5, eval_every=5,
                                            eval_datasets=eval_ds)
    assert [sn["step"] for sn in snaps] == [4, 9]
    assert isinstance(snaps[0]["final_acc"], float)
    assert snaps[0]["acc_per_layer"].shape == (CFG.n_layers,)


# --------------------------------------- donate-through-checkpoint resume
def test_resume_is_bit_exact_through_donated_engine(mds, tmp_path):
    """ISSUE acceptance: a mid-schedule checkpoint restore resumes
    BIT-EXACTLY into the donated engine — continuing from the restored
    state equals continuing from the live state, bit for bit, and the
    split run matches the uninterrupted one to fp tolerance."""
    sch = SCH.dropout_schedule(BASE_A, 20, n_drop=1, seed=3)
    key = jax.random.PRNGKey(5)
    stacked = stack_meta_datasets(mds)
    run = E.make_train_scan(CFG, sch)
    ref, _, _ = run(E.init_state(key, CFG), stacked, key, 20)
    st10, _, _ = run(E.init_state(key, CFG), stacked, key, 10)
    E.resume.save_state(tmp_path, st10)
    live, _, _ = run(st10, stacked, key, 10)   # donates st10 (saved above)
    restored = E.resume.restore_state(tmp_path, CFG)
    assert int(restored.step) == 10
    resumed, _, _ = run(restored, stacked, key, 10)
    for a, b in zip(jax.tree_util.tree_leaves(live),
                    jax.tree_util.tree_leaves(resumed)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    _assert_trees_close(ref, resumed, atol=1e-6, rtol=1e-6)


def test_resume_train_scan_offsets_history_and_snapshots(mds, eval_ds,
                                                         tmp_path):
    """High-level resume: restored runs log ABSOLUTE steps and emit the
    SAME snapshots (same snapshot keys, carried-step cadence) as the
    uninterrupted run."""
    _, S = surf.make_problem(CFG, seed=0)
    key = jax.random.PRNGKey(2)
    full_state, _, full_snaps = E.train_scan(CFG, S, mds, 16, key,
                                             eval_every=4,
                                             eval_datasets=eval_ds)
    half, _ = E.train_scan(CFG, S, mds, 8, key)
    E.resume.save_state(tmp_path, half)
    state, hist, snaps = E.resume.resume_train_scan(
        CFG, S, mds, 16, key, str(tmp_path), log_every=4, eval_every=4,
        eval_datasets=eval_ds)
    assert int(state.step) == 16
    assert [h["step"] for h in hist] == [8, 12, 15]
    # cadence is on the ABSOLUTE step: a resume from step 8 with
    # log_every=5 logs 10, 15 (not 8, 13) — same grid as the full run
    _, hist5 = E.resume.resume_train_scan(CFG, S, mds, 16, key,
                                          str(tmp_path), log_every=5)
    assert [h["step"] for h in hist5] == [10, 15]
    assert [sn["step"] for sn in snaps] == [11, 15]
    tail = {sn["step"]: sn for sn in full_snaps}
    for sn in snaps:
        for k in ("final_acc", "acc_per_layer"):
            np.testing.assert_allclose(sn[k], tail[sn["step"]][k],
                                       atol=1e-5, rtol=1e-5)
    _assert_trees_close(state, full_state, atol=1e-6, rtol=1e-6)


def test_resume_errors(tmp_path):
    with pytest.raises(FileNotFoundError, match="no checkpoints"):
        E.resume.restore_state(tmp_path, CFG)
    with pytest.raises(FileNotFoundError):
        E.resume.restore_state(os.path.join(tmp_path, "missing"), CFG)


# ------------------------------------------------ checkpoint-io hardening
def test_latest_step_missing_empty_and_junk(tmp_path):
    assert ckpt.latest_step(os.path.join(tmp_path, "nope")) is None
    assert ckpt.latest_step(tmp_path) is None          # empty dir
    for junk in ("ckpt_abc.json", "ckpt_.json", "other_3.json",
                 "ckpt_5.npz"):
        open(os.path.join(tmp_path, junk), "w").close()
    assert ckpt.latest_step(tmp_path) is None          # nothing parseable
    open(os.path.join(tmp_path, "ckpt_7.json"), "w").close()
    open(os.path.join(tmp_path, "ckpt_12.json"), "w").close()
    assert ckpt.latest_step(tmp_path) == 12


def test_restore_missing_and_mismatched(tmp_path):
    tree = {"a": jnp.arange(3.0), "b": jnp.zeros((2, 2))}
    with pytest.raises(FileNotFoundError, match="no checkpoint"):
        ckpt.restore(os.path.join(tmp_path, "nope"), tree)
    path = os.path.join(tmp_path, "ck")
    ckpt.save(path, tree, step=0)
    os.remove(path + ".npz")
    with pytest.raises(FileNotFoundError, match="payload"):
        ckpt.restore(path, tree)
    ckpt.save(path, tree, step=0)
    with pytest.raises(ValueError, match="leaves"):
        ckpt.restore(path, {"a": jnp.arange(3.0)})


def test_restore_places_with_shardings(tmp_path):
    """Engine handoff: restore(shardings=...) returns committed device
    buffers carrying the requested shardings."""
    from repro.sharding.surf_rules import train_state_shardings
    state = E.init_state(jax.random.PRNGKey(0), CFG)
    path = os.path.join(tmp_path, "st")
    ckpt.save(path, state, step=0)
    mesh = make_agent_mesh(1)
    template = E.resume.state_template(CFG)
    sh = train_state_shardings(template, mesh)
    restored = ckpt.restore(path, template, shardings=sh)
    for leaf, want in zip(jax.tree_util.tree_leaves(restored),
                          jax.tree_util.tree_leaves(sh)):
        assert leaf.sharding.is_equivalent_to(want, leaf.ndim)
    _assert_trees_close(restored, state, atol=0, rtol=0)
    # single sharding broadcast to every leaf works too
    rep = jax.tree_util.tree_leaves(sh)[0]
    restored2 = ckpt.restore(path, template, shardings=rep)
    _assert_trees_close(restored2, state, atol=0, rtol=0)


# ------------------------------------------------- scheduled halo mixer
def test_scheduled_halo_plan_is_union_support():
    """The time-constant plan pays for the UNION band: link failures over
    a ring base keep the base ring's offsets/rows; per-step blocks zero
    out the failed links."""
    n, nshards = 16, 8
    A = F.ring_graph(n, 1)
    sch = SCH.link_failure_schedule(A, 6, p_fail=0.4, seed=2)
    S0_t, plans = scheduled_halo_plan(np.asarray(sch.S), nshards)
    _, base_plans = halo_plan(F.metropolis_weights(A), nshards)
    assert [d for d, _, _ in plans] == [d for d, _, _ in base_plans]
    assert halo_exchange_rows(plans) == halo_exchange_rows(base_plans)
    assert S0_t.shape == (6, nshards, n // nshards, n // nshards)


def test_scheduled_halo_matches_dense_per_step_single_device():
    sch = SCH.link_failure_schedule(BASE_A, 9, p_fail=0.3, seed=1)
    mix = make_scheduled_halo_mix(make_agent_mesh(1), "data", sch)
    assert mix.scheduled and mix.steps == 9
    W = jax.random.normal(jax.random.PRNGKey(0), (CFG.n_agents, 6))
    h = jnp.asarray([0.2, 0.5, 0.3])
    for t in (0, 4, 8, 11):                   # incl. mod-T wraparound
        y = mix.at_step(jnp.asarray(t, jnp.int32))(W, h)
        ref = graph_filter(sch.S[t % 9], W, h)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   atol=1e-5)


def test_scheduled_halo_through_engine_matches_dense_schedule(mds):
    """train_scan(schedule, mix_fn=scheduled_halo) == the dense schedule
    path (same S_t stream through the halo exchange), and the python
    reference driver runs the same combination."""
    sch = SCH.link_failure_schedule(BASE_A, 12, p_fail=0.3, seed=1)
    mix = make_scheduled_halo_mix(make_agent_mesh(1), "data", sch)
    key = jax.random.PRNGKey(3)
    st_d, h_d = E.train_scan(CFG, sch, mds, 12, key, log_every=4)
    st_h, h_h = E.train_scan(CFG, sch, mds, 12, key, log_every=4,
                             mix_fn=mix)
    _assert_trees_close(st_d.theta, st_h.theta)
    for hd, hh in zip(h_d, h_h):
        for k in hd:
            np.testing.assert_allclose(hd[k], hh[k], atol=1e-4, rtol=1e-3)
    st_py, _ = E.train(CFG, sch, mds, 12, key, mix_fn=mix)
    _assert_trees_close(st_d.theta, st_py.theta)


def test_scheduled_halo_validation(mds):
    sch = SCH.dropout_schedule(BASE_A, 6, n_drop=1, seed=0)
    mix = make_scheduled_halo_mix(make_agent_mesh(1), "data", sch)
    _, S = surf.make_problem(CFG, seed=0)
    with pytest.raises(ValueError, match="TopologySchedule"):
        E.make_train_scan(CFG, S, mix_fn=mix)
    other = SCH.dropout_schedule(BASE_A, 5, n_drop=1, seed=0)
    with pytest.raises(ValueError, match="steps"):
        E.make_train_scan(CFG, other, mix_fn=mix)
    # same length, different CONTENT: the engine must refuse, not let
    # the mixer's blocks silently override this schedule's S_t stream
    same_len = SCH.dropout_schedule(BASE_A, 6, n_drop=1, seed=1)
    with pytest.raises(ValueError, match="digest"):
        E.make_train_scan(CFG, same_len, mix_fn=mix)
    # the raw forward has no step counter to bind a scheduled mixer —
    # it must refuse rather than silently fall back to the dense path
    _, forward = E.make_meta_step(CFG, S, mix_fn=mix, jit=False)
    W0 = jnp.zeros((CFG.n_agents, CFG.head_dim))
    with pytest.raises(ValueError, match="step counter"):
        forward(None, W0, None, None)
    # tags: content-hashed, schedule-specific
    mix2 = make_scheduled_halo_mix(make_agent_mesh(1), "data", sch)
    assert mix.tag == mix2.tag
    assert mix.tag != make_scheduled_halo_mix(make_agent_mesh(1), "data",
                                              other).tag


def test_scheduled_halo_resumes_mid_schedule(mds, tmp_path):
    """The scheduled mixer binds blocks by the CARRIED step: a restored
    state resumes the exact mixing stream (split == uninterrupted)."""
    sch = SCH.link_failure_schedule(BASE_A, 14, p_fail=0.3, seed=4)
    mix = make_scheduled_halo_mix(make_agent_mesh(1), "data", sch)
    key = jax.random.PRNGKey(9)
    stacked = stack_meta_datasets(mds)
    run = E.make_train_scan(CFG, sch, mix_fn=mix)
    ref, _, _ = run(E.init_state(key, CFG), stacked, key, 14)
    half, _, _ = run(E.init_state(key, CFG), stacked, key, 7)
    E.resume.save_state(tmp_path, half)
    restored = E.resume.restore_state(tmp_path, CFG)
    resumed, _, _ = run(restored, stacked, key, 7)
    _assert_trees_close(ref, resumed, atol=1e-6, rtol=1e-6)


# ------------------------------------------------- lazy core attribute
def test_core_package_resolves_surf_lazily():
    # the lazy submodule stays reachable as a package ATTRIBUTE (PEP 562)
    import repro.core
    assert repro.core.surf is surf
    with pytest.raises(AttributeError):
        repro.core.nonexistent


# ------------------------------------------ periodic in-scan checkpoints
def test_in_scan_checkpoint_cadence_and_bit_exact_resume(mds, tmp_path):
    """ISSUE satellite: ``checkpoint_every`` writes ckpt_<step> payloads
    from INSIDE the compiled scan (io_callback at the snapshot-style
    cond cadence), the checkpointing run equals the plain run bit for
    bit, and resuming from an in-scan checkpoint is bit-exact."""
    _, S = surf.make_problem(CFG, seed=0)
    key = jax.random.PRNGKey(3)
    d = str(tmp_path)
    st_plain, _ = E.train_scan(CFG, S, mds, 20, key)
    st_ck, _ = E.train_scan(CFG, S, mds, 20, key, checkpoint_every=5,
                            checkpoint_dir=d)
    assert ckpt.latest_step(d) == 20
    steps = sorted(int(f[5:-5]) for f in os.listdir(d)
                   if f.endswith(".json"))
    assert steps == [5, 10, 15, 20]
    for a, b in zip(jax.tree_util.tree_leaves(st_plain),
                    jax.tree_util.tree_leaves(st_ck)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    st_res, hist = E.resume.resume_train_scan(CFG, S, mds, 20, key, d,
                                              step=10, log_every=5)
    assert [h["step"] for h in hist] == [10, 15, 19]
    for a, b in zip(jax.tree_util.tree_leaves(st_ck),
                    jax.tree_util.tree_leaves(st_res)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_resumed_run_rearms_checkpoint_cadence(mds, tmp_path):
    """A resumed run with checkpoint_every keeps saving on the SAME
    absolute ckpt_<step> grid as the interrupted run (carried-step
    cadence), into a directory of its own here to observe only the
    post-resume saves."""
    _, S = surf.make_problem(CFG, seed=0)
    key = jax.random.PRNGKey(3)
    d1, d2 = str(tmp_path / "a"), str(tmp_path / "b")
    E.train_scan(CFG, S, mds, 8, key, checkpoint_every=4,
                 checkpoint_dir=d1)
    E.resume.resume_train_scan(CFG, S, mds, 20, key, d1, step=8,
                               checkpoint_every=4, checkpoint_dir=d2)
    steps = sorted(int(f[5:-5]) for f in os.listdir(d2)
                   if f.endswith(".json"))
    assert steps == [12, 16, 20]


def test_checkpoint_cadence_validation(mds, tmp_path):
    _, S = surf.make_problem(CFG, seed=0)
    with pytest.raises(ValueError, match="checkpoint_dir"):
        E.make_train_scan(CFG, S, checkpoint_every=5)
    with pytest.raises(ValueError, match="checkpoint_dir"):
        E.make_seed_train_scan(CFG, jnp.stack([S, S]), checkpoint_every=5)
    with pytest.raises(ValueError, match="engine='scan'"):
        surf.train_surf(CFG, mds, steps=4, engine="python",
                        checkpoint_every=2, checkpoint_dir=str(tmp_path))


def test_train_surf_checkpoint_passthrough(mds, tmp_path):
    surf.train_surf(CFG, mds, steps=10, log_every=0, checkpoint_every=4,
                    checkpoint_dir=str(tmp_path))
    assert ckpt.latest_step(str(tmp_path)) == 8


def test_seed_batched_checkpoint_and_resume(mds, tmp_path):
    """Satellite acceptance: ``checkpoint_every`` with ``seeds=`` writes
    the STACKED per-seed tree under ``ckpt_<step>/`` at the cadence, and
    ``resume_train_scan_seeds`` from a mid-run stacked checkpoint equals
    the uninterrupted run bit for bit (state leaves AND history)."""
    seeds = [0, 1]
    d = str(tmp_path)
    states, hist, S_stack = surf.train_surf(
        CFG, mds, steps=10, seeds=seeds, log_every=5,
        checkpoint_every=4, checkpoint_dir=d)
    assert E.resume.latest_seed_step(d) == 8
    assert os.path.isdir(os.path.join(d, "ckpt_4"))
    restored = E.resume.restore_seed_states(d, CFG, len(seeds), step=4)
    np.testing.assert_array_equal(np.asarray(restored.step), [4, 4])
    S_stack2 = jnp.stack([surf.make_problem(CFG, s)[1] for s in seeds])
    states_r, hist_r = E.resume.resume_train_scan_seeds(
        CFG, S_stack2, mds, 10, seeds, d, log_every=5, step=4)
    for x, y in zip(jax.tree_util.tree_leaves(states),
                    jax.tree_util.tree_leaves(states_r)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    tail = [h for h in hist if h["step"] > 4]
    assert [h["step"] for h in hist_r] == [h["step"] for h in tail]
    for hb, hr in zip(tail, hist_r):
        for k in hb:
            if k == "step":
                continue
            np.testing.assert_array_equal(np.asarray(hb[k]),
                                          np.asarray(hr[k]))


# -------------------------------------------- multi-device (sharded lane)
@multi_device
def test_seed_axis_sharded_engine_matches_unsharded(mds):
    """8 seeds sharded over 8 devices (seed_scan_shardings): the
    seed-axis-sharded engine reproduces the unsharded seed-batched run."""
    seeds = list(range(8))
    mesh = make_agent_mesh(8)
    st_u, h_u, _ = surf.train_surf(CFG, mds, steps=STEPS, seeds=seeds,
                                   log_every=8, engine="scan")
    st_s, h_s, _ = surf.train_surf(CFG, mds, steps=STEPS, seeds=seeds,
                                   log_every=8, engine="scan", mesh=mesh)
    _assert_trees_close(st_u, st_s, atol=2e-5, rtol=2e-5)
    for hu, hs in zip(h_u, h_s):
        for k in hu:
            if k == "step":
                continue
            np.testing.assert_allclose(hu[k], hs[k], atol=1e-4, rtol=1e-3)


@multi_device
def test_seed_axis_sharded_scheduled_snapshot_run(mds, eval_ds):
    """The full unified composition on 8 shards: seed-axis-sharded ×
    time-varying schedules × in-scan snapshots, vs unsharded."""
    seeds = list(range(8))
    mesh = make_agent_mesh(8)
    st_u, _, sn_u, _ = surf.train_surf(
        CFG, mds, steps=12, seeds=seeds, log_every=0, engine="scan",
        scenario="link-failure", eval_every=4, eval_datasets=eval_ds)
    st_s, _, sn_s, _ = surf.train_surf(
        CFG, mds, steps=12, seeds=seeds, log_every=0, engine="scan",
        scenario="link-failure", eval_every=4, eval_datasets=eval_ds,
        mesh=mesh)
    _assert_trees_close(st_u, st_s, atol=2e-5, rtol=2e-5)
    for su, ss in zip(sn_u, sn_s):
        np.testing.assert_allclose(su["final_acc"], ss["final_acc"],
                                   atol=1e-4, rtol=1e-3)


@multi_device
def test_scheduled_halo_matches_dense_on_8_shards(mds):
    """Acceptance (correctness half): the scheduled halo exchange on 8
    real shards reproduces the dense S_t stream through the engine."""
    A = F.ring_graph(16, 1)
    import dataclasses
    cfg = dataclasses.replace(CFG, n_agents=16)
    sch = SCH.link_failure_schedule(A, 10, p_fail=0.2, seed=5)
    mesh = make_agent_mesh(8)
    mix = make_scheduled_halo_mix(mesh, "data", sch)
    mds16 = synthetic.make_meta_dataset(cfg, 4, seed=0)
    key = jax.random.PRNGKey(6)
    st_d, _ = E.train_scan(cfg, sch, mds16, 10, key)
    st_h, _ = E.train_scan(cfg, sch, mds16, 10, key, mix_fn=mix,
                           mesh=mesh)
    _assert_trees_close(st_d.theta, st_h.theta, atol=2e-5, rtol=2e-5)


@multi_device
def test_scheduled_halo_collective_bytes_drop():
    """Acceptance (efficiency half): a constant-plan banded schedule
    through the halo path moves fewer collective bytes per meta-step
    than its dense S_t @ W equivalent."""
    from repro.launch.surf_dryrun import meta_step_collective_bytes
    import dataclasses
    cfg = dataclasses.replace(CFG, n_agents=16)
    A = F.ring_graph(16, 1)
    sch = SCH.link_failure_schedule(A, 10, p_fail=0.2, seed=5)
    mesh = make_agent_mesh(8)
    mix = make_scheduled_halo_mix(mesh, "data", sch)
    S_t = jnp.asarray(sch.S[0])
    dense, _ = meta_step_collective_bytes(cfg, S_t, mesh)
    halo, by_kind = meta_step_collective_bytes(cfg, S_t, mesh, mix_fn=mix)
    assert halo < dense, f"scheduled halo {halo} !< dense {dense}"
    assert by_kind.get("collective-permute", 0) > 0
