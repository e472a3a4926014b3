"""The streaming SURF engine:

  * ``engine.core``      — S-as-argument meta-step / eval bodies,
                           ``TrainState``, compiled-engine cache keys;
  * ``engine.scan``      — the single-seed jitted scan (+ python-loop
                           reference driver ``train``);
  * ``engine.seeds``     — seed-batched training (outer vmap over
                           init/topology seeds, one executable);
  * ``engine.snapshots`` — in-scan evaluation at an ``eval_every``
                           cadence;
  * ``engine.resume``    — donate-through-checkpoint restore.

Cache-key anatomy: ``engine/README.md``.
"""
from repro.engine import resume, seeds, snapshots  # noqa: F401
from repro.engine.core import (  # noqa: F401
    _ENGINE_CACHE, _adaptive_eval_core, _check_static_s, _engine_cache_key,
    _eval_core, _meta_step_core, _mix_tag, adaptive_variant, TRACE_COUNTS,
    TrainState, init_state, make_eval, make_meta_step)
from repro.engine.scan import (  # noqa: F401
    _decimate_history, make_train_scan, train, train_scan)
from repro.engine.seeds import (  # noqa: F401
    init_states, make_seed_train_scan, seed_keys, stack_schedules,
    state_for_seed, train_scan_seeds)
from repro.engine.snapshots import (  # noqa: F401
    decimate_snapshots, make_snapshot_fn, snapshot_key, snapshot_reference)

__all__ = [
    "TRACE_COUNTS", "TrainState", "adaptive_variant", "init_state",
    "make_meta_step",
    "make_eval", "make_train_scan", "train", "train_scan",
    "make_seed_train_scan", "train_scan_seeds", "seed_keys", "init_states",
    "state_for_seed", "stack_schedules", "make_snapshot_fn",
    "snapshot_key", "snapshot_reference", "decimate_snapshots", "resume",
    "seeds", "snapshots",
]
