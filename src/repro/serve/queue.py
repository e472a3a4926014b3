"""Continuous-batching federation server.

``FederationServer`` turns the bucketed request-vmapped solver into a
request/response loop: ``submit()`` uploads ONE new federation (its
mixing matrix + dataset), featurizes it at its true shape and pads it
into its shape bucket on the device (``_sample_and_pad``), and enqueues
the padded slot without waiting for the chip; ``tick()`` admits up to
``max_batch`` bucket-compatible requests FIFO-first, stacks their device
slots into the bucket's fixed ``(B, n_pad, ...)`` batch with one jitted
``assemble`` program (empty slots repeat the first slot and are masked
out, so the executable never sees a new batch size), solves them in
one jitted call and brings to the host only what the admitted requests'
futures hold: each admitted slot's W, its real columns, as one fully
replicated array from a jitted ``take`` program (one transfer from one
device, whatever the layout; the empty slots' W never leaves the
device), and the small per-slot leaves (losses, accuracies, depth) in
one transfer.  A request's data thus crosses the host link once each
way; only each slot's small ``mask`` and ``t_real`` are host arrays.

The admission rule favors batch fullness without starving rare shapes:
a tick serves the FULLEST bucket in the queue (ties broken by FIFO head
position, so a uniform stream behaves exactly like head-of-queue FIFO),
EXCEPT that any bucket whose head request has been passed over for
``max_wait_ticks`` ticks wins outright (oldest-waiting first) — an
aging override that bounds every request's wait even when one popular
shape could otherwise monopolize admission.  A request submitted with
``deadline_ticks=`` outranks both rules once passing it over would miss
the deadline — latency-sensitive requests cut ahead of fuller buckets.

``mesh=`` shards the request axis of every bucket executable over the
mesh's agent-role axis (``solver.request_shardings``; ``assemble`` lays
the batch out that way, so the solver gets its blocks without a
reshard) — serving is
embarrassingly parallel, so a batch of B requests splits over devices
with zero collectives.  A mesh with a 'theta' axis
(``launch.mesh.serve_mesh`` makes one when θ does not fit a device)
also splits θ's perceptron by columns: the server lays θ out once
(``surf_rules.place_theta``), ``submit`` puts each slot on every device
with W0's columns split (``solver.slot_shardings``), and the solver
all-gathers W once a layer.  ``serve.AsyncDriver`` wraps the server in a
background tick thread (``submit`` returns immediately, ticks fire at a
cadence); queue mutations are guarded by a server lock so driver ticks
and caller submits interleave safely.

``depth="adaptive"`` serves through the batched early-exit solver
(``solver._serve_core_adaptive``): each request additionally carries a
padded convergence-probe split, results gain a realized ``depth``, and
``metrics.summary()`` grows a depth histogram + FLOPs-saved estimates.

Everything expensive is cached: one executable per (bucket, B, mix,
task) in a per-server ``BoundedLRU`` (registered as "serve-buckets" for
``repro.clear_caches()``), beside one pad program per (true shape,
bucket), one ``assemble`` program per bucket shape and one ``take``
program per bucket shape, all warmed ahead of traffic with ``warm()``.
"""
from __future__ import annotations

import dataclasses
import functools
import itertools
import threading
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import SURFConfig
from repro.core import unroll as U
from repro.core.tasks import resolve_task
from repro.serve.buckets import BucketSpec, pad_cohort, pad_probe, slot_mask
from repro.serve.metrics import ServeMetrics
from repro.serve.solver import (make_bucket_solver, request_shardings,
                                resolve_serve_mix, slot_shardings,
                                tick_bytes)
from repro.sharding.surf_rules import (padded_columns, place_theta,
                                       replicated, theta_split)
from repro.utils import spans
from repro.utils.cache import BoundedLRU

_REQUIRED = ("Xtr", "Ytr", "Xte", "Yte")


class ServeFuture:
    """Result handle for one submitted federation."""

    def __init__(self):
        self._result = None
        self._done = False
        self.latency = None              # seconds, set at completion

    def done(self) -> bool:
        return self._done

    def result(self) -> dict:
        if not self._done:
            raise RuntimeError("request not solved yet — call "
                               "FederationServer.tick()/drain() first")
        return self._result

    def _set(self, result, latency):
        self._result = result
        self.latency = latency
        self._done = True


@dataclasses.dataclass
class _Request:
    bucket: object
    arrays: tuple                        # padded (S, W0, Xl, Yl, Xte, Yte)
    #                                      (+ Xp, Yp when depth="adaptive"),
    #                                      on the device until admitted
    mask: np.ndarray
    t_real: np.float32
    n_real: int
    rows_real: int
    future: ServeFuture
    t_submit: float                      # perf_counter at submit's entry
    rid: int                             # per-server request id (spans)
    ticks_waited: int = 0                # ticks passed over (aging input)
    deadline_ticks: int | None = None    # admission deadline (optional)


class FederationServer:
    """Amortized-solver server for one trained model.

    ``cfg``/``theta`` come from meta-training (``train_surf``); the
    model serves ANY cohort size (the perceptron is shared across
    agents — permutation equivariance, paper Remark 5.1 — so its
    parameter shapes never mention n_agents).  ``mix`` is
    None/"dense"/"pallas" (see ``solver.resolve_serve_mix``)."""

    def __init__(self, cfg: SURFConfig, theta, *, activation="relu",
                 mix=None, task=None, buckets: BucketSpec = None,
                 max_batch: int = 8, max_buckets: int = 16,
                 depth: str = "fixed", max_wait_ticks: int = 8,
                 mesh=None):
        if cfg.topology == "star":
            raise ValueError(
                "star-topology serving is unsupported: the server-row "
                "mask (core.unroll.star_filter_mask) bakes cfg.n_agents "
                "and breaks under agent padding — serve decentralized "
                "configs, or evaluate star cohorts via evaluate_surf")
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        if depth not in ("fixed", "adaptive"):
            raise ValueError(f"depth must be 'fixed' or 'adaptive', got "
                             f"{depth!r}")
        if max_wait_ticks < 1:
            raise ValueError(f"max_wait_ticks must be >= 1, got "
                             f"{max_wait_ticks}")
        self.task = resolve_task(cfg, task)
        self.cols = self.task.dim
        slot_out, stack_out = None, None
        if mesh is not None:
            # fail at construction, not at the first tick: the request
            # axis must split evenly over the mesh (ragged TRAFFIC is
            # fine — masked empty slots — but the bucket batch shape
            # is fixed)
            in_sh, _ = request_shardings(mesh, int(max_batch), depth)
            stack_out = (in_sh[0],) + in_sh[2:-2]
            slot_out = slot_shardings(mesh, depth)
            self.cols = padded_columns(self.task.dim, theta_split(mesh))
            theta = place_theta(theta, mesh, self.task.dim)
        self.depth = depth
        self.max_wait_ticks = int(max_wait_ticks)
        self.cfg = cfg
        self.theta = theta
        self.activation = activation
        self.mix_fn = resolve_serve_mix(mix)
        self.buckets = buckets if buckets is not None else BucketSpec()
        self.max_batch = int(max_batch)
        self.mesh = mesh
        self.devices = 1 if mesh is None else int(mesh.size)
        self._cache = BoundedLRU(maxsize=max_buckets, name="serve-buckets")
        # one program per (true shape, bucket) draws and pads a slot, and
        # one per bucket shape stacks B device slots into the solver's
        # (B, ...) arguments, each laid out as the solver takes them
        self._pad = _pad_program(slot_out)
        self._assemble = jax.jit(_stack_slots, **(
            {} if mesh is None else {"out_shardings": stack_out}))
        # and one per bucket shape takes slot i's W, its real columns, as
        # one array replicated on every device, so it comes back in one
        # transfer from one device (``_fetch``)
        self._take = jax.jit(
            functools.partial(_take_slot, dim=self.task.dim),
            **({} if mesh is None else {"out_shardings": replicated(mesh)}))
        self.metrics = ServeMetrics(cache=self._cache)
        self._queue = deque()
        self._ids = itertools.count()
        # guards queue mutations only (submit's append, tick's admission
        # sweep) so an async driver can tick while submits keep landing;
        # the solve itself runs outside the lock
        self._lock = threading.RLock()

    # ------------------------------------------------------------ admit
    def submit(self, S, dataset, *, seed=0, q=0,
               deadline_ticks=None) -> ServeFuture:
        """Enqueue one federation: mixing matrix ``S`` (n, n) + dataset
        dict (``Xtr``/``Ytr``/``Xte``/``Yte`` in the (n, m, F)/(n, m)
        engine layout).  ``seed``/``q`` select the solve's RNG stream —
        ``fold_in(PRNGKey(1000 + seed), q)``, the exact
        ``evaluate_surf(..., seed=seed)`` stream for dataset index
        ``q``, which is what makes serve results parity-testable
        against single-cohort evaluation.  Featurization (W0 + layer
        mini-batches) is dispatched NOW at the true cohort shape;
        padding follows on the device, so it never perturbs the draw.
        ``submit`` validates on the host and returns without waiting
        for the chip: the queued request holds its padded slot as
        device arrays.

        ``deadline_ticks``: optional admission deadline — the request
        should be admitted within that many ticks of entering the
        queue.  A tick PREFERS buckets holding a request that would
        miss its deadline if passed over again (most-urgent first),
        ahead of the aging and fullest-bucket rules
        (``_select_bucket``)."""
        t_submit = time.perf_counter()
        rid = next(self._ids)
        with spans.span("serve.submit", req=rid):
            if deadline_ticks is not None and int(deadline_ticks) < 1:
                raise ValueError(f"deadline_ticks must be >= 1, got "
                                 f"{deadline_ticks}")
            S = np.asarray(S, np.float32)
            if S.ndim != 2 or S.shape[0] != S.shape[1]:
                raise ValueError(f"S must be square (n, n), got {S.shape}")
            n = S.shape[0]
            missing = [k for k in _REQUIRED if k not in dataset]
            if missing:
                raise ValueError(f"dataset missing keys {missing}")
            shapes = {k: np.shape(dataset[k]) for k in _REQUIRED}
            for k, shape in shapes.items():
                if shape[0] != n:
                    raise ValueError(
                        f"dataset[{k!r}] leads with {shape[0]} agents but "
                        f"S is {n}x{n}")
            m, t = shapes["Xtr"][1], shapes["Xte"][1]
            if self.depth == "adaptive" and m < self.cfg.probe_size:
                raise ValueError(
                    f"adaptive serving needs probe_size="
                    f"{self.cfg.probe_size} training rows per agent for "
                    f"the convergence probe, got {m} — probe rows must be "
                    "shape-constant per bucket executable")
            bucket = self.buckets.bucket_for(n, t)
            arrays = self._slot(S, dataset, bucket, seed, q)
            mask, t_real = slot_mask(n, t, bucket)
            fut = ServeFuture()
            req = _Request(
                bucket=bucket, arrays=arrays,
                mask=mask, t_real=t_real, n_real=n, rows_real=t, future=fut,
                t_submit=t_submit, rid=rid,
                deadline_ticks=(None if deadline_ticks is None
                                else int(deadline_ticks)))
            with self._lock:
                self._queue.append(req)
        return fut

    def _slot(self, S, dataset, bucket, seed, q):
        """A request's padded device slot: the dataset uploaded once,
        ``featurize_cohort``'s draws at the true shape (so they are the
        ones ``evaluate_surf`` makes), then padding, with the mini-batch
        draw and the padding in one program (``_sample_and_pad``).  Only
        dispatches: nothing here waits for the chip."""
        cfg_r = dataclasses.replace(self.cfg, n_agents=S.shape[0])
        with spans.span("serve.submit.featurize"):
            batch = {k: jnp.asarray(dataset[k]) for k in _REQUIRED}
            # featurize_cohort's stream, split: W0 is drawn eagerly (under
            # jit its draw can round differently, by a fused multiply-add),
            # the mini-batches inside _sample_and_pad (exact under jit)
            key = jax.random.fold_in(
                jax.random.PRNGKey(1000 + int(seed)), int(q))
            kw, kb = jax.random.split(key)
            W0 = U.sample_w0(kw, cfg_r, task=self.task)
        with spans.span("serve.submit.pad"):
            return self._pad(kb, S, W0, batch, cfg=cfg_r, bucket=bucket,
                             probe=self.depth == "adaptive", cols=self.cols)

    def pending(self) -> int:
        """Requests currently queued (admitted-but-unsolved is never
        observable — a tick completes what it admits)."""
        with self._lock:
            return len(self._queue)

    # ------------------------------------------------------------ solve
    def _solver(self, bucket):
        return make_bucket_solver(self.cfg, bucket, self.max_batch,
                                  activation=self.activation,
                                  mix_fn=self.mix_fn, task=self.task,
                                  cache=self._cache, depth=self.depth,
                                  mesh=self.mesh)

    def _batch(self, bucket, slots, masks=(), t_reals=()):
        """The solver's arguments for the device ``slots`` (one or more)
        of ``bucket``, the first ``len(masks)`` of them admitted: the
        ``assemble`` program stacks them into ``max_batch`` slots, each
        missing one a repeat of the first slot, and every slot past the
        admitted ones gets an all-false mask and ``t_real = t_pad`` —
        masked agents never reach a result (the padded-loss corrections
        stay on their identity branch; adaptive slots start INACTIVE,
        depth 0), and an empty slot's result is never read — so empty
        slots cost no device memory of their own.  ``mask`` (B, n_pad)
        and ``t_real`` (B,) are host arrays."""
        B = self.max_batch
        stacked = self._assemble(list(slots)
                                 + [slots[0]] * (B - len(slots)))
        e_mask, e_t = slot_mask(0, int(bucket.rows), bucket)
        empty = B - len(masks)
        mask = np.stack(list(masks) + [e_mask] * empty)
        t_real = np.array(list(t_reals) + [e_t] * empty, np.float32)
        return stacked, mask, t_real

    def _select_bucket(self):
        """The tick's bucket, by the deadline-then-aging admission
        policy:

          1. if any queued request would MISS its ``deadline_ticks``
             when passed over this tick (slack = deadline − waited ≤ 1),
             the bucket holding the most urgent such request wins
             (smallest slack; FIFO position breaks ties) — a deadline
             beats a fuller bucket;
          2. else, if any bucket's HEAD request has been passed over for
             ``max_wait_ticks`` ticks, the oldest-waiting such bucket
             wins (FIFO position breaks ties) — no shape starves;
          3. otherwise the FULLEST bucket wins (occupancy capped at
             ``max_batch`` — surplus beyond one batch confers no
             advantage), ties broken by FIFO head position, so a
             single-shape stream degenerates to plain FIFO."""
        counts, first_pos, urgent = {}, {}, {}
        for i, r in enumerate(self._queue):
            counts[r.bucket] = counts.get(r.bucket, 0) + 1
            first_pos.setdefault(r.bucket, i)
            if r.deadline_ticks is not None:
                slack = r.deadline_ticks - r.ticks_waited
                if slack <= 1:
                    cur = urgent.get(r.bucket)
                    if cur is None or slack < cur[0]:
                        urgent[r.bucket] = (slack, i)
        if urgent:
            return min(urgent, key=lambda b: urgent[b])
        aged = [b for b, i in first_pos.items()
                if self._queue[i].ticks_waited >= self.max_wait_ticks]
        if aged:
            return max(aged, key=lambda b: (
                self._queue[first_pos[b]].ticks_waited, -first_pos[b]))
        return max(counts, key=lambda b: (
            min(counts[b], self.max_batch), -first_pos[b]))

    def _fetch(self, out, k):
        """The results of the solver's first ``k`` slots on the host, and
        the bytes brought: each slot's W, its real columns, taken on the
        device as one replicated array (``_take_slot``; its transfer
        started at once, so the ``k`` overlap), then every slot's small
        leaves in one ``device_get``.  The empty slots' W stays on the
        device."""
        Ws = [self._take(out["W"], np.int32(i)) for i in range(k)]
        for W in Ws:
            W.copy_to_host_async()
        small = jax.device_get({key: v for key, v in out.items()
                                if key != "W"})
        Ws = [np.asarray(W) for W in Ws]
        nbytes = sum(a.nbytes for a in Ws + list(small.values()))
        return Ws, small, nbytes

    def tick(self) -> int:
        """One continuous-batching step: pick a bucket
        (``_select_bucket``), admit up to ``max_batch`` of its requests
        FIFO-within-bucket, solve, complete their futures.  Passed-over
        requests age by one tick.  Returns the number of requests
        completed (0 on an empty queue).  Bucket selection and admission
        run under the server lock (an async driver may tick while
        submits keep landing); the solve itself does not.  Admitted
        requests hold the batch's first slots, so the tick fetches only
        those slots' W (``_fetch``: one replicated array a request, its
        real columns) and the small leaves, never the empty slots' W;
        the ``serve.tick.unpack`` span counts the bytes as
        ``bytes_out``."""
        with spans.span("serve.tick") as tick_span:
            with spans.span("serve.tick.admit"), self._lock:
                if not self._queue:
                    return 0
                bucket = self._select_bucket()
                admitted, rest = [], deque()
                while self._queue:
                    r = self._queue.popleft()
                    if (r.bucket == bucket
                            and len(admitted) < self.max_batch):
                        admitted.append(r)
                    else:
                        r.ticks_waited += 1
                        rest.append(r)
                self._queue = rest
            tick_span.set(reqs=[r.rid for r in admitted],
                          bucket=tuple(bucket))
            with spans.span("serve.tick.stack"):
                stacked, mask, t_real = self._batch(
                    bucket, *zip(*[(r.arrays, r.mask, r.t_real)
                                   for r in admitted]))
                for r in admitted:      # free each slot once stacked
                    r.arrays = None
            solve = self._solver(bucket)
            theta_b, gather_b = tick_bytes(self.cfg, bucket, self.max_batch,
                                           self.mesh, self.task)
            t0 = time.perf_counter()
            with spans.span("serve.tick.call",
                            bytes_in=mask.nbytes + t_real.nbytes,
                            devices=self.devices, theta_bytes=theta_b,
                            gather_bytes=gather_b):
                out = solve(stacked[0], self.theta, *stacked[1:], mask,
                            t_real)
            del stacked
            with spans.span("serve.tick.wait"):
                jax.block_until_ready(out)
            now = time.perf_counter()
            wall = now - t0
            lats = []
            with spans.span("serve.tick.unpack") as unpack_span:
                Ws, host, nbytes = self._fetch(out, len(admitted))
                for i, (r, W) in enumerate(zip(admitted, Ws)):
                    res = {k: np.array(v[i]) for k, v in host.items()}
                    res["W"] = np.array(W[:r.n_real])
                    lat = now - r.t_submit
                    r.future._set(res, lat)
                    lats.append(lat)
                unpack_span.set(bytes_out=nbytes)
            useful = sum(r.n_real * r.rows_real for r in admitted)
            padded = self.max_batch * int(bucket.n_agents) * int(bucket.rows)
            kw = {}
            if self.depth == "adaptive":
                depths = [int(d) for d in host["depth"][:len(admitted)]]
                kw = {"depths": depths,
                      "layers_run": max(depths, default=0),
                      "n_layers": self.cfg.n_layers}
            self.metrics.record_tick(bucket, len(admitted), self.max_batch,
                                     useful, padded, lats, wall, done_at=now,
                                     **kw)
            return len(admitted)

    def drain(self) -> int:
        """Tick until the queue is empty; returns requests completed."""
        done = 0
        while self._queue:
            done += self.tick()
        return done

    # ------------------------------------------------------------- warm
    def warm(self, cohorts) -> list:
        """Compile ahead of traffic: ``cohorts`` is an iterable of
        (n_agents, test_rows) pairs.  Each pair gets its featurization
        and pad program built by padding an all-zero request of that
        true shape (``cfg.train_per_agent`` training rows); each distinct
        bucket they map to gets its ``assemble`` program, executable and
        ``take`` program, run once on a batch of such a slot under
        all-false masks, through the same path as a tick (identical jit
        signatures to real traffic — exactly ONE solver body trace per
        bucket, which ``tests/test_serve.py`` asserts in
        ``test_trace_count_one_per_warm_bucket_zero_at_request_rate``).
        Returns the warmed buckets."""
        ydt = self.task.label_dtype
        F, m = self.task.feat_dim, int(self.cfg.train_per_agent)
        slots = {}
        for n, t in cohorts:
            zeros = {"Xtr": np.zeros((n, m, F), np.float32),
                     "Ytr": np.zeros((n, m), ydt),
                     "Xte": np.zeros((n, t, F), np.float32),
                     "Yte": np.zeros((n, t), ydt)}
            bucket = self.buckets.bucket_for(n, t)
            slot = self._slot(np.zeros((n, n), np.float32), zeros, bucket,
                              0, 0)
            slots.setdefault(bucket, slot)
        for bucket, slot in slots.items():
            stacked, mask, t_real = self._batch(bucket, [slot])
            out = self._solver(bucket)(stacked[0], self.theta, *stacked[1:],
                                       mask, t_real)
            self._fetch(out, 1)
        return list(slots)

    def cache_stats(self) -> dict:
        """Stats of this server's bucket-executable cache."""
        return self._cache.stats()


def _stack_slots(slots):
    """B padded slots (tuples of device arrays, one per request) → the
    tuple of (B, ...) stacked solver arguments; jitted per server as its
    ``assemble`` program."""
    return tuple(jnp.stack(a) for a in zip(*slots))


def _take_slot(W, i, *, dim):
    """Slot ``i`` (a traced int32, so one program a bucket shape) of the
    solver's stacked W, its first ``dim`` columns: jitted per server as
    its ``take`` program."""
    return jax.lax.dynamic_index_in_dim(W, i, 0, keepdims=False)[:, :dim]


def _sample_and_pad(kb, S, W0, batch, *, cfg, bucket, probe, cols):
    """One request's padded device slot, one program per (true shape,
    bucket): the layer mini-batches drawn from ``kb`` at the true shape
    (``unroll.sample_layer_batches``: integer draws and a one-hot
    contraction at HIGHEST precision, so bit-identical to the eager
    draw), then ``pad_cohort`` (W0's columns zero-padded to ``cols``) and,
    with ``probe``, ``pad_probe`` of the convergence-probe split."""
    Xl, Yl = U.sample_layer_batches(kb, batch["Xtr"], batch["Ytr"], cfg)
    W0 = jnp.pad(W0, ((0, 0), (0, cols - W0.shape[1])))
    slot = pad_cohort(S, W0, Xl, Yl, batch["Xte"], batch["Yte"], bucket)
    if probe:
        slot += pad_probe(*U.probe_batch(batch, cfg), bucket)
    return slot


@functools.lru_cache(maxsize=None)
def _pad_program(out_shardings):
    """``_sample_and_pad`` jitted, its slot laid out as ``out_shardings``
    (None: on the default device); shared by the servers of a layout."""
    return jax.jit(_sample_and_pad,
                   static_argnames=("cfg", "bucket", "probe", "cols"),
                   **({} if out_shardings is None
                      else {"out_shardings": out_shardings}))
