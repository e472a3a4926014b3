"""Shape bucketing: map ragged cohorts onto a small set of padded
shapes so the whole request stream runs through a handful of compiled
executables.

A request is bucketed by ``(n_agents_bucket, rows_bucket)`` — the
smallest configured sizes that fit its true agent count and
test-rows-per-agent — and the full executable identity additionally
carries ``task.cache_tag`` and the mix tag (see
``solver.serve_cache_key``).  Padding is constructed so it is PROVABLY
inert:

  * agents — S gets zero rows/cols for padded agents (they contribute
    nothing to any real agent's graph-filter sum) and every W/X/Y agent
    row past ``n_real`` is zero; the solver re-zeroes W rows per layer;
  * test rows — padded rows are COPIES OF ROW 0 (shape-stable,
    in-distribution), and the task's ``padded_local_loss`` /
    ``padded_local_metric`` subtract their contribution exactly.

``pad_cohort`` runs AFTER ``core.unroll.featurize_cohort`` — W0 and the
layer batches were drawn at the true cohort shape, so padding never
perturbs the RNG stream.  It pads on the device: the server runs it
inside one jitted program per (true shape, bucket), so a request's
padded slot never leaves the device between ``submit`` and the solve.
Only the slot's ``mask`` and ``t_real`` (``slot_mask``) stay on the
host.
"""
from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp
import numpy as np


class Bucket(NamedTuple):
    """One padded serving shape: ``n_agents`` cohort slots x ``rows``
    test rows per agent."""
    n_agents: int
    rows: int


class BucketSpec(NamedTuple):
    """The configured bucket grid (ascending size ladders)."""
    agent_sizes: tuple = (8, 16, 32, 64, 128)
    row_sizes: tuple = (4, 8, 16, 32, 64)

    def bucket_for(self, n_agents: int, rows: int) -> Bucket:
        """Smallest bucket fitting (n_agents, rows); actionable error
        when the request exceeds the grid."""
        na = next((a for a in sorted(self.agent_sizes) if a >= n_agents),
                  None)
        nr = next((r for r in sorted(self.row_sizes) if r >= rows), None)
        if na is None or nr is None:
            raise ValueError(
                f"cohort (n_agents={n_agents}, rows={rows}) exceeds the "
                f"bucket grid (agent_sizes={tuple(self.agent_sizes)}, "
                f"row_sizes={tuple(self.row_sizes)}) — extend BucketSpec "
                "or split the cohort")
        return Bucket(na, nr)

    def buckets_for(self, cohorts):
        """Distinct buckets covering an iterable of (n_agents, rows)
        pairs, in first-seen order (warm-up helper)."""
        seen, out = set(), []
        for n, t in cohorts:
            b = self.bucket_for(n, t)
            if b not in seen:
                seen.add(b)
                out.append(b)
        return out


def _zero_pad(a, size, axis=0):
    """``a`` zero-padded to ``size`` along ``axis``."""
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, size - a.shape[axis])
    return jnp.pad(a, widths)


def pad_cohort(S, W0, Xl, Yl, Xte, Yte, bucket: Bucket):
    """Pad one featurized cohort to ``bucket`` shape.  Returns ``(S, W0,
    Xl, Yl, Xte, Yte)`` as jax arrays — agent axis padded with zeros (and
    zero S rows/cols), test-row axis padded with row-0 copies.  Runs
    eagerly or under ``jit``; ``slot_mask`` gives the host side."""
    n, t = S.shape[0], Xte.shape[1]
    npad, tpad = int(bucket.n_agents), int(bucket.rows)
    if n > npad or t > tpad:
        raise ValueError(f"cohort (n={n}, t={t}) does not fit bucket "
                         f"{bucket}")

    def rows(a):                              # row-0 copies (module doc)
        return jnp.concatenate(
            [a, jnp.repeat(a[:, :1], tpad - t, axis=1)], axis=1)

    return (_zero_pad(_zero_pad(S, npad), npad, 1), _zero_pad(W0, npad),
            _zero_pad(Xl, npad, 1), _zero_pad(Yl, npad, 1),
            _zero_pad(rows(Xte), npad), _zero_pad(rows(Yte), npad))


def slot_mask(n: int, t: int, bucket: Bucket):
    """The host side of a padded slot: ``mask`` (n_pad,) bool flagging
    the ``n`` real agents, and ``t_real``, the float true row count the
    padded-loss corrections consume."""
    mask = np.zeros(int(bucket.n_agents), bool)
    mask[:n] = True
    return mask, np.float32(t)


def pad_probe(Xp, Yp, bucket: Bucket):
    """Pad the convergence-probe split (``core.unroll.probe_batch``) to
    ``bucket``'s agent count.  Probe ROWS are a config constant
    (``cfg.probe_size``) so only the agent axis pads — with zeros, which
    ``task.masked_grad_norm`` zeroes out of the certificate exactly."""
    n, npad = Xp.shape[0], int(bucket.n_agents)
    if n > npad:
        raise ValueError(f"probe (n={n}) does not fit bucket {bucket}")
    return _zero_pad(Xp, npad), _zero_pad(Yp, npad)
