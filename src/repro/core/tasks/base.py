"""The ``Task`` interface: the inner FL problem the unrolled optimizer
solves, as a first-class object.

A ``Task`` is a FROZEN dataclass (hashable, compared by value) so it can
sit inside jit static arguments and the engine/eval cache keys.
Subclasses define the per-agent ``local_loss`` / ``local_metric`` on one
agent's weight row, how a mini-batch flattens into the perceptron input
(``batch_vector``), the dataset synthesis hook, and a stable
``cache_tag``; the federated lifts (``fl_loss`` / ``fl_metric`` /
``fl_grad`` / ``grad_norm``) and the W0 sampler (``init_state``) are
shared here and reproduce ``core.tasks.classification``'s module-level
functions bit-exactly.

The engine never branches on the task kind — it only calls this
interface — which is what makes classification and sparse recovery run
through the identical meta-step/mixers/schedules/2-D mesh.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class Task:
    kind = "abstract"
    metric_name = "metric"       # what fl_metric measures (accuracy / nmse)
    metric_higher_better = True
    label_dtype = jnp.int32      # dtype of Ytr/Yte leaves

    # ------------------------------------------------ subclass contract
    @property
    def dim(self) -> int:
        """Per-agent weight dimension d (rows of W ∈ R^{n×d})."""
        raise NotImplementedError

    @property
    def feat_dim(self) -> int:
        """Per-example feature dimension F (trailing axis of Xtr/Xte)."""
        raise NotImplementedError

    @property
    def batch_feat(self) -> int:
        """Per-example width in the flattened perceptron input b_i —
        features plus the label channel(s)."""
        raise NotImplementedError

    @property
    def cache_tag(self):
        """Hashable tag folded into every engine/eval cache key. Two tasks
        with equal tags MUST trace identical computations."""
        raise NotImplementedError

    def local_loss(self, w, X, Y):
        """f_i(w): one agent's loss on its batch. w (d,), X (b,F), Y (b,)."""
        raise NotImplementedError

    def local_metric(self, w, X, Y):
        """Per-agent reporting metric (accuracy, NMSE, ...)."""
        raise NotImplementedError

    def batch_vector(self, Xb, Yb):
        """Flatten per-agent mini-batches into the perceptron input:
        Xb (n,b,F), Yb (n,b) -> (n, b*batch_feat)."""
        raise NotImplementedError

    def synth_datasets(self, cfg, Q, seed=0, **kw):
        """Q synthetic downstream datasets (list of Xtr/Ytr/Xte/Yte dicts
        in the engine's (n, m, F)/(n, m) layout)."""
        raise NotImplementedError

    # ------------------------------------------------- shared FL lifts
    def fl_loss(self, W, X, Y):
        """f(W) = (1/n) Σ_i f_i(w_i).  W (n,d), X (n,b,F), Y (n,b)."""
        with jax.named_scope("surf/loss"):
            return jnp.mean(jax.vmap(self.local_loss)(W, X, Y))

    def fl_metric(self, W, X, Y):
        with jax.named_scope("surf/loss"):
            return jnp.mean(jax.vmap(self.local_metric)(W, X, Y))

    def fl_grad(self, W, X, Y):
        """Stochastic ∇f(W) ∈ R^{n×d} — row i is ∇f_i(w_i)/n."""
        g = jax.vmap(jax.grad(self.local_loss))(W, X, Y)
        return g / W.shape[0]

    def grad_norm(self, W, X, Y):
        """‖∇f(W)‖_F — the quantity the descending constraints control."""
        g = self.fl_grad(W, X, Y)
        return jnp.sqrt(jnp.sum(jnp.square(g)) + 1e-12)

    def masked_grad_norm(self, W, X, Y, mask):
        """``grad_norm`` over the REAL agents of a padded cohort: padded
        rows are zeroed out of the gradient and the 1/n normalization
        uses the real agent count, so the value equals ``grad_norm`` on
        the unpadded cohort exactly (zero rows add exact zeros to the
        reduction). This is the serve-path early-exit certificate —
        padding must not perturb the exit decision."""
        g = jax.vmap(jax.grad(self.local_loss))(W, X, Y)
        g = jnp.where(mask[:, None], g, 0.0)
        n_real = jnp.maximum(jnp.sum(mask), 1.0)
        return jnp.sqrt(jnp.sum(jnp.square(g / n_real)) + 1e-12)

    def init_state(self, key, cfg):
        """W0 ~ N(w0_mean, w0_std²) ∈ R^{n×d} — the unrolled net's input."""
        return cfg.w0_mean + cfg.w0_std * jax.random.normal(
            key, (cfg.n_agents, self.dim))

    # -------------------------------------------- padded-row corrections
    # The serving layer (``repro.serve``) pads each agent's eval rows up
    # to a bucket size t_pad by REPLICATING ROW 0 (so padded rows are
    # in-distribution and shape-stable), then un-biases the padded value
    # here. The default corrections are EXACT whenever local_loss /
    # local_metric is a mean over rows plus a row-independent term
    # (classification CE/accuracy; the LASSO loss's ρ‖w‖₁ is row-free):
    # with t_pad rows of which t_pad − t_real are copies of row 0,
    #     t_pad·mean_pad = t_real·mean_real + (t_pad − t_real)·stat(row 0)
    # which solves to
    #     L_real = (t_pad·L_pad − (t_pad − t_real)·L_0) / t_real
    # where L_0 is the statistic on an all-row-0 batch. Ratio-of-sums
    # metrics (sparse NMSE) must override ``padded_local_metric``.

    def padded_local_loss(self, w, X, Y, t_real):
        """``local_loss`` on a row-0-padded batch, corrected back to the
        value on the first ``t_real`` rows. X (t_pad,F), Y (t_pad,)."""
        t_pad = X.shape[0]
        Lp = self.local_loss(w, X, Y)
        X0 = jnp.broadcast_to(X[:1], X.shape)
        Y0 = jnp.broadcast_to(Y[:1], Y.shape)
        L0 = self.local_loss(w, X0, Y0)
        tr = jnp.maximum(t_real, 1.0)
        Lr = (t_pad * Lp - (t_pad - t_real) * L0) / tr
        return jnp.where(t_real == t_pad, Lp, Lr)

    def padded_local_metric(self, w, X, Y, t_real):
        """``local_metric`` on a row-0-padded batch, corrected back to the
        value on the first ``t_real`` rows (mean-over-rows default)."""
        t_pad = X.shape[0]
        Mp = self.local_metric(w, X, Y)
        X0 = jnp.broadcast_to(X[:1], X.shape)
        Y0 = jnp.broadcast_to(Y[:1], Y.shape)
        M0 = self.local_metric(w, X0, Y0)
        tr = jnp.maximum(t_real, 1.0)
        Mr = (t_pad * Mp - (t_pad - t_real) * M0) / tr
        return jnp.where(t_real == t_pad, Mp, Mr)


def resolve_task(cfg, task=None):
    """The one task-resolution point: an explicit ``task`` object wins;
    otherwise ``cfg.task`` (a ``configs.base.TaskConfig``) is materialized;
    ``cfg.task is None`` yields the legacy classification task built from
    ``cfg.feature_dim``/``cfg.n_classes`` (bit-exact default path)."""
    if task is not None:
        return task
    tc = getattr(cfg, "task", None)
    kind = getattr(tc, "kind", "classification")
    if kind == "classification":
        from repro.core.tasks.classification import ClassificationTask
        if tc is None:
            return ClassificationTask(feat_dim=cfg.feature_dim,
                                      n_classes=cfg.n_classes)
        return ClassificationTask(feat_dim=tc.feature_dim,
                                  n_classes=tc.n_classes)
    if kind == "sparse_recovery":
        from repro.core.tasks.sparse_recovery import SparseRecoveryTask
        return SparseRecoveryTask(signal_dim=tc.signal_dim, rho=tc.rho,
                                  sparsity=tc.sparsity, noise=tc.noise)
    raise ValueError(f"unknown task kind {kind!r}")
