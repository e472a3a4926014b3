"""Classification task (paper §6): collaboratively train a softmax
classifier head on frozen backbone features.

Per-agent head weights are flattened into rows of W ∈ R^{n×d},
d = F·C + C. The paper freezes a ResNet18; here features come from
``data/synthetic.py`` (offline container) or from any assigned
architecture's final hidden state via ``features_from_backbone``.

The module-level functions take the feature and class counts as
arguments; ``ClassificationTask`` wraps them behind the generic ``Task``
interface so the engine traces the identical graph either way.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.core.tasks.base import Task


def head_dim(feat_dim, n_classes):
    return feat_dim * n_classes + n_classes


def unflatten(w, feat_dim, n_classes):
    Wm = w[: feat_dim * n_classes].reshape(feat_dim, n_classes)
    b = w[feat_dim * n_classes:]
    return Wm, b


def local_loss(w, X, Y, feat_dim, n_classes):
    """CE of one agent's head on its batch. X (b, F), Y (b,) int."""
    Wm, b = unflatten(w, feat_dim, n_classes)
    logits = X @ Wm + b
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, Y[:, None], axis=-1))


def local_accuracy(w, X, Y, feat_dim, n_classes):
    Wm, b = unflatten(w, feat_dim, n_classes)
    return jnp.mean((jnp.argmax(X @ Wm + b, -1) == Y).astype(jnp.float32))


def fl_loss(W, X, Y, feat_dim, n_classes):
    """f(W) = (1/n) Σ_i f_i(w_i).  X (n, b, F), Y (n, b)."""
    losses = jax.vmap(local_loss, (0, 0, 0, None, None))(
        W, X, Y, feat_dim, n_classes)
    return jnp.mean(losses)


def fl_accuracy(W, X, Y, feat_dim, n_classes):
    accs = jax.vmap(local_accuracy, (0, 0, 0, None, None))(
        W, X, Y, feat_dim, n_classes)
    return jnp.mean(accs)


def fl_grad(W, X, Y, feat_dim, n_classes):
    """Stochastic ∇f(W) ∈ R^{n×d} — row i is ∇f_i(w_i)/n (matches f's 1/n)."""
    g = jax.vmap(jax.grad(local_loss), (0, 0, 0, None, None))(
        W, X, Y, feat_dim, n_classes)
    return g / W.shape[0]


def grad_norm(W, X, Y, feat_dim, n_classes):
    """‖∇f(W)‖_F — the quantity the descending constraints control."""
    g = fl_grad(W, X, Y, feat_dim, n_classes)
    return jnp.sqrt(jnp.sum(jnp.square(g)) + 1e-12)


def features_from_backbone(cfg, params, tokens):
    """Frozen-feature extraction from an assigned architecture: the final
    pre-logits hidden state, mean-pooled over the sequence."""
    from repro.models import model as M  # noqa: F401  (kept for parity)
    from repro.models import stack as ST
    from repro.models import layers as L
    x = L.embed(params["embed"], tokens)
    ctx = ST.Ctx(mode="full")
    for name, reps, kinds in ST.build_segments(cfg):
        x, _, _ = ST.apply_segment(cfg, kinds, params["segments"][name],
                                   x, None, ctx)
    x = L.apply_norm(cfg.norm, params["final_norm"], x)
    return jnp.mean(x, axis=1)


@dataclass(frozen=True)
class ClassificationTask(Task):
    feat_dim: int = 64
    n_classes: int = 10

    kind = "classification"
    metric_name = "accuracy"
    metric_higher_better = True
    label_dtype = jnp.int32

    @property
    def dim(self) -> int:
        return head_dim(self.feat_dim, self.n_classes)

    @property
    def batch_feat(self) -> int:
        return self.feat_dim + self.n_classes

    @property
    def cache_tag(self):
        return ("classification", self.feat_dim, self.n_classes)

    def local_loss(self, w, X, Y):
        return local_loss(w, X, Y, self.feat_dim, self.n_classes)

    def local_metric(self, w, X, Y):
        return local_accuracy(w, X, Y, self.feat_dim, self.n_classes)

    def batch_vector(self, Xb, Yb):
        """Each example's features and one-hot label follow each other:
        Xb (n, b, F), Yb (n, b) -> (n, b*(F+C))."""
        oh = jax.nn.one_hot(Yb, self.n_classes, dtype=Xb.dtype)
        packed = jnp.concatenate([Xb, oh], axis=-1)      # (n, b, F+C)
        return packed.reshape(Xb.shape[0], -1)

    def synth_datasets(self, cfg, Q, seed=0, **kw):
        from repro.data.synthetic import make_meta_dataset
        return make_meta_dataset(cfg, Q, seed=seed, **kw)


def classification_task(cfg) -> ClassificationTask:
    """The classification task a config describes (its ``task`` field, or
    the legacy ``feature_dim``/``n_classes`` pair when that is None)."""
    tc = cfg.task_config
    if tc.kind != "classification":
        raise ValueError(f"cfg describes a {tc.kind!r} task")
    return ClassificationTask(feat_dim=tc.feature_dim, n_classes=tc.n_classes)
