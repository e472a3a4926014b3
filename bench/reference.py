"""Plain reference of SURF's math, in ``jax.numpy`` alone, and the numbers
that decide ``correct``.

It imports nothing of the program and takes nothing the program made: θ,
the federations and the graph come again from the seed (``surfgen``), and
the program's random draws (W0 and the layer mini-batch rows) are made
again from the same keys, as the paper's Algorithm 1 draws them:

  * meta-step t uses ``fold_in(key, t)``, split into (W0 key, rows key);
  * W0 = w0_mean + w0_std · N(0, I) of shape (n, d);
  * rows: ``randint(rows key, (L, n, b), 0, m)`` into each agent's
    training split, picked here by a host gather.

One layer is  W' = Σ_k h_k S^k W − relu([W ∥ b_in] M + d)  with b_in each
sampled example's features then its one-hot label. The loss is the mean
over agents of the softmax cross-entropy of each agent's head; the
constraint slacks are ‖∇f(W_l)‖ − (1 − ε)‖∇f(W_{l−1})‖ with ∇f written
out by hand; the meta-step differentiates the Lagrangian, clips the
gradient to a global norm of 10, takes an Adam step (0.9, 0.999, 1e-8) and
a projected dual step.

``dtype`` float32 runs at HIGHEST matmul precision, the reference; the
control runs the same code in bfloat16 (every array, default precision).
"""
from __future__ import annotations

from functools import partial

import numpy as np

import surfgen

MAX_NORM = 10.0
B1, B2, ADAM_EPS = 0.9, 0.999, 1e-8


def _prec(dtype):
    import jax
    import jax.numpy as jnp
    return (jax.lax.Precision.HIGHEST if jnp.dtype(dtype) == jnp.float32
            else jax.lax.Precision.DEFAULT)


def head_grad(W, X, Y, F, C, prec):
    """Per-agent CE losses (n,) and ∇f(W) (n, d), row i = ∇f_i / n."""
    import jax
    import jax.numpy as jnp
    n, b = X.shape[0], X.shape[1]
    Wm = W[:, :F * C].reshape(n, F, C)
    logits = jnp.einsum("nbf,nfc->nbc", X, Wm, precision=prec) + \
        W[:, None, F * C:]
    logp = jax.nn.log_softmax(logits, axis=-1)
    oh = jax.nn.one_hot(Y, C, dtype=W.dtype)
    loss = -jnp.mean(jnp.sum(oh * logp, -1), -1)
    dlog = (jnp.exp(logp) - oh) / b
    gWm = jnp.einsum("nbf,nbc->nfc", X, dlog, precision=prec)
    g = jnp.concatenate([gWm.reshape(n, F * C), jnp.sum(dlog, 1)], -1)
    return loss, g / n


def accuracy(W, X, Y, F, C, prec):
    import jax.numpy as jnp
    n = X.shape[0]
    logits = jnp.einsum("nbf,nfc->nbc", X, W[:, :F * C].reshape(n, F, C),
                        precision=prec) + W[:, None, F * C:]
    return jnp.mean((jnp.argmax(logits, -1) == Y).astype(jnp.float32))


def layer(p, S, W, Xb, Yb, F, C, prec):
    import jax
    import jax.numpy as jnp
    h = p["h"]
    K = h.shape[0] - 1
    mixed = h[K] * W
    for k in range(K - 1, -1, -1):
        mixed = jnp.matmul(S, mixed, precision=prec) + h[k] * W
    oh = jax.nn.one_hot(Yb, C, dtype=W.dtype)
    b_in = jnp.concatenate([Xb, oh], -1).reshape(W.shape[0], -1)
    z = jnp.matmul(jnp.concatenate([W, b_in], -1), p["M"],
                   precision=prec) + p["d"]
    return mixed - jax.nn.relu(z)


def draws(key_t, cfg, m):
    """The step's W0 and row indices, from its key."""
    import jax
    d, _ = surfgen.dims(cfg)
    kw, kb = jax.random.split(key_t)
    W0 = cfg["w0_mean"] + cfg["w0_std"] * jax.random.normal(
        kw, (cfg["n_agents"], d))
    idx = jax.random.randint(kb, (cfg["n_layers"], cfg["n_agents"],
                                  cfg["batch_per_agent"]), 0, m)
    return W0, np.asarray(idx)


def gather_rows(fed, idx):
    """Host gather of the sampled rows: (L, n, b, F), (L, n, b)."""
    Xtr, Ytr = np.asarray(fed["Xtr"]), np.asarray(fed["Ytr"])
    agents = np.arange(Xtr.shape[0])[None, :, None]
    return Xtr[agents, idx], Ytr[agents, idx]


def _cast(tree, dtype):
    import jax
    import jax.numpy as jnp
    return jax.tree_util.tree_map(
        lambda a: a.astype(dtype) if jnp.issubdtype(a.dtype, jnp.floating)
        else a, tree)


def make_train_step(cfg, dtype):
    """Jitted reference meta-step (θ, m, v, t, λ, S, W0, Xl, Yl, Xte, Yte)
    → (θ, m, v, λ, loss, per-leaf norms of the clipped gradient); θ, m
    and v are donated."""
    import jax
    import jax.numpy as jnp
    F, C, L = cfg["feature_dim"], cfg["n_classes"], cfg["n_layers"]
    prec = _prec(dtype)
    one_minus_eps = 1.0 - cfg["eps"]

    def lagrangian(theta, lam, S, W0, Xl, Yl, Xte, Yte):
        W, Ws = W0, [W0]
        for l in range(L):
            p = jax.tree_util.tree_map(lambda a: a[l], theta)
            W = layer(p, S, W, Xl[l], Yl[l], F, C, prec)
            Ws.append(W)
        loss = jnp.mean(head_grad(W, Xte, Yte, F, C, prec)[0])
        g = [jnp.sqrt(jnp.sum(jnp.square(
            head_grad(Ws[l], Xl[max(l - 1, 0)], Yl[max(l - 1, 0)], F, C,
                      prec)[1])) + 1e-12) for l in range(L + 1)]
        g = jnp.stack(g)
        slack = g[1:] - one_minus_eps * g[:-1]
        return loss + jnp.sum(lam * slack), (loss, slack)

    @partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(theta, m, v, t, lam, S, W0, Xl, Yl, Xte, Yte):
        (_, (loss, slack)), grads = jax.value_and_grad(
            lagrangian, has_aux=True)(theta, lam, S, W0, Xl, Yl, Xte, Yte)
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                          for g in jax.tree_util.tree_leaves(grads)))
        scale = jnp.minimum(1.0, MAX_NORM / (gn + 1e-9)).astype(dtype)
        grads = jax.tree_util.tree_map(lambda g: g * scale, grads)
        t = t + 1
        m = jax.tree_util.tree_map(lambda a, g: B1 * a + (1 - B1) * g,
                                   m, grads)
        v = jax.tree_util.tree_map(lambda a, g: B2 * a + (1 - B2) * g * g,
                                   v, grads)
        bc1, bc2 = 1 - B1 ** t, 1 - B2 ** t
        theta = jax.tree_util.tree_map(
            lambda p, a, b: p - (cfg["lr_theta"] * (a / bc1)
                                 / (jnp.sqrt(b / bc2) + ADAM_EPS)
                                 ).astype(p.dtype), theta, m, v)
        lam = jnp.maximum(lam + cfg["lr_lambda"] * slack, 0.0)
        return theta, m, v, lam, loss, leaf_norms(grads)
    return step


def leaf_norms(tree):
    """{leaf: ‖leaf‖} (float32 sums), traceable."""
    import jax.numpy as jnp
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


def host(norms):
    return {k: float(v) for k, v in norms.items()}


def train_readings(cfg, key, steps, dtype):
    """The reference's readings over the first ``steps`` meta-steps from
    the seed: losses, the duals λ after each step, per-leaf norms of the
    first (clipped) gradient, and per-leaf norms of θ's change after
    ``steps`` steps."""
    import jax
    import jax.numpy as jnp
    S = jnp.asarray(surfgen.mixing_matrix(cfg, graph_seed(key)))
    theta = _cast(surfgen.make_theta(key, cfg, cfg["theta_scale"]), dtype)
    m = jax.tree_util.tree_map(jnp.zeros_like, theta)
    v = jax.tree_util.tree_map(jnp.zeros_like, theta)
    lam = jnp.zeros(cfg["n_layers"], dtype)
    step = make_train_step(cfg, dtype)
    losses, lams, first_grad = [], [], None
    for t in range(steps):
        fed = surfgen.pool_member(key, cfg, t % cfg["meta_pool"])
        W0, idx = draws(jax.random.fold_in(key, t), cfg,
                        cfg["train_per_agent"])
        Xl, Yl = gather_rows(fed, idx)
        S_, W0, Xl, Xte = _cast((S, W0, jnp.asarray(Xl), fed["Xte"]), dtype)
        theta, m, v, lam, loss, g = step(
            theta, m, v, t, lam, S_, W0, Xl, jnp.asarray(Yl), Xte,
            fed["Yte"])
        losses.append(float(loss))
        lams.append(np.asarray(lam, np.float64).tolist())
        if t == 0:
            first_grad = host(g)
    del m, v
    change = host(surfgen.change_norms(theta, key, cfg))
    return {"loss": losses, "lam": lams, "grad": first_grad,
            "change": change}


def graph_seed(key):
    """The graph's seed, from the run's raw key (its two uint32 words)."""
    hi, lo = (int(x) for x in np.asarray(key))
    return (hi << 32) | lo


def leaf_gap(prog, ref, keep=None):
    """Worst leaf of |‖prog‖ − ‖ref‖| over max(‖ref leaf‖, median ‖ref‖)."""
    keys = [k for k in ref if keep is None or k in keep]
    med = float(np.median([ref[k] for k in ref]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
               for k in keys)


def moved_leaves(grad):
    """Leaves whose reference gradient is at least a thousandth of the
    median leaf's: the others move under Adam by round-off alone."""
    med = float(np.median(list(grad.values())))
    return {k for k, g in grad.items() if g >= 1e-3 * med}


def lam_gap(prog, ref):
    """Relative gap ‖λ_prog − λ_ref‖ / ‖λ_ref‖ of the duals after one step."""
    p, r = np.asarray(prog), np.asarray(ref)
    return float(np.linalg.norm(p - r) / max(np.linalg.norm(r), 1e-30))


def train_gaps(prog, ref):
    """The numbers a training cell can compare (its file gives the limits
    of those it does), and each step's relative loss and dual gaps beside
    them (``step_loss_gaps``, ``step_lam_gaps``, not compared).

    ``lam_gap`` holds the constraint path to the reference: λ after the
    first step is [μ_λ · slack]_+, the slacks of the L + 1 per-layer
    gradient norms at the seed's θ, so the norms, the slacks and the
    projected dual step all enter it; the first gradient is taken at
    λ = 0 and does not see them.

    Only the first step's loss is compared: Adam's first update is about
    lr·sign(g) in every entry of θ, whatever the size of g, so entries
    whose gradient is at rounding level take a full step of either sign
    and the losses of steps 2 and 3 (hundreds of times the first, at
    these sizes) carry that noise."""
    steps = [abs(a - b) / max(abs(b), 1e-30)
             for a, b in zip(prog["loss"], ref["loss"])]
    return {"first_loss_gap": steps[0],
            "grad_gap": leaf_gap(prog["grad"], ref["grad"]),
            "change_gap": leaf_gap(prog["change"], ref["change"],
                                   keep=moved_leaves(ref["grad"])),
            "lam_gap": lam_gap(prog["lam"][0], ref["lam"][0]),
            "step_loss_gaps": steps,
            "step_lam_gaps": [lam_gap(a, b)
                              for a, b in zip(prog["lam"], ref["lam"])]}


# ------------------------------------------------------------------ serve
def make_solve(cfg, dtype):
    """Jitted reference solve of one federation: (θ, S, W0, Xl, Yl, Xte,
    Yte) → (W_L, final loss, final accuracy)."""
    import jax
    import jax.numpy as jnp
    F, C, L = cfg["feature_dim"], cfg["n_classes"], cfg["n_layers"]
    prec = _prec(dtype)

    @jax.jit
    def solve(theta, S, W0, Xl, Yl, Xte, Yte):
        W = W0
        for l in range(L):
            p = jax.tree_util.tree_map(lambda a: a[l], theta)
            W = layer(p, S, W, Xl[l], Yl[l], F, C, prec)
        loss = jnp.mean(head_grad(W, Xte, Yte, F, C, prec)[0])
        return W, loss, accuracy(W, Xte, Yte, F, C, prec)
    return solve


def solve_key(solve_seed):
    """The server's RNG stream for a request submitted with
    ``seed=solve_seed, q=0``: fold_in(PRNGKey(1000 + seed), 0)."""
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(1000 + int(solve_seed)), 0)


def serve_reference(cfg, theta, S, fed, solve_seed, solve):
    """Reference answer for one request."""
    import jax.numpy as jnp
    dtype = theta["M"].dtype
    W0, idx = draws(solve_key(solve_seed), cfg, np.asarray(fed["Xtr"]).shape[1])
    Xl, Yl = gather_rows(fed, idx)
    args = _cast((jnp.asarray(S), W0, jnp.asarray(Xl),
                  jnp.asarray(fed["Xte"])), dtype)
    W, loss, acc = solve(theta, args[0], args[1], args[2], jnp.asarray(Yl),
                         args[3], jnp.asarray(fed["Yte"]))
    return np.asarray(W, np.float32), float(loss), float(acc)


def serve_gaps(answers, refs):
    """Widest relative gap of the served W and of the final loss, and the
    widest accuracy gap, over the compared requests."""
    w = max(float(np.linalg.norm(a["W"] - r[0]) / np.linalg.norm(r[0]))
            for a, r in zip(answers, refs))
    loss = max(abs(float(a["final_loss"]) - r[1]) / abs(r[1])
               for a, r in zip(answers, refs))
    acc = max(abs(float(a["final_acc"]) - r[2])
              for a, r in zip(answers, refs))
    return {"w_gap": w, "loss_gap": loss, "acc_gap": acc}
