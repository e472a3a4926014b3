"""Model FLOPs of SURF's math, counted from the shapes (multiply-adds of
the matrix products, 2 FLOPs each; elementwise work, the row sampling and
recomputation are not counted).

One unrolled layer on n agents, W (n, d), din = d + b·(F + C):

  * graph filter  Σ_k h_k S^k W in Horner form: K products S @ Y, 2n²d each;
  * perceptron    [W ∥ b_in] @ M: 2·n·din·d.

A meta-step (``meta_step_flops``) runs the L layers forward, the test
loss (2·n·t·F·C), and the L + 1 constraint gradient norms, each a logits
product and a gradient product (4·n·b·F·C). Its backward pass is charged
only for what θ needs:

  * dM of each layer (2·n·din·d), and the gradient into each layer's input
    W (the filter's K products again, and 2·n·d·d through M's W rows) for
    layers 2..L, whose input depends on θ; layer 1's input W0 is data;
  * the test loss's gradient into W_L (2·n·t·F·C);
  * the constraint norms of W_1..W_L back into W (4·n·b·F·C each).

A served solve (``solve_flops``) is the L layers forward plus each
layer's loss and accuracy on the test rows (2 · 2·n·t·F·C).
"""
from __future__ import annotations


def _shape(cfg, n=None):
    F, C, b = cfg["feature_dim"], cfg["n_classes"], cfg["batch_per_agent"]
    d = F * C + C
    return (cfg["n_agents"] if n is None else n, d, d + b * (F + C), F, C, b,
            cfg["filter_taps"], cfg["n_layers"])


def layer_flops(cfg, n=None):
    """(filter, perceptron) FLOPs of one layer's forward pass."""
    n, d, din, F, C, b, K, L = _shape(cfg, n)
    return 2 * K * n * n * d, 2 * n * din * d


def meta_step_flops(cfg):
    n, d, din, F, C, b, K, L = _shape(cfg)
    t = cfg["test_per_agent"]
    filt, perc = layer_flops(cfg)
    forward = L * (filt + perc) + 2 * n * t * F * C + (L + 1) * 4 * n * b * F * C
    backward = (L * perc + (L - 1) * (filt + 2 * n * d * d)
                + 2 * n * t * F * C + L * 4 * n * b * F * C)
    return forward + backward


def solve_flops(cfg, n=None):
    n_, d, din, F, C, b, K, L = _shape(cfg, n)
    t = cfg["test_per_agent"]
    filt, perc = layer_flops(cfg, n_)
    return L * (filt + perc + 4 * n_ * t * F * C)
