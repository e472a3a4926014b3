"""jit'd public wrapper with a custom VJP (the unrolled optimizer trains
THROUGH the graph filter, eq. 6).

  Y = Σ_k h_k S^k W
  ∂L/∂W = Σ_k h_k (Sᵀ)^k Ḡ          — a graph filter with Sᵀ (same kernel!)
  ∂L/∂h_k = ⟨Ḡ, S^k W⟩
  ∂L/∂S = Σ_k h_k Σ_{a+b=k−1} (Sᵀ)^a Ḡ (S^b W)ᵀ

The dS term is the expensive one (K² extra matmuls) but training holds S
constant — its cotangent is unused, so JAX's backward-pass partial eval /
XLA DCE prune it; only dW (one more kernel call) and dh survive on the
meta-training hot path.

Dispatch rules (``graph_filter``, the single public entry point):

  * argument order is ``(S, W, h)``, matching ``core.unroll.graph_filter``
    and the engine's mixer protocol.
  * ``interpret=None`` auto-selects by backend: COMPILED Pallas on
    TPU/GPU, the Pallas interpreter everywhere else (CPU has no Mosaic
    target — interpreter mode is a correctness path, not a perf path).
    Pass ``interpret=`` explicitly to override either way.
  * ``block_d=None`` picks the widest power-of-two column block that
    divides the 128-padded d and keeps S plus three (n, block_d) W/Y
    buffers inside a ~8 MB VMEM budget (``pick_block_d``).
  * ``impl``: "pallas" forces the kernel, "jnp" forces the reference
    Horner loop (``ref.graph_filter_ref``, natively differentiable),
    "auto" uses the kernel only when ``pallas_profitable(n, d)`` — the
    (8, 128) tile padding must not more than 4× the real element count,
    else the padding work dominates whatever the fusion saves.

Padding note: zero-padded agent rows of W and zero rows/cols of S leave
real outputs untouched, so pad→kernel→slice is exact.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.graph_filter.kernel import graph_filter_pallas
from repro.kernels.graph_filter.ref import graph_filter_ref

# Backends with a compiled Pallas lowering for this kernel. Everything
# else (cpu, the default test/CI platform) runs the interpreter.
_COMPILED_BACKENDS = ("tpu", "gpu")

IMPLS = ("pallas", "jnp", "auto")


def resolve_interpret(interpret=None):
    """Backend-aware interpreter default: None -> interpret only where no
    compiled Pallas target exists (anything but TPU/GPU). An explicit
    bool always wins — callers debugging a TPU kernel can force the
    interpreter, and tests can pin the mode into cache tags."""
    if interpret is not None:
        return bool(interpret)
    return jax.default_backend() not in _COMPILED_BACKENDS


def _padded(n, d):
    return n + (-n) % 8, d + (-d) % 128


def pick_block_d(n, d):
    """Column-block width for an (n, n) × (n, d) filter: the widest
    power-of-two block that divides the 128-padded d while S (VMEM-
    resident across all K hops) plus three (n, block_d) W/Y buffers fit
    a ~8 MB f32 budget (half a TPU core's VMEM, leaving room for
    double-buffering)."""
    n_p, d_p = _padded(n, d)
    budget = (8 * 1024 * 1024) // 4               # f32 elements
    avail = max(budget - n_p * n_p, 3 * n_p * 128)
    bd = 128
    while (bd * 2 <= d_p and d_p % (bd * 2) == 0
           and 3 * n_p * (bd * 2) <= avail):
        bd *= 2
    return bd


def pallas_profitable(n, d):
    """The ``impl="auto"`` rule: tile only when the (8, 128) padding keeps
    the padded element count within 4× the real one (and at least one
    full sublane of agents exists). Below that, the kernel mostly
    multiplies zeros — the jnp Horner loop wins."""
    n_p, d_p = _padded(n, d)
    return n >= 8 and n_p * d_p <= 4 * n * d


def _pad_call(h, S, W, block_d, interpret):
    n, d = W.shape
    n_pad = (-n) % 8
    d_pad = (-d) % 128
    Sp = jnp.pad(S, ((0, n_pad), (0, n_pad)))
    Wp = jnp.pad(W, ((0, n_pad), (0, d_pad)))
    Y = graph_filter_pallas(Sp, Wp, h, block_d=block_d, interpret=interpret)
    return Y[:n, :d]


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _graph_filter(h, S, W, block_d, interpret):
    return _pad_call(h, S, W, block_d, interpret)


def _fwd(h, S, W, block_d, interpret):
    return _pad_call(h, S, W, block_d, interpret), (h, S, W)


def _bwd(block_d, interpret, res, g):
    h, S, W = res
    K = h.shape[0] - 1
    g = g.astype(jnp.float32)
    dW = _pad_call(h, S.T, g, block_d, interpret).astype(W.dtype)
    # powers P_k = S^k W
    powers = [W.astype(jnp.float32)]
    for _ in range(K):
        powers.append(S.astype(jnp.float32) @ powers[-1])
    dh = jnp.stack([jnp.sum(g * p) for p in powers]).astype(h.dtype)
    # dS (graphs are usually fixed — DCE'd when S's cotangent is unused,
    # but kept exact for topology-learning callers)
    gT = [g]          # (S^T)^a g
    for _ in range(K):
        gT.append(S.T.astype(jnp.float32) @ gT[-1])
    dS = jnp.zeros_like(S, dtype=jnp.float32)
    for k in range(1, K + 1):
        for a in range(k):
            dS = dS + h[k].astype(jnp.float32) * gT[a] @ powers[k - 1 - a].T
    return dh, dS.astype(S.dtype), dW


_graph_filter.defvjp(_fwd, _bwd)


@partial(jax.jit, static_argnames=("block_d", "interpret", "impl"))
def graph_filter(S, W, h, block_d=None, interpret=None, impl="pallas"):
    """Fused K-hop graph filter Σ_k h_k S^k W with a custom VJP.

    S (n, n), W (n, d), h (K+1,). See the module docstring for the
    ``block_d`` / ``interpret`` / ``impl`` dispatch rules; all three are
    static (they select the traced computation, not values)."""
    if impl not in IMPLS:
        raise ValueError(f"impl must be one of {IMPLS}, got {impl!r}")
    n, d = W.shape
    if impl == "jnp" or (impl == "auto" and not pallas_profitable(n, d)):
        return graph_filter_ref(S, W, h)
    bd = pick_block_d(n, d) if block_d is None else int(block_d)
    return _graph_filter(h, S, W, bd, resolve_interpret(interpret))


def make_pallas_mix(*, block_d=None, interpret=None, tag=None):
    """S-as-argument dense mixer routing the eq.-6 graph filter of every
    unrolled layer through the Pallas kernel: ``mix_fn(S, W, h)`` with
    ``takes_S = True`` — the engine protocol telling
    ``core.unroll.udgd_layer`` to pass the CURRENT mixing matrix instead
    of a value baked at build time.

    Because S stays a jit ARGUMENT, the mixer composes with everything
    the dense path does: topology schedules (the scan body hands it
    S_t), the seed-batched engine (each vmap lane hands it its own S_i)
    and the engine cache (no content hash in the tag — same S-out-of-
    the-closure contract as the dense matmul path). Meta-gradients flow
    through the kernel's custom VJP (dW/dh; the unused dS cotangent is
    DCE'd).

    ``train_surf(mix="pallas")`` builds exactly this mixer."""
    mode = resolve_interpret(interpret)

    def mix_fn(S, W, h):
        return graph_filter(S, W, h, block_d=block_d, interpret=interpret,
                            impl="pallas")

    mix_fn.takes_S = True
    mix_fn.tag = tag if tag is not None else (
        "pallas", jax.default_backend(),
        0 if block_d is None else int(block_d), bool(mode))
    return mix_fn
