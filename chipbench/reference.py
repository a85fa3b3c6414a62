"""Plain training reference: gradients, AdamW, WSD, around an
architecture's loss.

Straight ``jax.numpy`` in float32 at ``"highest"`` matmul precision, one
layer after another, with no kernels, scans, sharding rules or caches.  It
imports nothing of the program under test: the configuration comes from the
benchmark's configuration file, the forward pass and the loss from the
architecture's module (``archs/<arch>.py``), the weights from its
``program_weights``.

``dtype=jnp.bfloat16`` gives the control: the same steps with the weights
and activations in bfloat16 (norm statistics, softmax and loss in float32,
master weights and AdamW in float32), the mixed precision a later change
might be tempted to ship.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def wsd(step: int, tr: dict) -> float:
    """MiniCPM's warmup-stable-decay multiplier of the peak rate."""
    w, s, d = tr["warmup"], tr["stable"], tr["decay"]
    if step < w:
        return step / max(w, 1)
    frac = min(max((step - w - s) / max(d, 1), 0.0), 1.0)
    return tr["final_frac"] ** frac


def adamw(w, g, m, v, count: int, lr: float, tr: dict):
    """One AdamW step with global-norm clipping; returns (w, m, v, clipped g)."""
    gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in g.values()))
    clip = jnp.minimum(1.0, tr["grad_clip"] / jnp.maximum(gnorm, 1e-9))
    c1 = 1.0 - tr["b1"] ** count
    c2 = 1.0 - tr["b2"] ** count
    out_w, out_m, out_v, out_g = {}, {}, {}, {}
    for k in w:
        gk = g[k] * clip
        mk = tr["b1"] * m[k] + (1 - tr["b1"]) * gk
        vk = tr["b2"] * v[k] + (1 - tr["b2"]) * gk * gk
        upd = (mk / c1) / (jnp.sqrt(vk / c2) + tr["eps"]) \
            + tr["weight_decay"] * w[k]
        out_w[k], out_m[k], out_v[k], out_g[k] = w[k] - lr * upd, mk, vk, gk
    return out_w, out_m, out_v, out_g


def _norms(tree: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    return {k: jnp.linalg.norm(x.astype(jnp.float32).ravel())
            for k, x in tree.items()}


def run_steps(cfg: dict, arch, make_w0: Callable[[], Dict[str, jax.Array]],
              batches: List[np.ndarray], dtype=jnp.float32
              ) -> Tuple[List[float], Dict[str, float], Dict[str, float]]:
    """Train ``len(batches)`` steps of the architecture module ``arch``'s
    loss from the weights ``make_w0()`` gives.

    Returns the loss of each step, the norm of each leaf of the first
    clipped gradient, and the norm of each leaf's change over all steps.
    ``make_w0`` is called twice, so that the starting weights need not be
    held beside the optimizer's state.
    """
    dm, tr = arch.dims(cfg), cfg["training"]
    precision = "highest" if dtype == jnp.float32 else "default"
    grad = jax.jit(jax.value_and_grad(
        lambda w, t: arch.loss_fn(dm, w, t, dtype)))

    def update(w, g, m, v, count, lr):
        w, m, v, gc = adamw(w, g, m, v, count, lr, tr)
        return w, m, v, _norms(gc)

    step = jax.jit(update, donate_argnums=(0, 1, 2, 3))
    change = jax.jit(lambda w, w0: _norms(
        {k: w[k] - w0[k] for k in w}))
    w = make_w0()
    m = {k: jnp.zeros_like(x) for k, x in w.items()}
    v = {k: jnp.zeros_like(x) for k, x in w.items()}
    losses, first_grad = [], None
    with jax.default_matmul_precision(precision):
        for i, tokens in enumerate(batches):
            loss, g = grad(w, jnp.asarray(tokens))
            w, m, v, gn = step(w, g, m, v, float(i + 1),
                               tr["lr"] * wsd(i, tr))
            losses.append(float(loss))
            if first_grad is None:
                first_grad = {k: float(x) for k, x in gn.items()}
        del m, v, g
        moved = change(w, make_w0())
    return losses, first_grad, {k: float(x) for k, x in moved.items()}
