"""Plain MiniCPM training reference: forward, loss, gradients, AdamW, WSD.

Straight ``jax.numpy`` in float32 at ``"highest"`` matmul precision, one
layer after another, with no kernels, scans, sharding rules or caches.  It
imports nothing of the program under test: the configuration comes from the
benchmark's configuration file and the weights from ``weights.py``.

The layer equations follow the published MiniCPM (llama-like block,
``scale_emb`` on the embedding, residual branches scaled by
``scale_depth / sqrt(num_hidden_layers)``, tied head divided by
``hidden_size / dim_model_base``), with the file's ``rms_norm_eps``.  The
softmax and the loss span the ``vocab_size`` rows of the vocabulary and no
more: rows the program pads its table with (``as_run.padded_vocab_rows``)
are in the weights but never among the classes.  The norm's scale is stored
as ``1 + w``, as ``as_run`` says: the published norm with its weight
starting at 1, whose offset ``w`` AdamW decays.

``dtype=jnp.bfloat16`` gives the control: the same steps with the weights
and activations in bfloat16 (norm statistics, softmax and loss in float32,
master weights and AdamW in float32), the mixed precision a later change
might be tempted to ship.
"""
from __future__ import annotations

import math
from typing import Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def dims(cfg: dict) -> dict:
    """The numbers the equations use, from a configuration file's dict."""
    d = cfg["hidden_size"]
    n_heads = cfg["num_attention_heads"]
    return dict(
        d=d, heads=n_heads, kv_heads=cfg["num_key_value_heads"],
        hd=cfg.get("head_dim") or d // n_heads,
        ffn=cfg["intermediate_size"], layers=cfg["num_hidden_layers"],
        vocab=cfg["vocab_size"], vocab_rows=cfg["as_run"]["padded_vocab_rows"],
        eps=cfg["rms_norm_eps"], theta=cfg["rope_theta"],
        scale_emb=float(cfg["scale_emb"]),
        residual=cfg["scale_depth"] / math.sqrt(
            cfg["published"]["num_hidden_layers"]),
        logit_div=d / cfg["dim_model_base"])


def _rms_norm(x, w, eps):
    x32 = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * inv * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def _rope(x, theta):
    """Rotate-half RoPE on (B, S, N, hd), positions 0..S-1."""
    hd, S = x.shape[-1], x.shape[1]
    freqs = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = np.arange(S, dtype=np.float64)[:, None] * freqs[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


def loss_fn(dm: dict, w: Dict[str, jax.Array], tokens: jax.Array,
            dtype=jnp.float32) -> jax.Array:
    """Mean next-token cross-entropy of ``tokens`` (B, S)."""
    B, S = tokens.shape
    H, KV, hd = dm["heads"], dm["kv_heads"], dm["hd"]
    c = lambda name: w[name].astype(dtype)
    emb = c("embed")
    x = emb[tokens] * jnp.asarray(dm["scale_emb"], dtype)
    causal = np.tril(np.ones((S, S), dtype=bool))
    for l in range(dm["layers"]):
        p = f"layers.{l}."
        h = _rms_norm(x, w[p + "attn_norm"], dm["eps"])
        q = (h @ c(p + "wq")).reshape(B, S, H, hd)
        k = (h @ c(p + "wk")).reshape(B, S, KV, hd)
        v = (h @ c(p + "wv")).reshape(B, S, KV, hd)
        q, k = _rope(q, dm["theta"]), _rope(k, dm["theta"])
        k = jnp.repeat(k, H // KV, axis=2)
        v = jnp.repeat(v, H // KV, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
        s = jnp.where(causal, s / math.sqrt(hd), -jnp.inf)
        a = jax.nn.softmax(s, axis=-1).astype(dtype)
        o = jnp.einsum("bhqk,bkhd->bqhd", a, v).reshape(B, S, H * hd)
        x = x + (o @ c(p + "wo")) * jnp.asarray(dm["residual"], dtype)
        h = _rms_norm(x, w[p + "mlp_norm"], dm["eps"])
        m = jax.nn.silu(h @ c(p + "w_gate")) * (h @ c(p + "w_up"))
        x = x + (m @ c(p + "w_down")) * jnp.asarray(dm["residual"], dtype)
    x = _rms_norm(x, w["final_norm"], dm["eps"])
    head = emb[: dm["vocab"]]
    logits = (x @ head.T).astype(jnp.float32) / dm["logit_div"]
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    gold = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return -jnp.mean(gold)


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


def run_steps(cfg: dict, make_w0: Callable[[], Dict[str, jax.Array]],
              batches: List[np.ndarray], dtype=jnp.float32
              ) -> Tuple[List[float], Dict[str, float], Dict[str, float]]:
    """Train ``len(batches)`` steps from the weights ``make_w0()`` gives.

    Returns the loss of each step, the norm of each leaf of the first
    clipped gradient, and the norm of each leaf's change over all steps.
    ``make_w0`` is called twice, so that the starting weights need not be
    held beside the optimizer's state.
    """
    dm, tr = dims(cfg), cfg["training"]
    precision = "highest" if dtype == jnp.float32 else "default"
    grad = jax.jit(jax.value_and_grad(
        lambda w, t: loss_fn(dm, w, t, dtype)))

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
