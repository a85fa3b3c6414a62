"""MiniCPM: its dimensions, weights, plain reference, FLOP count, program
fields and smoke cut.

The layer equations follow the published MiniCPM (llama-like block,
``scale_emb`` on the embedding, residual branches scaled by
``scale_depth / sqrt(num_hidden_layers)``, tied head divided by
``hidden_size / dim_model_base``), with the file's ``rms_norm_eps``.  The
softmax and the loss span the ``vocab_size`` rows of the vocabulary and no
more: rows the program pads its table with (``as_run.padded_vocab_rows``)
are in the weights but never among the classes.  The norm's scale is stored
as ``1 + w``, as ``as_run`` says: the published norm with its weight
starting at 1, whose offset ``w`` AdamW decays.

Weights are made in float32 (the type they are trained in) directly in the
program's layout: the layers stacked on a leading axis under
``layers/p0/{mixer,ffn}``, as ``repro.models.lm`` scans them.
"""
from __future__ import annotations

import copy
import dataclasses
import math
from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import weights as W

# Program leaf (under "layers"/"p0") -> reference name of each layer's slice.
_LAYER_LEAVES = {
    ("mixer", "ln"): "attn_norm",
    ("mixer", "wq"): "wq",
    ("mixer", "wk"): "wk",
    ("mixer", "wv"): "wv",
    ("mixer", "wo"): "wo",
    ("ffn", "ln"): "mlp_norm",
    ("ffn", "w_gate"): "w_gate",
    ("ffn", "w_up"): "w_up",
    ("ffn", "w_down"): "w_down",
}


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


# -- weights ------------------------------------------------------------------
def leaf_shapes(dm: dict) -> Dict[str, Tuple[Tuple[int, ...], float]]:
    """Reference name -> (shape of one layer's slice, init stddev; 0 = zeros)."""
    d, H, KV, hd, F = dm["d"], dm["heads"], dm["kv_heads"], dm["hd"], dm["ffn"]
    out = {"embed": ((dm["vocab_rows"], d), 0.02), "final_norm": ((d,), 0.0)}
    per_layer = {
        "attn_norm": ((d,), 0.0), "wq": ((d, H * hd), d ** -0.5),
        "wk": ((d, KV * hd), d ** -0.5), "wv": ((d, KV * hd), d ** -0.5),
        "wo": ((H * hd, d), (H * hd) ** -0.5), "mlp_norm": ((d,), 0.0),
        "w_gate": ((d, F), d ** -0.5), "w_up": ((d, F), d ** -0.5),
        "w_down": ((F, d), F ** -0.5)}
    for l in range(dm["layers"]):
        for name, spec in per_layer.items():
            out[f"layers.{l}.{name}"] = spec
    return out


def _make(dm: dict, seed_data: jax.Array):
    key = jax.random.wrap_key_data(seed_data)
    made = {}
    for i, (name, (shape, std)) in enumerate(sorted(leaf_shapes(dm).items())):
        if std == 0.0:
            made[name] = jnp.zeros(shape, jnp.float32)
        else:
            made[name] = std * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32)
    return made


def _to_program(dm: dict, ref: Dict[str, jax.Array]):
    layers: Dict[str, Dict[str, jax.Array]] = {"mixer": {}, "ffn": {}}
    for (group, leaf), name in _LAYER_LEAVES.items():
        layers[group][leaf] = jnp.stack(
            [ref[f"layers.{l}.{name}"] for l in range(dm["layers"])])
    return {"embed": ref["embed"], "final_ln": ref["final_norm"],
            "layers": {"p0": layers}}


@partial(jax.jit, static_argnums=0)
def _program_weights(dm_items: tuple, seed_data: jax.Array):
    dm = dict(dm_items)
    return _to_program(dm, _make(dm, seed_data))


def program_weights(dm: dict, seed: int):
    """The program's parameter tree for ``seed``, made in one jitted call."""
    return _program_weights(tuple(sorted(dm.items())),
                            jax.random.key_data(W.seed_key(seed)))


def slices(tree):
    """(whole leaves, layer-stacked leaves) of a program-layout tree, keyed
    by the reference's names."""
    whole = {"embed": tree["embed"], "final_norm": tree["final_ln"]}
    stacked = {name: tree["layers"]["p0"][group][leaf]
               for (group, leaf), name in _LAYER_LEAVES.items()}
    return whole, stacked


def reference_view(dm: dict, tree) -> Dict[str, jax.Array]:
    """Slice a tree in the program's layout into the reference's names."""
    whole, stacked = slices(tree)
    out = dict(whole)
    for name, x in stacked.items():
        for l in range(dm["layers"]):
            out[f"layers.{l}.{name}"] = x[l]
    return out


@partial(jax.jit, static_argnums=0)
def _change_norms(dm_items: tuple, tree, seed_data):
    dm = dict(dm_items)
    start = _to_program(dm, _make(dm, seed_data))
    return W.norms_by_slice(*slices(jax.tree_util.tree_map(
        lambda a, b: a - b, tree, start)))


def change_norms(dm: dict, tree, seed: int) -> Dict[str, jax.Array]:
    """Norms of ``tree`` minus the weights ``seed`` started from, per slice."""
    return _change_norms(tuple(sorted(dm.items())), tree,
                         jax.random.key_data(W.seed_key(seed)))


# -- the plain reference ------------------------------------------------------
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


# -- the FLOP count -----------------------------------------------------------
def matmul_params(dm: dict) -> int:
    """Parameters of the matmuls: the tied head once, over the configured
    vocabulary (rows the program pads its table with are not work the model
    requires), and each layer's q, k, v, o and three FFN matrices."""
    d, H, KV, hd, F = dm["d"], dm["heads"], dm["kv_heads"], dm["hd"], dm["ffn"]
    attn = d * H * hd + 2 * d * KV * hd + H * hd * d
    mlp = 3 * d * F
    return dm["vocab"] * d + dm["layers"] * (attn + mlp)


def train_step_flops(dm: dict, batch: int, seq_len: int) -> int:
    """Model FLOPs of one step: 6 per matmul parameter per token (2 forward,
    4 backward), plus 12 * layers * seq_len * (heads * head_dim) per token
    for the attention scores and their weighting, forward and backward.
    Norms, rotary embedding, softmax, the optimizer and recomputation are
    left out."""
    tokens = batch * seq_len
    attention = 12 * dm["layers"] * seq_len * dm["heads"] * dm["hd"]
    return tokens * (6 * matmul_params(dm) + attention)


# -- the program's side -------------------------------------------------------
def program_fields(cfg: dict, dm: dict) -> dict:
    """The program's ``ModelConfig`` fields the configuration states."""
    return dict(d_model=dm["d"], n_heads=dm["heads"],
                n_kv_heads=dm["kv_heads"], hd=dm["hd"], d_ff=dm["ffn"],
                n_layers=dm["layers"],
                vocab_size=dm["vocab"], padded_vocab=dm["vocab_rows"],
                norm_eps=dm["eps"], rope_theta=dm["theta"],
                embed_scale=dm["scale_emb"], residual_scale=dm["residual"],
                logit_divisor=dm["logit_div"],
                tie_embeddings=cfg["tie_word_embeddings"],
                pattern=("attn",), n_experts=0, qk_norm=False,
                post_norm=False, attn_softcap=0.0, final_softcap=0.0)


def configured(model_config, dm: dict):
    """``model_config`` with the norm's eps and the vocabulary set as the
    configuration states, through the program's own ``ModelConfig`` fields
    (its MiniCPM entry leaves them at 1e-6 and 122,753)."""
    def config(run):
        return dataclasses.replace(model_config(run), norm_eps=dm["eps"],
                                   vocab_size=dm["vocab"])
    return config


def smoke(cfg: dict) -> dict:
    """The program's own reduced MiniCPM (``RunConfig(use_smoke=True)``):
    widths 64/4/2/16/128, vocabulary 512, 2 layers, with the published
    scalars (``scale_emb``, the residual scale of 40 layers, logits divided
    by 9)."""
    cfg = copy.deepcopy(cfg)
    cfg.update(hidden_size=64, intermediate_size=128, num_attention_heads=4,
               num_key_value_heads=2, head_dim=16, num_hidden_layers=2,
               vocab_size=512, dim_model_base=64 / 9)
    cfg["as_run"]["padded_vocab_rows"] = 512
    cfg["program"] = {"arch": "minicpm-2b", "use_smoke": True,
                      "n_layers": None}
    return cfg
