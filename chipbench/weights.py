"""Weights and tokens made from the seed, and the program's parameter layout.

The benchmark makes both the weights and the token stream, so that the
reference can make the same ones again without taking anything from the
program.  Weights are made on the device in one jitted call, in float32 (the
type they are trained in), directly in the program's layout: the layers of
each kind stacked on a leading axis, as ``repro.models.lm`` scans them.
``reference_view`` names each layer's slice as the reference does.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

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


def seed_key(seed: int) -> jax.Array:
    """A threefry key that keeps all 64 bits of ``seed``."""
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed must be in [0, 2**64), got {seed}")
    return jax.random.wrap_key_data(
        jnp.asarray(np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32)))


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
                            jax.random.key_data(seed_key(seed)))


def reference_view(dm: dict, tree) -> Dict[str, jax.Array]:
    """Slice a tree in the program's layout into the reference's names."""
    out = {"embed": tree["embed"], "final_norm": tree["final_ln"]}
    for (group, leaf), name in _LAYER_LEAVES.items():
        stacked = tree["layers"]["p0"][group][leaf]
        for l in range(dm["layers"]):
            out[f"layers.{l}.{name}"] = stacked[l]
    return out


def _slice_norms(tree) -> Dict[str, jax.Array]:
    """Norm of each leaf of a program-layout tree, per layer where stacked,
    keyed by the reference's names."""
    out = {"embed": jnp.linalg.norm(tree["embed"].ravel()),
           "final_norm": jnp.linalg.norm(tree["final_ln"].ravel())}
    for (group, leaf), name in _LAYER_LEAVES.items():
        stacked = tree["layers"]["p0"][group][leaf]
        per = jnp.sqrt(jnp.sum(jnp.square(stacked.reshape(
            stacked.shape[0], -1)), axis=1))
        for l in range(stacked.shape[0]):
            out[f"layers.{l}.{name}"] = per[l]
    return out


slice_norms = jax.jit(_slice_norms)


@partial(jax.jit, static_argnums=0)
def _change_norms(dm_items: tuple, tree, seed_data):
    dm = dict(dm_items)
    start = _to_program(dm, _make(dm, seed_data))
    return _slice_norms(jax.tree_util.tree_map(
        lambda a, b: a - b, tree, start))


def change_norms(dm: dict, tree, seed: int) -> Dict[str, jax.Array]:
    """Norms of ``tree`` minus the weights ``seed`` started from, per slice."""
    return _change_norms(tuple(sorted(dm.items())), tree,
                         jax.random.key_data(seed_key(seed)))


@jax.jit
def fingerprint(tree) -> List[jax.Array]:
    """Per leaf, the sum of its float32 bit patterns modulo 2**32: equal
    fingerprints for a saved and a restored tree mean equal bits, up to
    flips that cancel."""
    return [jnp.sum(jax.lax.bitcast_convert_type(x, jnp.uint32),
                    dtype=jnp.uint32)
            for x in jax.tree_util.tree_leaves(tree)]


class SeededTokens:
    """Token ids (B, S) for each step, a pure function of (seed, step).

    Ids follow a zipf-like unigram law, p(i) proportional to 1/(i + offset),
    over the configured vocabulary, so that the loss has something to learn
    from the first steps on.  Every step draws fresh rows.
    """

    def __init__(self, vocab: int, batch: int, seq_len: int, seed: int,
                 offset: float):
        p = 1.0 / (np.arange(vocab, dtype=np.float64) + offset)
        self._cdf = np.cumsum(p / p.sum())
        self.vocab, self.batch, self.seq_len, self.seed = \
            vocab, batch, seq_len, seed

    def tokens(self, step: int) -> np.ndarray:
        rng = np.random.Generator(np.random.Philox(
            key=self.seed, counter=[0, 0, 0, step]))
        u = rng.random(self.batch * self.seq_len)
        ids = np.minimum(np.searchsorted(self._cdf, u, side="right"),
                         self.vocab - 1)
        return ids.astype(np.int32).reshape(self.batch, self.seq_len)

    def batch_at(self, step: int) -> Dict[str, np.ndarray]:
        toks = self.tokens(step)
        return {"tokens": toks, "labels": toks.copy()}
