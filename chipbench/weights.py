"""The seed's key and token stream, and the norms and fingerprints of
parameter trees, for every architecture.

The benchmark makes both the weights (each architecture's
``program_weights``, from ``seed_key``) and the token stream, so that the
reference can make the same ones again without taking anything from the
program.
"""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int) -> jax.Array:
    """A threefry key that keeps all 64 bits of ``seed``."""
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed must be in [0, 2**64), got {seed}")
    return jax.random.wrap_key_data(
        jnp.asarray(np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32)))


def norms_by_slice(whole: Dict[str, jax.Array],
                   stacked: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    """Norm of each whole leaf, and of each layer's slice of each stacked
    leaf, the slice ``l`` of ``name`` keyed ``layers.<l>.<name>``: the
    reference's leaves, from an architecture's ``slices``."""
    out = {k: jnp.linalg.norm(x.ravel()) for k, x in whole.items()}
    for name, x in stacked.items():
        per = jnp.sqrt(jnp.sum(jnp.square(x.reshape(x.shape[0], -1)), axis=1))
        for l in range(x.shape[0]):
            out[f"layers.{l}.{name}"] = per[l]
    return out


slice_norms = jax.jit(norms_by_slice)


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
