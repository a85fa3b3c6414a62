"""Shard (de)serialization: pytree leaves ↔ bytes, and host partitioning.

Format: npz of path-keyed arrays (fast, dependency-free, self-describing).
``to_host`` pulls a tree's device leaves to the host once per save; the
save then hands that host tree to ``partition_leaves`` and ``pack_tree``.
``partition_leaves`` deterministically assigns leaf paths to hosts by a
size-balanced greedy rule, so a restore can reassemble the full tree from
any historical host count — this is what makes restarts *elastic*.

Also home to the k-of-n erasure codec (``ec_encode`` / ``ec_decode``): a
Reed-Solomon-lite code over GF(256) with a Vandermonde generator matrix,
numpy-only.  A checkpoint payload split into ``k`` data stripes becomes
``n`` fragments — one per replica volume — any ``k`` of which reconstruct
the payload.  With (k=2, n=5) a restore needs just TWO surviving volumes
(a *minority*) at 2.5× storage instead of the 5× of full replication.
Fragments carry a self-describing header (k, n, index, payload length),
so a restore can decode from whatever subset survived without any
out-of-band metadata.
"""
from __future__ import annotations

import io
import struct
from typing import Dict, List, Sequence, Tuple

import numpy as np


def _flatten(tree) -> Dict[str, np.ndarray]:
    import jax  # lazy: the EC codec below is numpy-only
    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                       for p in path)
        flat[key] = np.asarray(leaf)
    return flat


def to_host(tree):
    """The same tree with every leaf copied to the host: ``np.asarray``
    leaf by leaf, in the tree's order."""
    import jax
    return jax.tree_util.tree_map(np.asarray, tree)


def pack_tree(tree, keys: Sequence[str] | None = None) -> bytes:
    """Serialize (a subset of) a pytree's leaves."""
    flat = _flatten(tree)
    if keys is not None:
        flat = {k: flat[k] for k in keys}
    buf = io.BytesIO()
    np.savez(buf, **flat)
    return buf.getvalue()


def unpack_tree(payload: bytes) -> Dict[str, np.ndarray]:
    buf = io.BytesIO(payload)
    with np.load(buf) as z:
        return {k: z[k] for k in z.files}


def merge_into_tree(tree, flat: Dict[str, np.ndarray]):
    """Write flat path->array entries back into a template pytree."""
    import jax
    leaves, treedef = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for path, leaf in leaves:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                       for p in path)
        if key in flat:
            arr = flat[key]
            out.append(jax.numpy.asarray(arr, dtype=leaf.dtype))
        else:
            out.append(leaf)
    return jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(tree), out)


def partition_leaves(tree, n_hosts: int) -> List[List[str]]:
    """Deterministic size-balanced assignment of leaf paths to hosts."""
    flat = _flatten(tree)
    items = sorted(flat.items(), key=lambda kv: (-kv[1].nbytes, kv[0]))
    buckets: List[List[str]] = [[] for _ in range(n_hosts)]
    loads = [0] * n_hosts
    for key, arr in items:
        i = loads.index(min(loads))
        buckets[i].append(key)
        loads[i] += max(1, arr.nbytes)
    return buckets


# ---------------------------------------------------------------------------
# k-of-n erasure codec (Reed-Solomon-lite over GF(256), numpy-only)
# ---------------------------------------------------------------------------
# GF(2^8) with the AES reduction polynomial 0x11d; exp table doubled so a
# log-sum (max 508) indexes without a mod.
_GF_EXP = np.zeros(512, dtype=np.uint8)
_GF_LOG = np.zeros(256, dtype=np.int32)
_x = 1
for _i in range(255):
    _GF_EXP[_i] = _x
    _GF_LOG[_x] = _i
    _x <<= 1
    if _x & 0x100:
        _x ^= 0x11D
_GF_EXP[255:510] = _GF_EXP[:255]

# Full 256x256 product table: _GF_MUL[c] maps a byte vector through "*c"
# with one fancy-index — the whole codec is table lookups and XORs.
_GF_MUL = np.zeros((256, 256), dtype=np.uint8)
_nz = np.arange(1, 256)
for _c in range(1, 256):
    _GF_MUL[_c, 1:] = _GF_EXP[_GF_LOG[_c] + _GF_LOG[_nz]]


def _gf_mul(a: int, b: int) -> int:
    if a == 0 or b == 0:
        return 0
    return int(_GF_EXP[_GF_LOG[a] + _GF_LOG[b]])


def _gf_inv(a: int) -> int:
    if a == 0:
        raise ZeroDivisionError("GF(256) inverse of 0")
    return int(_GF_EXP[255 - _GF_LOG[a]])


# Fragment header: magic, k, n, fragment index, original payload length.
_EC_HEADER = struct.Struct(">4sBBBQ")
_EC_MAGIC = b"ECS1"


def ec_encode(payload: bytes, k: int, n: int) -> List[bytes]:
    """Encode ``payload`` into ``n`` fragments, any ``k`` of which decode.

    Fragment j is the GF(256) inner product of the k data stripes with the
    Vandermonde row (x_j^0 .. x_j^{k-1}), x_j = j+1: distinct nonzero
    evaluation points, so every k×k row subset is invertible.
    """
    if not 1 <= k <= n <= 255:
        raise ValueError(f"need 1 <= k <= n <= 255, got k={k} n={n}")
    data = np.frombuffer(payload, dtype=np.uint8)
    stripe = max(1, -(-len(data) // k))
    padded = np.zeros(k * stripe, dtype=np.uint8)
    padded[:len(data)] = data
    stripes = padded.reshape(k, stripe)
    frags: List[bytes] = []
    for j in range(n):
        x = j + 1
        acc = np.zeros(stripe, dtype=np.uint8)
        coeff = 1
        for i in range(k):
            acc ^= _GF_MUL[coeff][stripes[i]]
            coeff = _gf_mul(coeff, x)
        frags.append(_EC_HEADER.pack(_EC_MAGIC, k, n, j, len(payload))
                     + acc.tobytes())
    return frags


def ec_decode(fragments: Sequence[bytes]) -> bytes:
    """Reconstruct the payload from any >= k surviving fragments.

    Headers are self-describing; duplicates and fragments from a different
    (k, n) geometry are rejected.  Raises ``ValueError`` when fewer than k
    distinct fragments survive — the caller's signal that the epoch's data
    really is gone.
    """
    seen: Dict[int, np.ndarray] = {}
    geometry = None
    for frag in fragments:
        if len(frag) < _EC_HEADER.size:
            raise ValueError("truncated erasure fragment")
        magic, k, n, j, orig_len = _EC_HEADER.unpack(
            frag[:_EC_HEADER.size])
        if magic != _EC_MAGIC:
            raise ValueError(f"bad fragment magic {magic!r}")
        if geometry is None:
            geometry = (k, n, orig_len)
        elif geometry != (k, n, orig_len):
            raise ValueError(f"mixed fragment geometries: {geometry} "
                             f"vs {(k, n, orig_len)}")
        seen.setdefault(j, np.frombuffer(frag[_EC_HEADER.size:],
                                         dtype=np.uint8))
    if geometry is None:
        raise ValueError("no fragments")
    k, n, orig_len = geometry
    if len(seen) < k:
        raise ValueError(f"need {k} distinct fragments, "
                         f"have {len(seen)} of {n}")
    rows = sorted(seen.items())[:k]
    # Solve A·D = F by Gauss-Jordan over GF(256); row ops on the fragment
    # byte vectors ride the product table.
    A = [[pow_gf(j + 1, i) for i in range(k)] for j, _ in rows]
    F = np.stack([body.copy() for _, body in rows])
    for col in range(k):
        pivot = next(r for r in range(col, k) if A[r][col] != 0)
        A[col], A[pivot] = A[pivot], A[col]
        F[[col, pivot]] = F[[pivot, col]]
        inv = _gf_inv(A[col][col])
        A[col] = [_gf_mul(inv, v) for v in A[col]]
        F[col] = _GF_MUL[inv][F[col]]
        for r in range(k):
            f = A[r][col]
            if r == col or f == 0:
                continue
            A[r] = [a ^ _gf_mul(f, b) for a, b in zip(A[r], A[col])]
            F[r] ^= _GF_MUL[f][F[col]]
    return F.reshape(-1).tobytes()[:orig_len]


def pow_gf(x: int, e: int) -> int:
    """x**e in GF(256) (e >= 0)."""
    out = 1
    for _ in range(e):
        out = _gf_mul(out, x)
    return out
