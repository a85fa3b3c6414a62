"""Shard (de)serialization: pytree leaves ↔ bytes, and host partitioning.

Format (``CKS1``): the magic ``b"CKS1"``, the header's length as a
little-endian uint64, a JSON header listing each leaf in ``keys`` order
(key, dtype by name, shape, byte offset, byte length, CRC32), then, from
the next multiple of 64, each leaf's raw C-order bytes at its offset (a
multiple of 64 past that start), zero-padded between leaves.
``pack_tree`` returns it as a ``Payload`` held in pieces (the header, then
a read-only view of each leaf's own memory), so a save copies no
contiguous leaf: the stores write the pieces in order.  ``unpack_tree``
takes each leaf as a view of the payload and checks its CRC32; a payload
in the zip container of ``np.savez`` (checkpoints written before ``CKS1``)
still goes through ``np.load``.

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
import json
import struct
import zlib
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, Iterator, List, Sequence, Tuple

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


_MAGIC = b"CKS1"
_ZIP_MAGIC = b"PK\x03\x04"            # np.savez's container, before CKS1
_HEADER_LEN = struct.Struct("<Q")
_ALIGN = 64
# zlib.crc32 releases the GIL, so leaves are checksummed on this many
# threads at once.
_CRC_THREADS = 8


class Payload:
    """A packed payload held in pieces: the header, then each leaf's bytes
    with the zero padding between them.  ``len()`` is the byte count,
    ``bytes()`` the joined bytes; iterating gives the pieces in order.
    ``copied`` counts the leaf bytes that had to be made contiguous."""

    __slots__ = ("pieces", "nbytes", "copied")

    def __init__(self, pieces: List, copied: int):
        self.pieces = pieces
        self.nbytes = sum(len(p) for p in pieces)
        self.copied = copied

    def __len__(self) -> int:
        return self.nbytes

    def __iter__(self) -> Iterator:
        return iter(self.pieces)

    def __bytes__(self) -> bytes:
        return b"".join(self.pieces)


def _crcs(buffers: Sequence) -> List[int]:
    with ThreadPoolExecutor(_CRC_THREADS) as ex:
        return list(ex.map(zlib.crc32, buffers))


def _dtype(name: str) -> np.dtype:
    try:
        return np.dtype(name)
    except TypeError:  # bfloat16 and the other ml_dtypes types
        import ml_dtypes
        return np.dtype(getattr(ml_dtypes, name))


def _aligned(n: int) -> int:
    return -(-n // _ALIGN) * _ALIGN


def pack_tree(tree, keys: Sequence[str] | None = None) -> Payload:
    """Serialize (a subset of) a pytree's leaves in the ``CKS1`` format."""
    flat = _flatten(tree)
    if keys is None:
        keys = list(flat)
    views, copied = [], 0
    for k in keys:
        arr = flat[k]
        if not (arr.flags.c_contiguous and arr.dtype.isnative):
            arr = np.ascontiguousarray(
                arr, dtype=arr.dtype.newbyteorder("="))
            copied += arr.nbytes
        view = arr.reshape(-1).view(np.uint8)
        view.flags.writeable = False
        views.append(view)
    entries, offset = [], 0
    for k, view, crc in zip(keys, views, _crcs(views)):
        entries.append(dict(key=k, dtype=flat[k].dtype.name,
                            shape=list(flat[k].shape), offset=offset,
                            nbytes=len(view), crc32=crc))
        offset = _aligned(offset + len(view))
    header = json.dumps({"leaves": entries}).encode()
    head = _MAGIC + _HEADER_LEN.pack(len(header)) + header
    pieces: List = [head + bytes(_aligned(len(head)) - len(head))]
    end = 0
    for e, view in zip(entries, views):
        pieces += [bytes(e["offset"] - end), view]
        end = e["offset"] + e["nbytes"]
    return Payload(pieces, copied)


def unpack_tree(payload: bytes) -> Dict[str, np.ndarray]:
    """The path-keyed leaves of a packed payload, as read-only views of it.
    Raises ``ValueError`` when a leaf's bytes fail their CRC32."""
    if isinstance(payload, Payload):
        payload = bytes(payload)
    magic = payload[:4]
    if magic == _ZIP_MAGIC:
        with np.load(io.BytesIO(payload)) as z:
            return {k: z[k] for k in z.files}
    if magic != _MAGIC:
        raise ValueError(f"not a checkpoint payload: magic {magic!r}")
    start = len(_MAGIC) + _HEADER_LEN.size
    (n,) = _HEADER_LEN.unpack_from(payload, len(_MAGIC))
    entries = json.loads(payload[start:start + n])["leaves"]
    mem = memoryview(payload)[_aligned(start + n):]
    spans = [mem[e["offset"]:e["offset"] + e["nbytes"]] for e in entries]
    out = {}
    for e, span, crc in zip(entries, spans, _crcs(spans)):
        if len(span) != e["nbytes"] or crc != e["crc32"]:
            raise ValueError(
                f"checkpoint leaf {e['key']!r} is corrupt: CRC32 "
                f"{crc:#010x} over {len(span)} bytes, header says "
                f"{e['crc32']:#010x} over {e['nbytes']}")
        dtype = _dtype(e["dtype"])
        out[e["key"]] = np.frombuffer(
            span, dtype, e["nbytes"] // dtype.itemsize).reshape(e["shape"])
    return out


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
    A ``Payload`` is joined first.

    Fragment j is the GF(256) inner product of the k data stripes with the
    Vandermonde row (x_j^0 .. x_j^{k-1}), x_j = j+1: distinct nonzero
    evaluation points, so every k×k row subset is invertible.
    """
    if not 1 <= k <= n <= 255:
        raise ValueError(f"need 1 <= k <= n <= 255, got k={k} n={n}")
    if isinstance(payload, Payload):
        payload = bytes(payload)
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
