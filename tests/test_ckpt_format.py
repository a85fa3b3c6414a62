"""The checkpoint shard codec (``CKS1``) and the stores that write it.

``pack_tree`` lays a host's leaves out as a header and raw C-order bytes,
handed to the stores in pieces that share memory with the leaves;
``unpack_tree`` reads them back as views and checks each leaf's CRC32.
Payloads in the ``np.savez`` container written before ``CKS1`` still
restore.
"""
import io
import os
import threading

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from repro import obs
from repro.ckpt import (CornusCheckpointer, latest_committed, pack_tree,
                        partition_leaves, restore_params, to_host,
                        unpack_tree)
from repro.ckpt.commit import AsyncCheckpointer, _txn
from repro.ckpt.restore import fetch_payloads
from repro.ckpt.shards import Payload
from repro.core.state import Decision
from repro.core.storage import FileStore, MemoryStore, ReplicatedStore

RNG = np.random.RandomState(0)
CASES = {
    "float32": {"w": RNG.randn(64, 16).astype(np.float32)},
    "bfloat16": {"w": RNG.randn(8, 24).astype(ml_dtypes.bfloat16)},
    "int32": {"i": RNG.randint(-2**31, 2**31 - 1, (5, 7), dtype=np.int32)},
    "uint8": {"b": RNG.randint(0, 256, 99, dtype=np.uint8)},
    "0-d": {"s": np.asarray(2.5, np.float32), "n": np.asarray(7, np.int32)},
    "empty": {"e": np.zeros((0, 3), np.float32), "f": np.ones(4, np.float32)},
    "transposed": {"t": RNG.randn(12, 5).astype(np.float32).T},
    "big-endian": {"f": RNG.randn(6).astype(">f4")},
    "keys subset": {"a": RNG.randn(3, 3).astype(np.float32),
                    "b": RNG.randn(10).astype(np.float32),
                    "c": RNG.randn(2, 2, 2).astype(np.float32)},
}


def _bits(a):
    return np.ascontiguousarray(a).reshape(-1).view(np.uint8)


def _commit(store, hosts, epoch, payloads):
    for h in hosts:
        CornusCheckpointer(store, h, hosts).vote(epoch, payloads[h])
    assert latest_committed(store, hosts) == epoch


@pytest.mark.parametrize("case", sorted(CASES))
def test_roundtrip(case):
    tree = CASES[case]
    keys = ["c", "a"] if case == "keys subset" else None
    payload = pack_tree(tree, keys)
    flat = unpack_tree(bytes(payload))
    assert list(flat) == (keys or sorted(tree))  # jax sorts dict keys
    for k, got in flat.items():
        want = tree[k]
        assert got.dtype == want.dtype.newbyteorder("=")
        assert got.shape == want.shape
        np.testing.assert_array_equal(got, want)
        np.testing.assert_array_equal(_bits(got),
                                      _bits(want.astype(got.dtype)))
    assert unpack_tree(payload).keys() == flat.keys()


def test_pack_shares_memory_with_contiguous_leaves():
    tree = {"a": np.arange(1000, dtype=np.float32),
            "b": np.ones((20, 30), np.float32)}
    payload = pack_tree(tree)
    assert isinstance(payload, Payload) and payload.copied == 0
    for leaf in tree.values():
        assert any(np.shares_memory(leaf, piece) for piece in payload)
    assert all(not getattr(p, "flags", None) or not p.flags.writeable
               for p in payload)
    assert len(payload) == len(bytes(payload))
    # A leaf that is not contiguous is copied once, and counted.
    strided = {"t": np.ones((30, 20), np.float32).T}
    assert pack_tree(strided).copied == strided["t"].nbytes


def test_leaves_align_to_64_bytes():
    tree = {"a": np.arange(3, dtype=np.uint8),
            "b": np.arange(5, dtype=np.float32)}
    payload = bytes(pack_tree(tree))
    flat = unpack_tree(payload)
    base = np.frombuffer(payload, np.uint8).__array_interface__["data"][0]
    for leaf in flat.values():
        assert (leaf.__array_interface__["data"][0] - base) % 64 == 0


def test_pack_span_reports_no_copy(tmp_path):
    from repro.launch.train import RunConfig, _checkpoint

    hosts = ["h0", "h1"]
    store = FileStore(str(tmp_path))
    params = {"w": jnp.ones((16, 8)), "b": jnp.zeros((8,))}
    opt = {"m": jax.tree_util.tree_map(jnp.zeros_like, params),
           "v": jax.tree_util.tree_map(jnp.ones_like, params)}
    cks = {h: CornusCheckpointer(store, h, hosts) for h in hosts}
    out = _checkpoint(RunConfig(), None, params, opt, 3, hosts, cks, None)
    assert out.decision == Decision.COMMIT
    packs = obs.records("pack")[-2:]
    assert [r.attrs["host"] for r in packs] == hosts
    assert all(r.attrs["copied"] == 0 and r.attrs["bytes"] > 0
               for r in packs)


def test_flipped_byte_in_stored_payload_is_detected(tmp_path):
    store = FileStore(str(tmp_path))
    tree = {"w": np.arange(256, dtype=np.float32)}
    _commit(store, ["h0"], 1, {"h0": pack_tree(tree)})
    path = store.data_path("h0", _txn(1))
    with open(path, "r+b") as f:
        f.seek(os.path.getsize(path) - 17)
        byte = f.read(1)
        f.seek(-1, os.SEEK_CUR)
        f.write(bytes([byte[0] ^ 0x04]))
    with pytest.raises(ValueError, match="'w' is corrupt"):
        unpack_tree(store.get_data("h0", _txn(1)))
    with pytest.raises(ValueError, match="corrupt"):
        restore_params(store, ["h0"], 1, {"w": jnp.zeros(256)})


def test_truncated_payload_is_detected():
    payload = bytes(pack_tree({"w": np.arange(64, dtype=np.float32)}))
    with pytest.raises(ValueError, match="corrupt"):
        unpack_tree(payload[:-8])
    with pytest.raises(ValueError, match="magic"):
        unpack_tree(b"junk" + payload[4:])


def test_npz_payload_from_before_cks1_restores(tmp_path):
    """A checkpoint written as ``np.savez`` into memory still restores."""
    store = FileStore(str(tmp_path))
    hosts = ["h0", "h1"]
    tree = {"embed": RNG.randn(8, 4).astype(np.float32),
            "ln": RNG.randn(4).astype(np.float32),
            "step": np.asarray(5, np.int32)}
    payloads = {}
    for h, keys in zip(hosts, partition_leaves(tree, len(hosts))):
        buf = io.BytesIO()
        np.savez(buf, **{k: tree[k] for k in keys})
        payloads[h] = buf.getvalue()
    _commit(store, hosts, 4, payloads)
    template = jax.tree_util.tree_map(jnp.zeros_like, tree)
    got = restore_params(store, hosts, 4, template)
    for k in tree:
        np.testing.assert_array_equal(np.asarray(got[k]), tree[k])


# ---------------------------------------------------------------------------
# Stores
# ---------------------------------------------------------------------------
def test_filestore_writes_pieces_as_their_join(tmp_path):
    store = FileStore(str(tmp_path))
    payload = pack_tree(CASES["keys subset"])
    store.put_data("h0", "p", payload)
    assert store.get_data("h0", "p") == bytes(payload)
    store.put_data("h0", "q", b"plain bytes")
    assert store.get_data("h0", "q") == b"plain bytes"
    assert not [n for n in os.listdir(os.path.join(str(tmp_path), "data",
                                                   "h0")) if ".tmp" in n]


@pytest.mark.parametrize("make", [MemoryStore,
                                  lambda: ReplicatedStore(n_replicas=3)],
                         ids=["memory", "replicated"])
def test_stores_accept_a_payload_in_pieces(make):
    store = make()
    payload = pack_tree(CASES["bfloat16"])
    store.put_data("h0", "p", payload)
    assert store.get_data("h0", "p") == bytes(payload)


def test_erasure_coded_payload_in_pieces_restores():
    store = ReplicatedStore(n_replicas=5)
    hosts = ["h0", "h1"]
    tree = {"w": RNG.randn(33, 7).astype(np.float32),
            "b": RNG.randn(9).astype(np.float32)}
    payloads = {h: pack_tree(tree, keys)
                for h, keys in zip(hosts, partition_leaves(tree, 2))}
    for h in hosts:
        CornusCheckpointer(store, h, hosts, ec_k=2).vote(6, payloads[h])
    for i in (0, 3, 4):
        store.replicas[i].drop_data()
    got = fetch_payloads(store, hosts, 6)
    assert got == {h: bytes(p) for h, p in payloads.items()}
    flat = {}
    for p in got.values():
        flat.update(unpack_tree(p))
    for k in tree:
        np.testing.assert_array_equal(flat[k], tree[k])


def test_async_save_of_pulled_leaves_is_exact_while_training(tmp_path):
    """The payload references the pulled host arrays, not a copy: the
    restored state is the saved one, bit for bit, though training goes on
    with donated buffers while the save thread writes."""
    store = FileStore(str(tmp_path))
    step = jax.jit(lambda t: jax.tree_util.tree_map(lambda x: x * 1.5 + 1, t),
                   donate_argnums=0)
    state = {"w": jnp.arange(4096.0).reshape(64, 64), "b": jnp.ones(512)}
    state = step(state)
    host = to_host(state)
    saved = {k: np.array(v, copy=True) for k, v in host.items()}
    ck = AsyncCheckpointer(CornusCheckpointer(store, "h0", ["h0"]))
    ck.save(2, pack_tree(host))
    del host
    done = threading.Event()
    threading.Thread(target=lambda: (ck.join(), done.set()),
                     daemon=True).start()
    while not done.is_set():
        state = step(state)
    jax.block_until_ready(state)
    assert ck.join()[-1].decision == Decision.COMMIT
    got = restore_params(store, ["h0"], 2,
                         jax.tree_util.tree_map(jnp.zeros_like, state))
    for k in saved:
        np.testing.assert_array_equal(np.asarray(got[k]).view(np.uint32),
                                      saved[k].view(np.uint32))
