"""End-to-end trainer tests: loss goes down, checkpoints commit, a mid-epoch
crash restarts EXACTLY (the Cornus restore + stateless pipeline combination),
and elastic restarts onto different fleet sizes work.
"""
import os

import numpy as np
import pytest

from repro.ckpt import latest_committed
from repro.core.state import Decision
from repro.core.storage import FileStore
from repro.launch.train import (MidCheckpointCrash, RunConfig, RunResult,
                                train, _hosts)

# Real multi-step training runs — minutes of CPU per test.
pytestmark = pytest.mark.slow


def base_run(tmp, **kw):
    d = dict(arch="llama3.2-1b", steps=24, batch=4, seq_len=64,
             ckpt_every=8, ckpt_dir=str(tmp), n_hosts=3, log_every=0,
             lr=3e-3, seed=7)
    d.update(kw)
    return RunConfig(**d)


def test_loss_decreases_and_ckpts_commit(tmp_path):
    # 32 steps (not 24) and wide 8-step averaging windows: at 24 steps the
    # loss plateaus for some seeds (warmup covers 20 of them, so barely 4
    # run at full lr) and the 4-step window verdict flips seed-dependently.
    # 12 full-lr steps + 8-step windows give a stable margin.
    res = train(base_run(tmp_path, steps=32))
    assert res.steps_done == 32
    first = np.mean(res.losses[:8])
    last = np.mean(res.losses[-8:])
    assert last < first, f"no learning: {first} -> {last}"
    assert len(res.ckpt_outcomes) == 4
    assert all(o.decision == Decision.COMMIT for o in res.ckpt_outcomes)
    store = FileStore(str(tmp_path))
    assert latest_committed(store, _hosts(3)) == 32


def test_crash_restart_is_exact(tmp_path):
    """Kill mid-checkpoint at step 16; restart must resolve the in-flight
    epoch (force-abort), restore epoch 8, and REPRODUCE the uncrashed loss
    curve exactly — checkpoint+data determinism end-to-end."""
    golden = train(base_run(tmp_path / "golden"))

    with pytest.raises(MidCheckpointCrash):
        train(base_run(tmp_path / "crash", die_mid_checkpoint_at=16))
    store = FileStore(str(tmp_path / "crash"))
    # In-flight epoch 16 resolves to ABORT; epoch 8 is the restore point.
    assert latest_committed(store, _hosts(3)) == 8

    resumed = train(base_run(tmp_path / "crash", resume=True))
    assert resumed.restored_from == 8
    # Steps 8..24 must match the golden run bit-for-bit (same data, same
    # restored state). Compare the overlapping region.
    np.testing.assert_allclose(resumed.losses, golden.losses[8:], rtol=1e-5)


def test_elastic_restart_smaller_fleet(tmp_path):
    train(base_run(tmp_path, steps=8, ckpt_every=8, n_hosts=4))
    res = train(base_run(tmp_path, steps=16, ckpt_every=8, n_hosts=2,
                         resume=True))
    # restore read the 4-host epoch, then the 2-host fleet kept going
    assert res.restored_from == 8
    assert res.steps_done == 16
    store = FileStore(str(tmp_path))
    assert latest_committed(store, _hosts(2)) == 16


def test_async_checkpoint_commits(tmp_path):
    res = train(base_run(tmp_path, async_ckpt=True))
    assert res.ckpt_outcomes and all(
        o.decision == Decision.COMMIT for o in res.ckpt_outcomes)


def test_async_checkpoint_restores_saved_state_bit_for_bit(tmp_path,
                                                           monkeypatch):
    """The async save's payload references the pulled host arrays, not a
    copy of them, while training goes on with donated buffers: each stored
    payload is byte for byte what was packed, and the restore gives back
    the packed state bit for bit."""
    import jax
    import repro.launch.train as T
    from repro.ckpt import fetch_payloads, restore_params, unpack_tree
    from repro.ckpt.shards import _flatten

    pack, packed = T.pack_tree, []

    def pack_and_keep(tree, keys=None):
        payload = pack(tree, keys)
        packed.append(bytes(payload))
        return payload

    monkeypatch.setattr(T, "pack_tree", pack_and_keep)
    run = base_run(tmp_path, async_ckpt=True)
    res = train(run)
    hosts = _hosts(run.n_hosts)
    assert sorted(o.epoch for o in res.ckpt_outcomes) == \
        [e for e in (8, 16, 24) for _ in hosts]
    assert all(o.decision == Decision.COMMIT for o in res.ckpt_outcomes)
    store = FileStore(str(tmp_path))
    stored = [fetch_payloads(store, hosts, e)[h]
              for e in (8, 16, 24) for h in hosts]
    assert stored == packed

    want = {}
    for payload in packed[-len(hosts):]:
        want.update(unpack_tree(payload))
    params = T.lm.init_model(T.model_config(run), jax.random.key(0))
    got = _flatten(restore_params(
        store, hosts, 24, {"params": params, "opt": {"m": params,
                                                     "v": params}}))
    assert got.keys() == want.keys()
    for key, leaf in got.items():
        np.testing.assert_array_equal(leaf.reshape(-1).view(np.uint8),
                                      want[key].reshape(-1).view(np.uint8))


def test_byte_corpus_training(tmp_path):
    """Train on real bytes (this test file) — loss must drop fast on code."""
    src = os.path.abspath(__file__)
    res = train(base_run(tmp_path, data_source=f"bytes:{src}", steps=30,
                         ckpt_every=30))
    assert np.mean(res.losses[-5:]) < np.mean(res.losses[:5])
