"""The program's own spans (``repro.obs``): nesting, failure, the ring's
bound, threads, and the spans a smoke-size ``train()`` writes around its
steps, its saves and its restore."""
import dataclasses
import threading

import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.ckpt import CornusCheckpointer
from repro.ckpt.commit import AsyncCheckpointer, _txn
from repro.core.state import Decision
from repro.core.storage import FileStore, MemoryStore
from repro.launch import train as T
from repro.models import lm
from repro.optim import adamw_init


def _since(mark):
    return [r for r in obs.records() if r.id > mark.id]


@pytest.fixture
def mark():
    """A span closed just before the test: every record after it is the
    test's own."""
    with obs.span("mark") as sp:
        pass
    return sp


def _kids(recs, rec, name=None):
    return [r for r in recs if r.parent == rec.id
            and (name is None or r.name == name)]


def test_nesting_parents_attrs_and_duration(mark):
    with obs.span("outer", epoch=3) as outer:
        with obs.span("inner") as inner:
            inner.set(bytes=12)
        with obs.span("inner"):
            pass
    recs = _since(mark)
    assert [r.name for r in recs] == ["inner", "inner", "outer"]
    top = recs[-1]
    assert top.parent is None and top.attrs == {"epoch": 3} and top.ok
    assert [r.parent for r in recs[:2]] == [top.id, top.id]
    assert recs[0].attrs == {"bytes": 12}
    assert top.start_ns <= recs[0].start_ns <= recs[1].end_ns <= top.end_ns
    assert outer.ms == top.ms >= inner.ms >= 0
    assert [r.id for r in obs.children(top, "inner")] == \
        [r.id for r in recs[:2]]
    assert obs.children(top, "outer") == []


def test_a_raising_block_records_not_ok_and_reraises(mark):
    with pytest.raises(KeyError):
        with obs.span("outer"):
            with obs.span("inner"):
                raise KeyError("x")
    with obs.span("after"):
        pass
    recs = _since(mark)
    assert [(r.name, r.ok) for r in recs] == [
        ("inner", False), ("outer", False), ("after", True)]
    # The failed spans left the parent stack as they found it.
    assert recs[2].parent is None


def test_the_ring_keeps_the_newest_spans():
    for i in range(obs.MAX_RECORDS + 10):
        with obs.span("fill", i=i):
            pass
    recs = obs.records()
    assert len(recs) == obs.MAX_RECORDS
    assert recs[0].attrs == {"i": 10}
    assert recs[-1].attrs == {"i": obs.MAX_RECORDS + 9}


def test_each_thread_keeps_its_own_parents(mark):
    store = MemoryStore()
    ck = CornusCheckpointer(store, "h0", ["h0"])
    async_ck = AsyncCheckpointer(ck)
    with obs.span("caller"):
        async_ck.save(7, b"payload")
        outcomes = async_ck.join()
    assert outcomes[0].decision == Decision.COMMIT
    recs = _since(mark)
    (vote,) = [r for r in recs if r.name == "vote"]
    # The save ran on its own thread: its vote is no child of the caller.
    assert vote.parent is None
    assert vote.attrs == {"epoch": 7, "host": "h0"}
    (upload,) = _kids(recs, vote, "upload")
    assert upload.attrs == {"epoch": 7, "bytes": len(b"payload")}
    assert [r.attrs for r in _kids(recs, vote, "log_once")] == [{"epoch": 7}]
    (resolve,) = [r for r in recs if r.name == "resolve"]
    assert resolve.parent is None
    assert outcomes[0].vote_ms == vote.ms
    assert outcomes[0].resolve_ms == resolve.ms


def test_threads_do_not_see_each_others_open_spans(mark):
    def work():
        with obs.span("worker"):
            pass

    with obs.span("main"):
        t = threading.Thread(target=work)
        t.start()
        t.join(timeout=10)
    assert not t.is_alive()
    rec = [r for r in _since(mark) if r.name == "worker"][0]
    assert rec.parent is None


def _tiny_state():
    params = {"w": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
              "b": jnp.ones((5,), jnp.float32)}
    opt = {"m": jax.tree_util.tree_map(jnp.zeros_like, params),
           "v": jax.tree_util.tree_map(jnp.ones_like, params)}
    return params, opt


def test_a_crashed_save_is_recorded_not_ok(tmp_path, mark):
    hosts = T._hosts(2)
    store = FileStore(str(tmp_path))
    cks = {h: CornusCheckpointer(store, h, hosts) for h in hosts}
    run = T.RunConfig(die_mid_checkpoint_at=4)
    params, opt = _tiny_state()
    with pytest.raises(T.MidCheckpointCrash):
        T._checkpoint(run, None, params, opt, 4, hosts, cks, None)
    recs = _since(mark)
    (ck,) = [r for r in recs if r.name == "checkpoint"]
    assert not ck.ok
    assert [r.name for r in _kids(recs, ck)] == [
        "d2h", "partition", "pack", "pack", "vote"]
    assert all(r.ok for r in _kids(recs, ck))
    assert _kids(recs, ck, "vote")[0].attrs == {"epoch": 4, "host": "host0"}


def _state_nbytes(run):
    cfg = T.model_config(run)
    params = jax.eval_shape(lambda: lm.init_model(cfg, jax.random.key(0)))
    opt = jax.eval_shape(lambda: adamw_init(params,
                                            T.train_settings(run).opt))
    return sum(leaf.size * leaf.dtype.itemsize
               for leaf in jax.tree_util.tree_leaves(
                   [params, opt["m"], opt["v"]]))


def test_smoke_train_two_saves_and_a_resume_write_their_spans(tmp_path,
                                                               mark):
    run = T.RunConfig(arch="llama3.2-1b", use_smoke=True, n_layers=1,
                      steps=4, batch=2, seq_len=16, ckpt_every=2,
                      ckpt_dir=str(tmp_path), n_hosts=2, log_every=0)
    hosts = T._hosts(run.n_hosts)
    res = T.train(run)
    assert [o.decision for o in res.ckpt_outcomes] == [Decision.COMMIT] * 2
    recs = _since(mark)

    steps = [r for r in recs if r.name == "step"]
    assert [r.attrs["step"] for r in steps] == [0, 1, 2, 3]
    for s in steps:
        assert s.ok and s.parent is None
        assert [r.name for r in _kids(recs, s)] == [
            "data", "h2d", "train_step", "loss_sync"]

    saves = [r for r in recs if r.name == "checkpoint"]
    assert [r.attrs["epoch"] for r in saves] == [2, 4]
    nbytes = _state_nbytes(run)
    store = FileStore(str(tmp_path))
    for ck, outcome in zip(saves, res.ckpt_outcomes):
        epoch = ck.attrs["epoch"]
        assert ck.ok and ck.parent is None
        (d2h,) = _kids(recs, ck, "d2h")
        assert d2h.attrs == {"epoch": epoch, "bytes": nbytes}
        packs = _kids(recs, ck, "pack")
        votes = _kids(recs, ck, "vote")
        assert [p.attrs["host"] for p in packs] == hosts
        assert [v.attrs["host"] for v in votes] == hosts
        assert len(_kids(recs, ck, "resolve")) == 1
        for pack, vote in zip(packs, votes):
            payload = store.get_data(vote.attrs["host"], _txn(epoch))
            (upload,) = _kids(recs, vote, "upload")
            assert upload.attrs["bytes"] == len(payload) == \
                pack.attrs["bytes"]
            # One forced log write per host, and no decision record.
            assert len(_kids(recs, vote, "log_once")) == 1
        assert outcome.vote_ms == pytest.approx(
            sum(v.ms for v in votes), rel=1e-12)
        assert outcome.resolve_ms == _kids(recs, ck, "resolve")[0].ms

    mark2 = recs[-1]
    resumed = T.train(dataclasses.replace(run, resume=True, steps=5))
    assert resumed.restored_from == 4
    recs = _since(mark2)
    (restore,) = [r for r in recs if r.name == "restore"]
    assert restore.ok and restore.attrs == {"epoch": 4}
    assert [r.name for r in _kids(recs, restore)] == ["load", "put"]
    (load,) = _kids(recs, restore, "load")
    assert load.attrs["bytes"] == sum(
        len(store.get_data(h, _txn(4))) for h in hosts)


def test_span_names_reach_the_profilers_host_plane(tmp_path):
    from chipbench import trace

    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("checkpoint", epoch=1):
            with obs.span("d2h"):
                jnp.ones(4).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    names = {e.name for p in trace.load(str(tmp_path))
             if p.name.startswith("/host:")
             for line in p.lines for e in line.events}
    assert {"checkpoint", "d2h"} <= names
