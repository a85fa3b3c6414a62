"""Transactional-serving tests: sessions, admission control, engine.

Deterministic twins carry the coverage (hypothesis is a dev-only
dependency); the @given properties re-check the batching-invisibility
contract under random request sets when hypothesis is installed.
"""
from __future__ import annotations

import threading
import time

import pytest

from conftest import hypothesis_or_stubs

HAS_HYPOTHESIS, given, settings, st = hypothesis_or_stubs()

from repro.core.state import Vote
from repro.serve import (AdmissionConfig, ContinuousBatcher, EngineConfig,
                         ServeEngine, SessionConfig, SessionManager,
                         StepRequest, StubDecode, build_session_store,
                         make_decode, run_serve)


# ---------------------------------------------------------------------------
# Sessions as transactions: per-protocol storage choreography
# ---------------------------------------------------------------------------
def _manager(protocol: str, **kw) -> SessionManager:
    cfg = SessionConfig(protocol=protocol, backend="memory",
                        participants_per_txn=3, kv_partitions=4, **kw)
    return SessionManager(build_session_store(cfg), cfg)


@pytest.mark.parametrize("protocol", ["cornus", "2pc", "cl"])
def test_session_lifecycle_commits(protocol):
    mgr = _manager(protocol)
    s = mgr.open_session("client")
    assert s.open
    for _ in range(3):
        out = mgr.step(s)
        assert out.committed
    assert mgr.close_session(s)
    assert s.kv_len == 3
    assert (mgr.opens, mgr.steps_committed, mgr.closes) == (1, 3, 1)


def test_cornus_step_leaves_only_votes():
    """Cornus: commit == the collective vote state; no decision record."""
    mgr = _manager("cornus")
    s = mgr.open_session("c")
    mgr.step(s)
    txn = s.step_txn(0)
    for p in s.partitions:
        assert mgr.store.read_state(p, txn) == Vote.VOTE_YES


def test_2pc_step_forces_decision_record():
    """2PC: the eager COMMIT record lands on the coordinator partition —
    the extra forced write cornus removes."""
    mgr = _manager("2pc")
    s = mgr.open_session("c")
    mgr.step(s)
    txn = s.step_txn(0)
    assert mgr.store.read_state(s.coordinator, txn) == Vote.COMMIT
    for p in s.partitions[1:]:
        assert mgr.store.read_state(p, txn) == Vote.VOTE_YES


def test_cl_step_logs_only_coordinator():
    mgr = _manager("cl")
    s = mgr.open_session("c")
    mgr.step(s)
    txn = s.step_txn(0)
    assert mgr.store.read_state(s.coordinator, txn) == Vote.COMMIT
    for p in s.partitions[1:]:
        assert mgr.store.read_state(p, txn) is None


def test_terminate_step_aborts_parked_step():
    """A step parked mid-vote is CAS-terminated by a scavenger and comes
    back ABORTED — never hangs (the paper's non-blocking property)."""
    mgr = _manager("cornus")
    s = mgr.open_session("c")
    txn = s.step_txn(s.steps)
    parts = list(s.partitions)

    def park(i: int, _p: str) -> None:
        if i == len(parts) - 1:     # stall before the LAST vote
            t = threading.Thread(target=mgr.terminate_step,
                                 args=(s.sid, txn, parts), daemon=True)
            t.start()
            t.join()                # scavenger fully done while we "hang"

    out = mgr.step(s, before_vote=park)
    assert not out.committed
    assert mgr.store.read_state(parts[-1], txn) == Vote.ABORT
    assert mgr.terminations == 1
    assert mgr.steps_aborted == 1
    assert s.kv_len == 0            # the aborted step appended nothing
    # Serving continues: the next step commits normally.
    assert mgr.step(s).committed


def test_terminate_step_after_full_commit_is_noop():
    mgr = _manager("cornus")
    s = mgr.open_session("c")
    out = mgr.step(s)
    assert out.committed
    landed = mgr.terminate_step(s.sid, s.step_txn(0), s.partitions)
    assert not landed               # every slot already held VOTE_YES


def test_build_session_store_rejects_sim_backends():
    with pytest.raises(ValueError, match="simulated"):
        build_session_store(SessionConfig(backend="sim"))


# ---------------------------------------------------------------------------
# Admission control: deadlines, backpressure, shutdown
# ---------------------------------------------------------------------------
class _GatedDecode:
    """Decode that announces entry and blocks until released — makes the
    backpressure tests deterministic."""

    def __init__(self) -> None:
        self.started = threading.Event()
        self.gate = threading.Event()
        self.calls = 0

    def __call__(self, reqs):
        self.calls += 1
        self.started.set()
        assert self.gate.wait(timeout=10.0)
        return [0] * len(reqs)


def test_deadline_expired_request_is_dropped_before_decode():
    decode = StubDecode(base_ms=0.1)
    b = ContinuousBatcher(decode, AdmissionConfig(max_batch=4,
                                                  window_ms=0.0)).start()
    try:
        req = StepRequest("s", 0, deadline_at=time.monotonic() - 1.0)
        assert b.submit(req)
        assert req.done.wait(timeout=5.0)
        assert req.dropped and req.result is None
        assert b.dropped == 1 and b.decoded == 0 and b.batches == 0
    finally:
        b.stop()


def test_backpressure_reject_sheds_when_queue_full():
    decode = _GatedDecode()
    b = ContinuousBatcher(decode, AdmissionConfig(
        max_batch=1, window_ms=0.0, queue_depth=1,
        backpressure="reject")).start()
    try:
        r1 = StepRequest("s", 0)
        assert b.submit(r1)
        assert decode.started.wait(timeout=5.0)   # worker busy on r1
        r2 = StepRequest("s", 1)
        assert b.submit(r2)                       # fills the queue
        r3 = StepRequest("s", 2)
        assert not b.submit(r3)                   # shed, immediately
        assert b.rejected == 1
        decode.gate.set()
        assert r1.done.wait(timeout=5.0)
        assert r2.done.wait(timeout=5.0)
        assert not r1.dropped and not r2.dropped
    finally:
        decode.gate.set()
        b.stop()


def test_backpressure_block_waits_for_capacity():
    decode = _GatedDecode()
    b = ContinuousBatcher(decode, AdmissionConfig(
        max_batch=1, window_ms=0.0, queue_depth=1,
        backpressure="block")).start()
    try:
        assert b.submit(StepRequest("s", 0))
        assert decode.started.wait(timeout=5.0)
        assert b.submit(StepRequest("s", 1))      # queue now full
        r3 = StepRequest("s", 2)
        got = []
        t = threading.Thread(target=lambda: got.append(b.submit(r3)),
                             daemon=True)
        t.start()
        t.join(timeout=0.15)
        assert t.is_alive()                       # blocked, not shed
        decode.gate.set()                         # drain; capacity frees
        t.join(timeout=5.0)
        assert not t.is_alive() and got == [True]
        assert r3.done.wait(timeout=5.0)
        assert b.rejected == 0
    finally:
        decode.gate.set()
        b.stop()


def test_stop_fails_queued_requests_instead_of_hanging():
    decode = _GatedDecode()
    b = ContinuousBatcher(decode, AdmissionConfig(
        max_batch=1, window_ms=0.0, queue_depth=8)).start()
    assert b.submit(StepRequest("s", 0))
    assert decode.started.wait(timeout=5.0)
    queued = StepRequest("s", 1)
    assert b.submit(queued)
    decode.gate.set()
    b.stop()
    assert queued.done.wait(timeout=5.0)          # failed, not forgotten


# ---------------------------------------------------------------------------
# Batching invisibility: batched == unbatched decode decisions
# ---------------------------------------------------------------------------
def _decode_all(reqs_spec, max_batch: int, window_ms: float):
    """Push every (session, token) through a batcher; return results and
    shed/drop counts."""
    b = ContinuousBatcher(StubDecode(base_ms=0.05, per_item_ms=0.01),
                         AdmissionConfig(max_batch=max_batch,
                                         window_ms=window_ms,
                                         queue_depth=10_000)).start()
    try:
        reqs = [StepRequest(sid, tok) for sid, tok in reqs_spec]
        for r in reqs:
            assert b.submit(r)
        for r in reqs:
            assert r.done.wait(timeout=30.0)
        assert b.dropped == 0 and b.rejected == 0
        return {(r.session, r.token): r.result for r in reqs}
    finally:
        b.stop()


def test_batched_equals_unbatched_results_deterministic():
    spec = [(f"s{i % 5}", i) for i in range(40)]
    batched = _decode_all(spec, max_batch=8, window_ms=2.0)
    unbatched = _decode_all(spec, max_batch=1, window_ms=0.0)
    assert batched == unbatched
    assert all(v is not None for v in batched.values())


@settings(max_examples=15, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 6), st.integers(0, 1000)),
                min_size=1, max_size=60))
def test_batched_equals_unbatched_results_property(pairs):
    spec = [(f"s{sid}", tok) for sid, tok in pairs]
    assert (_decode_all(spec, max_batch=8, window_ms=1.0)
            == _decode_all(spec, max_batch=1, window_ms=0.0))


# ---------------------------------------------------------------------------
# Engine: end-to-end serving with publish + failure injection
# ---------------------------------------------------------------------------
def test_engine_closed_loop_serves_through_publish_and_stall():
    cfg = EngineConfig(
        session=SessionConfig(protocol="cornus", backend="memory",
                              participants_per_txn=3,
                              service_delay_ms=0.5),
        admission=AdmissionConfig(max_batch=8, window_ms=0.5),
        clients=4, steps_per_session=10,
        publish_at=0.3, publish_until=0.7, stall_at=0.5)
    r = run_serve(cfg)
    rep = r.report
    total = 4 * 10
    assert rep.completed == total
    assert rep.aborted == 1                 # exactly the scavenged stall
    assert rep.committed == total - 1
    assert r.counters["terminations"] == 1
    assert len(r.publishes) >= 1            # epochs committed mid-traffic
    assert rep.publish_disruption is not None
    assert rep.p99_ms >= rep.p50_ms > 0
    assert r.counters["closes"] == 4


def test_engine_replicated_survives_replica_kill():
    cfg = EngineConfig(
        session=SessionConfig(protocol="cornus", backend="replicated",
                              replication=3, participants_per_txn=2,
                              service_delay_ms=0.5),
        admission=AdmissionConfig(max_batch=8, window_ms=0.5),
        clients=4, steps_per_session=8,
        publish_at=0.3, publish_until=0.8, kill_replica_at=0.3)
    r = run_serve(cfg)
    rep = r.report
    assert r.counters["replica_killed"] >= 0
    assert rep.committed == 4 * 8           # quorum survives, every step
    assert r.counters["fast_path_ops"] > 0  # lease fast path engaged
    assert len(r.publishes) >= 1


def test_engine_unbatched_mode_batches_of_one():
    cfg = EngineConfig(
        session=SessionConfig(protocol="cornus", backend="memory",
                              service_delay_ms=0.2),
        clients=3, steps_per_session=4, batch_mode="unbatched")
    r = run_serve(cfg)
    assert r.report.committed == 3 * 4
    assert r.counters["max_batch_seen"] == 1


def test_engine_deadline_drops_count_against_goodput():
    cfg = EngineConfig(
        session=SessionConfig(protocol="cornus", backend="memory",
                              service_delay_ms=0.2),
        admission=AdmissionConfig(max_batch=4, window_ms=5.0,
                                  deadline_ms=1e-4),
        clients=3, steps_per_session=4)
    r = run_serve(cfg)
    rep = r.report
    assert rep.dropped == 3 * 4             # every step expires queued
    assert rep.committed == 0 and rep.goodput_tps == 0.0


def test_engine_open_loop_sheds_instead_of_stalling():
    cfg = EngineConfig(
        session=SessionConfig(protocol="cornus", backend="memory",
                              service_delay_ms=0.5),
        admission=AdmissionConfig(max_batch=4, window_ms=0.5,
                                  backpressure="reject", queue_depth=8),
        clients=4, arrival="open", rate_rps=300.0, duration_s=0.5,
        max_inflight=16)
    r = run_serve(cfg)
    rep = r.report
    assert rep.committed > 0
    assert rep.committed == r.counters["steps_committed"]
    # Whatever wasn't admitted was shed, not lost: accounting adds up.
    assert rep.completed + rep.dropped <= r.counters["submitted"]


# ---------------------------------------------------------------------------
# Decode backends: the kernel only by name, and failures counted
# ---------------------------------------------------------------------------
def test_engine_pallas_decode_in_interpreter_commits_every_step():
    pytest.importorskip("jax")
    cfg = EngineConfig(
        session=SessionConfig(protocol="cornus", backend="memory",
                              participants_per_txn=2),
        admission=AdmissionConfig(max_batch=4, window_ms=1.0),
        decode="pallas",
        # The CPU has no Mosaic: the interpreter is asked for, never implied.
        decode_kwargs=dict(slots=8, q_heads=2, kv_heads=1, head_dim=32,
                           max_len=32, block_kv=16, interpret=True),
        clients=2, steps_per_session=3)
    engine = ServeEngine(cfg)
    assert engine.batcher.decode.interpret is True
    r = engine.run()
    assert r.report.committed == 2 * 3
    assert r.report.dropped == 0
    assert r.counters["decode_errors"] == 0


class _RaisingDecode:
    def __init__(self) -> None:
        self.raised: list = []

    def __call__(self, reqs):
        e = RuntimeError(f"decode refused batch {len(self.raised)}")
        self.raised.append(e)
        raise e


def test_engine_counts_decode_errors_and_keeps_the_first(capsys):
    cfg = EngineConfig(
        session=SessionConfig(protocol="cornus", backend="memory"),
        admission=AdmissionConfig(max_batch=4, window_ms=1.0),
        clients=3, steps_per_session=2)
    engine = ServeEngine(cfg)
    decode = _RaisingDecode()
    engine.batcher.decode = decode
    r = engine.run()
    # Serving semantics unchanged: the failed batches' steps are drops.
    assert r.report.committed == 0
    assert r.report.dropped == 3 * 2
    assert r.counters["decode_errors"] == len(decode.raised) >= 1
    assert r.counters["decode_errors"] == r.counters["batches"]
    assert engine.batcher.first_decode_error is decode.raised[0]
    # Logged once, however many batches failed.
    assert capsys.readouterr().err.count("decode failed") == 1


def test_serve_bench_runs_device_cells_in_process(monkeypatch):
    """A cell whose decode needs the chip never forks: a child of a parent
    that has touched JAX could not reach the device."""
    import multiprocessing

    from benchmarks import serve_bench

    def no_fork(*a, **k):
        raise AssertionError("a device cell was forked")

    monkeypatch.setattr(multiprocessing, "get_context", no_fork)
    monkeypatch.setattr(serve_bench, "_summarize", lambda cfg: {"ran": 1.0})
    assert serve_bench._run_isolated(EngineConfig(decode="pallas")) == \
        {"ran": 1.0}


def test_make_decode_accepts_only_pallas_and_stub():
    assert isinstance(make_decode("stub"), StubDecode)
    for kind in ("auto", "jax", ""):
        with pytest.raises(ValueError, match="pallas"):
            make_decode(kind)
