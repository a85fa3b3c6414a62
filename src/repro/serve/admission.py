"""Continuous-batching admission control for the serving engine.

The ingress queue is the same shape as the storage layer's
``GroupCommitIngress``: requests that arrive while a decode is in flight
coalesce into the next batch; a formation ``window_ms`` (counted from the
first request in the batch) trades per-step latency for batch occupancy;
a full batch flushes immediately.  On top of that it adds the two things
a serving frontend needs that a storage lane does not:

  backpressure – the queue is bounded (``queue_depth``); a submit against
                 a full queue either blocks the client (closed-loop) or is
                 rejected immediately (open-loop load shedding).
  deadlines    – each request carries an absolute deadline; requests that
                 expire while queued are dropped at batch formation,
                 before any decode compute is spent on them.

The decode call itself is pluggable: ``PallasDecode`` drives the
``kernels.decode_attention.flash_decode`` TPU kernel over a pooled KV
cache (compiled through Mosaic unless the caller asks for the Pallas
interpreter with ``interpret=True``, as CPU tests do); ``StubDecode`` is a
deterministic latency model (one base cost per batch plus a per-item
term — the same amortization shape as the storage batch lanes), reached
only by asking for ``"stub"``, used by tests and the sleep-model benches.

A decode exception fails its batch's requests (clients see drops) but
never the serving loop; the batcher counts it in ``decode_errors``, keeps
the first exception and logs it once, so a kernel that cannot run on the
device is not mistaken for a slow server.
"""
from __future__ import annotations

import sys
import threading
import time
import traceback
from dataclasses import dataclass
from typing import List, Optional, Sequence

__all__ = ["AdmissionConfig", "ContinuousBatcher", "PallasDecode",
           "StepRequest", "StubDecode", "make_decode"]


@dataclass
class AdmissionConfig:
    max_batch: int = 8
    window_ms: float = 2.0          # batch formation window from 1st arrival
    queue_depth: int = 64           # bounded ingress queue
    backpressure: str = "block"     # "block" | "reject" on a full queue
    deadline_ms: Optional[float] = None   # per-request; None = no deadline

    def __post_init__(self) -> None:
        if self.backpressure not in ("block", "reject"):
            raise ValueError(f"backpressure must be 'block' or 'reject', "
                             f"got {self.backpressure!r}")


class StepRequest:
    """One decode step for one session, in flight through the batcher."""

    __slots__ = ("session", "token", "submitted_at", "deadline_at", "done",
                 "result", "dropped", "batch_size", "decode_ms")

    def __init__(self, session: str, token: int,
                 deadline_at: Optional[float] = None) -> None:
        self.session = session
        self.token = token
        self.submitted_at = time.monotonic()
        self.deadline_at = deadline_at
        self.done = threading.Event()
        self.result: Optional[int] = None
        self.dropped = False
        self.batch_size = 0
        self.decode_ms = 0.0


class StubDecode:
    """Latency-modeled batched decode: one batch costs
    ``base_ms + per_item_ms * len(batch)`` of sleep — batching amortizes
    the base term exactly like a storage flush amortizes a round trip.
    The returned token is a deterministic hash of (session, token)."""

    def __init__(self, base_ms: float = 1.0, per_item_ms: float = 0.1,
                 vocab: int = 50_000) -> None:
        self.base_ms = base_ms
        self.per_item_ms = per_item_ms
        self.vocab = vocab

    def __call__(self, reqs: Sequence[StepRequest]) -> List[int]:
        time.sleep((self.base_ms + self.per_item_ms * len(reqs)) / 1e3)
        return [(hash((r.session, r.token)) & 0x7FFFFFFF) % self.vocab
                for r in reqs]


class PallasDecode:
    """flash_decode-backed batched decode over a pooled KV cache.

    Maintains one preallocated (slots, Hkv, T, hd) K/V pool; each session
    owns a slot and a valid-prefix length.  A batch gathers its sessions'
    cache rows, runs ONE ``flash_decode`` call for the whole batch (the
    continuous-batching payoff: the memory-bound kernel streams every
    session's cache in a single grid), then appends the new K/V at each
    session's write position.  Q/K/V projections of the incoming token are
    stand-ins (seeded random features) — the subsystem under test is the
    batching + commit loop, not the LM weights.
    """

    def __init__(self, slots: int = 64, q_heads: int = 4, kv_heads: int = 2,
                 head_dim: int = 64, max_len: int = 256,
                 block_kv: int = 128, seed: int = 0,
                 interpret: bool = False) -> None:
        import jax
        import jax.numpy as jnp
        from ..kernels.decode_attention import flash_decode
        self._jax, self._jnp = jax, jnp
        self._flash_decode = flash_decode
        self.slots = slots
        self.q_heads = q_heads
        self.kv_heads = kv_heads
        self.head_dim = head_dim
        self.max_len = max_len
        self.block_kv = block_kv
        self.interpret = interpret
        self._k = jnp.zeros((slots, kv_heads, max_len, head_dim),
                            jnp.float32)
        self._v = jnp.zeros((slots, kv_heads, max_len, head_dim),
                            jnp.float32)
        self._lens = [0] * slots
        self._by_session = {}
        self._free = list(range(slots))
        self._rng = jax.random.key(seed)
        self._lock = threading.Lock()

    def _slot_of(self, session: str) -> int:
        with self._lock:
            i = self._by_session.get(session)
            if i is None:
                if not self._free:
                    # Recycle the least-recently registered slot: a serving
                    # pool evicts idle sessions; the commit layer, not the
                    # cache, is the session's ground truth.
                    i = min(self._by_session.values())
                    stale = next(s for s, j in self._by_session.items()
                                 if j == i)
                    del self._by_session[stale]
                else:
                    i = self._free.pop()
                self._by_session[session] = i
                self._lens[i] = 0
            return i

    def release(self, session: str) -> None:
        with self._lock:
            i = self._by_session.pop(session, None)
            if i is not None:
                self._free.append(i)
                self._lens[i] = 0

    def __call__(self, reqs: Sequence[StepRequest]) -> List[int]:
        jax, jnp = self._jax, self._jnp
        idx = [self._slot_of(r.session) for r in reqs]
        B = len(reqs)
        self._rng, sub = jax.random.split(self._rng)
        q = jax.random.normal(
            sub, (B, self.q_heads, 1, self.head_dim), jnp.float32)
        kv_new = jax.random.normal(
            sub, (2, B, self.kv_heads, 1, self.head_dim), jnp.float32)
        gather = jnp.asarray(idx, jnp.int32)
        # Append this step's K/V at each session's write position FIRST so
        # the query attends to its own token even on an empty cache.
        for b, i in enumerate(idx):
            pos = min(self._lens[i], self.max_len - 1)
            self._k = self._k.at[i, :, pos].set(kv_new[0, b, :, 0])
            self._v = self._v.at[i, :, pos].set(kv_new[1, b, :, 0])
            self._lens[i] = pos + 1
        k = jnp.take(self._k, gather, axis=0)
        v = jnp.take(self._v, gather, axis=0)
        kv_len = max(self._lens[i] for i in idx)
        out = self._flash_decode(q, k, v, jnp.int32(kv_len),
                                 block_kv=self.block_kv,
                                 interpret=self.interpret)
        # Reduce each session's attention output to a token id — a stand-in
        # for the LM head (deterministic given the seeded projections).
        scores = jnp.sum(jnp.abs(out), axis=(1, 2, 3))
        return [int(s * 1e4) % 50_000 for s in jax.device_get(scores)]


def make_decode(kind: str, **kwargs):
    """'pallas' (the flash-decode kernel) | 'stub' (the latency model)."""
    if kind == "pallas":
        return PallasDecode(**kwargs)
    if kind == "stub":
        return StubDecode(**kwargs)
    raise ValueError(f"unknown decode backend {kind!r}: "
                     f"expected 'pallas' or 'stub'")


class ContinuousBatcher:
    """Bounded ingress queue + one decode worker forming batches.

    ``submit`` returns True when the request was admitted (its ``done``
    event will fire with either a result or ``dropped=True``), False when
    it was load-shed by ``reject`` backpressure.  ``stop()`` drains
    nothing: queued requests are failed as dropped so no client blocks
    forever across shutdown.
    """

    def __init__(self, decode, cfg: AdmissionConfig) -> None:
        self.decode = decode
        self.cfg = cfg
        self._queue: List[StepRequest] = []
        self._cv = threading.Condition()
        self._stopped = False
        self._thread: Optional[threading.Thread] = None
        # Counters (same spirit as GroupCommitIngress's).
        self.submitted = 0
        self.rejected = 0
        self.dropped = 0
        self.batches = 0
        self.decoded = 0
        self.max_batch_seen = 0
        self.decode_errors = 0                  # batches whose decode raised
        self.first_decode_error: Optional[BaseException] = None

    # -- client side --------------------------------------------------------
    def submit(self, req: StepRequest) -> bool:
        if self.cfg.deadline_ms is not None and req.deadline_at is None:
            req.deadline_at = req.submitted_at + self.cfg.deadline_ms / 1e3
        with self._cv:
            while (len(self._queue) >= self.cfg.queue_depth
                   and not self._stopped):
                if self.cfg.backpressure == "reject":
                    self.rejected += 1
                    return False
                self._cv.wait(timeout=0.05)
            if self._stopped:
                self.rejected += 1
                return False
            self._queue.append(req)
            self.submitted += 1
            self._cv.notify_all()
        return True

    # -- worker side --------------------------------------------------------
    def start(self) -> "ContinuousBatcher":
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        with self._cv:
            self._stopped = True
            leftovers = self._queue
            self._queue = []
            self._cv.notify_all()
        for req in leftovers:
            req.dropped = True
            req.done.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None

    def _take_batch(self) -> List[StepRequest]:
        """Block until a batch is formed: first arrival starts the window;
        the batch closes when the window elapses or ``max_batch`` queued."""
        with self._cv:
            while not self._queue and not self._stopped:
                self._cv.wait(timeout=0.05)
            if self._stopped and not self._queue:
                return []
            deadline = time.monotonic() + self.cfg.window_ms / 1e3
            while (len(self._queue) < self.cfg.max_batch
                   and not self._stopped):
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    break
                self._cv.wait(timeout=remaining)
            batch = self._queue[:self.cfg.max_batch]
            self._queue = self._queue[len(batch):]
            self._cv.notify_all()     # wake blocked submitters
            return batch

    def _loop(self) -> None:
        while True:
            batch = self._take_batch()
            if not batch:
                if self._stopped:
                    return
                continue
            now = time.monotonic()
            live: List[StepRequest] = []
            for req in batch:
                if req.deadline_at is not None and now >= req.deadline_at:
                    # Expired while queued: shed BEFORE spending decode
                    # compute on a result nobody will wait for.
                    req.dropped = True
                    self.dropped += 1
                    req.done.set()
                else:
                    live.append(req)
            if not live:
                continue
            self.batches += 1
            self.max_batch_seen = max(self.max_batch_seen, len(live))
            t0 = time.monotonic()
            try:
                results = self.decode(live)
            except Exception as e:
                # A decode failure fails the batch's requests, never the
                # serving loop (clients see a drop and may retry).
                self.decode_errors += 1
                if self.first_decode_error is None:
                    self.first_decode_error = e
                    print("ContinuousBatcher: decode failed; failing the "
                          "batch (further errors are counted, not logged):\n"
                          + "".join(traceback.format_exception(e)),
                          file=sys.stderr, flush=True)
                for req in live:
                    req.dropped = True
                    self.dropped += 1
                    req.done.set()
                continue
            ms = (time.monotonic() - t0) * 1e3
            for req, tok in zip(live, results):
                req.result = tok
                req.batch_size = len(live)
                req.decode_ms = ms
                self.decoded += 1
                req.done.set()

    @property
    def mean_batch(self) -> float:
        return self.decoded / self.batches if self.batches else 0.0
