"""Transactional serving example: sessions commit every decode step.

Eight closed-loop clients stream inference sessions through the serving
engine (``repro.serve``): steps coalesce in the continuous batcher, run a
batched decode (the Pallas flash-decode kernel), and each step's KV-cache
update COMMITS as a distributed transaction — here via Cornus, so a step
costs one forced LogOnce vote per KV partition and nothing else.  Mid-run, a background
publisher commits a checkpoint epoch through the same store while serving
continues.

Run:  PYTHONPATH=src python examples/serve_batched.py             # on a TPU
      JAX_PLATFORMS=cpu PYTHONPATH=src python examples/serve_batched.py \
          --interpret                                             # no TPU
"""
import argparse

from repro.serve import (AdmissionConfig, EngineConfig, SessionConfig,
                         run_serve)

ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
ap.add_argument("--interpret", action="store_true",
                help="run the decode kernel in the Pallas interpreter "
                     "(for a machine without a TPU)")
args = ap.parse_args()

cfg = EngineConfig(
    session=SessionConfig(protocol="cornus", backend="replicated",
                          replication=3, kv_partitions=8,
                          participants_per_txn=2, service_delay_ms=1.0),
    # Generous deadline: the interpreted kernel costs ~1s per batch, and
    # the example is about the commit path, not decode speed.
    admission=AdmissionConfig(max_batch=4, window_ms=1.5,
                              deadline_ms=30_000.0),
    decode="pallas",
    # Small attention geometry: in the interpreter big grids make an
    # example crawl.
    decode_kwargs=dict(slots=16, q_heads=2, kv_heads=1, head_dim=32,
                       max_len=64, block_kv=32, interpret=args.interpret),
    clients=8, steps_per_session=12,
    publish_at=0.4, publish_until=0.8, publish_interval_s=0.2)

result = run_serve(cfg)
rep = result.report
print(f"[serve] protocol={rep.protocol} committed={rep.committed} "
      f"aborted={rep.aborted} dropped={rep.dropped} "
      f"decode_errors={result.counters['decode_errors']:.0f}")
print(f"[serve] tput={rep.throughput_tps:.1f} steps/s "
      f"goodput={rep.goodput_tps:.1f}/s mean_batch={rep.mean_batch:.2f}")
print(f"[serve] p50={rep.p50_ms:.2f}ms p99={rep.p99_ms:.2f}ms "
      f"(tail amp {rep.tail_amplification:.2f}) "
      f"ttft_p50={rep.ttft_p50_ms:.2f}ms")
print(f"[serve] publishes={len(result.publishes)} "
      f"(window tput ratio "
      f"{rep.publish_disruption if rep.publish_disruption else 'n/a'}), "
      f"fast_path_ops={result.counters['fast_path_ops']:.0f}")
