"""Transactional-serving bench: protocol × arrival × batch mode.

Drives ``repro.serve`` — inference sessions whose every step commits as a
distributed transaction — through a sweep of commit protocol (cornus vs
2pc), arrival process (closed loop, open loop at a fixed rate), and batch
mode (continuous batching vs batches of one).  Per cell it reports
committed-step throughput (the tracked baseline metric), goodput within
deadline, and the latency tail (p50/p99, TTFT).

Every forced store write pays an injected 2 ms service delay (inside the
op, under the control plane), so the latency ordering is structural:
cornus commits a step after 3 forced vote writes, 2pc after the same 3
votes PLUS an eager forced commit record — a fixed ~2 ms tail gap that
the p99 gate pins per cell.

One extra cell prices disruption: a closed-loop cornus run on the quorum-
replicated store with a background checkpoint publisher committing
snapshot epochs over the middle third of the run AND one replica volume
killed at the same moment.  The gate requires in-window throughput to
stay ≥ 80% of steady state — serving must not stall behind a publish or
a dead replica.

Standalone entry point with a CI regression gate::

    python -m benchmarks.serve_bench --quick --check-baseline
    python -m benchmarks.serve_bench --quick --write-baseline

The baseline (``BENCH_serve.json`` at the repo root) pins quick-mode
throughput per cell; ``--check-baseline`` exits non-zero on a >15%
regression, on a cell where cornus p99 exceeds 2pc p99, or on a
disruption ratio below 0.8.
"""
from __future__ import annotations

import multiprocessing
import os
import sys
from typing import Dict, List, Optional

from repro.serve import AdmissionConfig, EngineConfig, SessionConfig, \
    run_serve

from benchmarks._baseline import Row, gate_main

BASELINE_PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_serve.json")

TRIALS = 3

# Injected per-forced-write service time: large enough that OS sleep
# overshoot stays a few percent of it, and the 2pc extra decision record
# (one more forced write per step) is a structural ~2 ms latency gap.
SERVICE_DELAY_MS = 2.0
PROTOCOLS = ("cornus", "2pc")

# (arrival label, batch modes swept at that arrival).  Open-loop rates
# sweep the arrival dimension; the unbatched control arm only needs the
# closed loop (it prices batching, not arrivals).
QUICK_ARRIVALS = (("closed", ("batched", "unbatched")),
                  ("open400", ("batched",)))
FULL_ARRIVALS = (("closed", ("batched", "unbatched")),
                 ("open200", ("batched",)),
                 ("open400", ("batched", "unbatched")),
                 ("open800", ("batched",)))


def _cell_config(protocol: str, arrival: str, mode: str,
                 quick: bool) -> EngineConfig:
    session = SessionConfig(protocol=protocol, backend="memory",
                            participants_per_txn=3,
                            service_delay_ms=SERVICE_DELAY_MS, seed=7)
    admission = AdmissionConfig(max_batch=8, window_ms=1.0,
                                queue_depth=64, deadline_ms=250.0)
    cfg = EngineConfig(session=session, admission=admission,
                       decode="stub", batch_mode=mode, seed=7,
                       clients=8,
                       steps_per_session=30 if quick else 80)
    if arrival.startswith("open"):
        cfg.arrival = "open"
        cfg.rate_rps = float(arrival[4:])
        cfg.duration_s = 1.2 if quick else 3.0
        cfg.admission = AdmissionConfig(max_batch=8, window_ms=1.0,
                                        queue_depth=64,
                                        backpressure="reject",
                                        deadline_ms=250.0)
    return cfg


def _disruption_config(quick: bool) -> EngineConfig:
    """Replicated store, background publish over the middle third of the
    run, one replica volume killed as publishing starts."""
    session = SessionConfig(protocol="cornus", backend="replicated",
                            replication=3, participants_per_txn=3,
                            service_delay_ms=SERVICE_DELAY_MS, seed=7)
    return EngineConfig(
        session=session,
        admission=AdmissionConfig(max_batch=8, window_ms=1.0),
        decode="stub", seed=7, clients=8,
        steps_per_session=45 if quick else 120,
        publish_at=0.33, publish_until=0.66, publish_hosts=2,
        publish_interval_s=0.02, kill_replica_at=0.33, stall_at=0.5)


def _summarize(cfg: EngineConfig) -> Dict[str, float]:
    """Best-of-TRIALS cell summary: throughput takes the best trial (noise
    only slows a run); tail latency and the disruption ratio take each
    trial's best too, so both protocols face the same scheduler luck."""
    best: Optional[Dict[str, float]] = None
    for _ in range(TRIALS):
        r = run_serve(cfg)
        rep = r.report
        cur = {
            "tput_tps": rep.throughput_tps,
            "goodput_tps": rep.goodput_tps,
            "p50_ms": rep.p50_ms, "p99_ms": rep.p99_ms,
            "ttft_p50_ms": rep.ttft_p50_ms,
            "tail_amp": rep.tail_amplification,
            "mean_batch": rep.mean_batch,
            "max_batch_seen": float(r.counters["max_batch_seen"]),
            "committed": float(rep.committed),
            "aborted": float(rep.aborted),
            "dropped": float(rep.dropped),
            "rejected": float(rep.rejected),
            "terminations": float(r.counters["terminations"]),
            "publishes": float(len(r.publishes)),
            "disruption": (rep.publish_disruption
                           if rep.publish_disruption is not None else -1.0),
        }
        if best is None:
            best = cur
        else:
            for k in ("tput_tps", "goodput_tps", "max_batch_seen",
                      "disruption"):
                best[k] = max(best[k], cur[k])
            for k in ("p50_ms", "p99_ms", "ttft_p50_ms", "tail_amp"):
                best[k] = min(best[k], cur[k])
    return best


def _run_cell(cfg: EngineConfig, queue: "multiprocessing.Queue") -> None:
    queue.put(_summarize(cfg))


def _run_isolated(cfg: EngineConfig) -> Dict[str, float]:
    """Each stub-decode cell in a fresh subprocess — no cross-cell
    thread/CPU interference in the wall-clock numbers (inline fallback when
    the platform can't fork).  A cell whose decode runs on the device runs
    in this process: the chip belongs to one process at a time, and a
    forked child of a parent that has touched JAX cannot reach it."""
    if cfg.decode != "stub":
        return _summarize(cfg)
    try:
        ctx = multiprocessing.get_context("fork")
        queue: "multiprocessing.Queue" = ctx.Queue()
        proc = ctx.Process(target=_run_cell, args=(cfg, queue))
        proc.start()
        result = queue.get(timeout=600)
        proc.join()
        return result
    except (OSError, ValueError) as e:
        print(f"# serve_bench: subprocess unavailable ({e!r}), "
              f"running cell inline", file=sys.stderr)
        return _summarize(cfg)


def sweep(quick: bool = False) -> List[Row]:
    rows: List[Row] = []
    arrivals = QUICK_ARRIVALS if quick else FULL_ARRIVALS
    for arrival, modes in arrivals:
        for mode in modes:
            for protocol in PROTOCOLS:
                s = _run_isolated(_cell_config(protocol, arrival, mode,
                                               quick))
                key = f"serve/{protocol}/{arrival}/{mode}"
                derived = (f"goodput={s['goodput_tps']:.1f} "
                           f"p50={s['p50_ms']:.2f} "
                           f"ttft_p50={s['ttft_p50_ms']:.2f} "
                           f"tail_amp={s['tail_amp']:.2f} "
                           f"mean_batch={s['mean_batch']:.2f} "
                           f"committed={s['committed']:.0f} "
                           f"aborted={s['aborted']:.0f} "
                           f"dropped={s['dropped']:.0f} "
                           f"rejected={s['rejected']:.0f}")
                rows.append((f"{key}/tput_tps", s["tput_tps"], derived))
                rows.append((f"{key}/p99_ms", s["p99_ms"],
                             "end-to-end step latency tail"))
                if mode == "batched":
                    rows.append((f"{key}/max_batch_seen",
                                 s["max_batch_seen"],
                                 "continuous batching engagement"))
    d = _run_isolated(_disruption_config(quick))
    rows.append(("serve/disruption/tput_tps", d["tput_tps"],
                 f"replicated+publish+replica-kill committed={d['committed']:.0f} "
                 f"aborted={d['aborted']:.0f} publishes={d['publishes']:.0f} "
                 f"terminations={d['terminations']:.0f}"))
    rows.append(("serve/disruption/ratio", d["disruption"],
                 "publish-window tput / steady-state tput (>=0.8 gated)"))
    rows.append(("serve/disruption/publishes", d["publishes"],
                 "checkpoint epochs committed mid-traffic"))
    return rows


# ---------------------------------------------------------------------------
# Baseline gate (CI) — shared machinery in benchmarks/_baseline.py
# ---------------------------------------------------------------------------
P99_SLACK = 1.02        # scheduler-noise allowance on the per-cell compare
MIN_DISRUPTION = 0.8    # publish+kill window keeps >=80% of steady tput


def check_serve(rows: List[Row]) -> bool:
    got: Dict[str, float] = {name: value for name, value, _ in rows}
    ok = True
    # Within every swept cell, cornus's p99 must not exceed 2pc's: the
    # eager decision record is a per-step latency cost, and it has to show.
    cells = sorted({name[len("serve/cornus/"):-len("/p99_ms")]
                    for name in got
                    if name.startswith("serve/cornus/")
                    and name.endswith("/p99_ms")})
    for cell in cells:
        c = got.get(f"serve/cornus/{cell}/p99_ms")
        t = got.get(f"serve/2pc/{cell}/p99_ms")
        if c is None or t is None:
            print(f"# p99 MISSING for cell {cell}", file=sys.stderr)
            ok = False
            continue
        good = c <= t * P99_SLACK
        verdict = "ok" if good else "TAIL-INVERTED"
        if not good:
            ok = False
        print(f"# p99 {verdict}: {cell} cornus {c:.2f}ms vs 2pc {t:.2f}ms",
              file=sys.stderr)
    ratio = got.get("serve/disruption/ratio")
    if ratio is None:
        print("# disruption MISSING", file=sys.stderr)
        ok = False
    else:
        good = ratio >= MIN_DISRUPTION
        verdict = "ok" if good else "STALLED"
        if not good:
            ok = False
        print(f"# disruption {verdict}: publish-window ratio {ratio:.2f} "
              f"(floor {MIN_DISRUPTION})", file=sys.stderr)
    pubs = got.get("serve/disruption/publishes", 0.0)
    if pubs <= 0:
        print("# disruption ZERO publishes: publisher never committed "
              "an epoch mid-traffic", file=sys.stderr)
        ok = False
    engaged = sum(v for name, v, _ in rows
                  if name.endswith("/max_batch_seen"))
    if engaged < 2:
        print(f"# batching ZERO: no batched cell ever formed a multi-item "
              f"batch (sum max_batch_seen={engaged:.0f})", file=sys.stderr)
        ok = False
    return ok


def main() -> None:
    gate_main(description=__doc__.splitlines()[0],
              sweep=sweep,
              baseline_path=BASELINE_PATH,
              bench_name="benchmarks.serve_bench --quick",
              error_msg="serving throughput regressed >15% against "
                        "BENCH_serve.json (or cornus p99 exceeded 2pc p99 "
                        "in a cell, or a publish/replica-kill window "
                        "dropped throughput below 80% of steady state)",
              extra_check=check_serve)


if __name__ == "__main__":
    main()
