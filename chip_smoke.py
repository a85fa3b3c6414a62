"""Run both device paths once on one TPU chip and check what comes out.

    python chip_smoke.py          # from the repository root, on a TPU host

Phases, in this order, all in this one process (the chip belongs to one
process at a time, so nothing here starts a child):

  device  jax.devices()[0] must be a TPU; otherwise exit 1 at once.
  kernel  flash_decode at llama3.2-1b decode widths (B 8, Hq 32, Hkv 8,
          hd 64, T 2048, one uniform kv_len), f32 and bf16, against
          kernels.ref.attention_ref at the tolerances of tests/test_kernels.py.
  serve   ServeEngine, closed loop, decode="pallas" at llama3.2-1b attention
          widths; 8 clients x 8 steps, each step committed through cornus on
          the replicated store (R=3).  Every step must commit, with no drops,
          no decode errors, and the kernel compiled (interpret=False).
  train   train() on llama3.2-1b at its published widths and vocabulary,
          depth cut to TRAIN_LAYERS; a Cornus checkpoint epoch on a FileStore
          across two hosts must COMMIT, and a resumed run must restore it and
          replay the next step's loss.

Each phase prints its findings on its own lines.  A failed phase raises; the
remaining phases still run so one call shows every fault, and the script
then exits 1 without a result line.  On success the last line is one JSON
object naming the device.  Times printed are single-run smoke timings, not
metrics.
"""
from __future__ import annotations

import functools
import json
import math
import os
import sys
import tempfile
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

ARCH = "llama3.2-1b"
# Same tolerances as tests/test_kernels.py.
TOL = {"float32": dict(rtol=2e-5, atol=2e-5),
       "bfloat16": dict(rtol=3e-2, atol=3e-2)}
DECODE_BATCH, DECODE_T, DECODE_KV_LEN, BLOCK_KV = 8, 2048, 1531, 128
SERVE_CLIENTS, SERVE_STEPS = 8, 8
# 4 of 16 layers: the rehearsal compile for one v5e chip gives 6.07 GB of
# arguments and 3.94 GB of temporaries at batch 8 x 128 (8 layers: 8.99 +
# 7.65 GB of 15.75 GB, too little headroom for the restore's second copy).
TRAIN_LAYERS = 4


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def phase_device():
    devices = jax.devices()
    d = devices[0]
    print(f"[device] platform={d.platform} kind={d.device_kind} "
          f"count={len(devices)} jax={jax.__version__}", flush=True)
    if d.platform != "tpu":
        print(f"[device] FAILED: no TPU (JAX found {d.platform!r}); no "
              f"phase falls back to it", file=sys.stderr, flush=True)
        sys.exit(1)
    return d


def phase_kernel() -> None:
    from repro.configs import get_config
    from repro.kernels import ref
    from repro.kernels.decode_attention import flash_decode

    cfg = get_config(ARCH)
    B, Hq, Hkv, hd = DECODE_BATCH, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    keys = jax.random.split(jax.random.key(0), 3)
    kernel = jax.jit(functools.partial(flash_decode, block_kv=BLOCK_KV))
    reference = jax.jit(functools.partial(ref.attention_ref, causal=False,
                                          kv_len=DECODE_KV_LEN))
    for dtype in (jnp.float32, jnp.bfloat16):
        q = jax.random.normal(keys[0], (B, Hq, 1, hd)).astype(dtype)
        k = jax.random.normal(keys[1], (B, Hkv, DECODE_T, hd)).astype(dtype)
        v = jax.random.normal(keys[2], (B, Hkv, DECODE_T, hd)).astype(dtype)
        got = kernel(q, k, v, jnp.int32(DECODE_KV_LEN))
        # The reference is fp32 math: keep XLA's matmuls at full precision.
        with jax.default_matmul_precision("highest"):
            want = reference(q, k, v)
        got = np.asarray(got.astype(jnp.float32))
        want = np.asarray(want.astype(jnp.float32))
        name = jnp.dtype(dtype).name
        err = float(np.max(np.abs(got - want)))
        print(f"[kernel] flash_decode {name} B={B} Hq={Hq} Hkv={Hkv} hd={hd} "
              f"T={DECODE_T} kv_len={DECODE_KV_LEN} block_kv={BLOCK_KV}: "
              f"max |kernel - attention_ref| = {err!r} "
              f"(rtol={TOL[name]['rtol']}, atol={TOL[name]['atol']})",
              flush=True)
        check(bool(np.all(np.isfinite(got))), f"{name}: non-finite output")
        np.testing.assert_allclose(got, want, **TOL[name])


def phase_serve() -> None:
    from repro.configs import get_config
    from repro.serve import (AdmissionConfig, EngineConfig, ServeEngine,
                             SessionConfig)

    cfg = get_config(ARCH)
    engine = ServeEngine(EngineConfig(
        session=SessionConfig(protocol="cornus", backend="replicated",
                              replication=3),
        admission=AdmissionConfig(max_batch=8),
        decode="pallas",
        decode_kwargs=dict(slots=64, q_heads=cfg.n_heads,
                           kv_heads=cfg.n_kv_heads, head_dim=cfg.hd,
                           max_len=DECODE_T),
        clients=SERVE_CLIENTS, steps_per_session=SERVE_STEPS))
    decode = engine.batcher.decode
    r = engine.run()
    rep, c = r.report, r.counters
    total = SERVE_CLIENTS * SERVE_STEPS
    print(f"[serve] cornus replicated R={c['replication']:.0f}, "
          f"decode=pallas interpret={decode.interpret} "
          f"(q_heads={decode.q_heads} kv_heads={decode.kv_heads} "
          f"head_dim={decode.head_dim} max_len={decode.max_len} "
          f"slots={decode.slots})", flush=True)
    print(f"[serve] committed {rep.committed} of {total} steps, "
          f"aborted={rep.aborted} dropped={rep.dropped} "
          f"rejected={rep.rejected} decode_errors={c['decode_errors']:.0f} "
          f"batches={c['batches']:.0f} mean_batch={rep.mean_batch!r}",
          flush=True)
    print(f"[serve] smoke timing: p50={rep.p50_ms!r} ms p99={rep.p99_ms!r} ms "
          f"elapsed={rep.elapsed_s!r} s (compiles included)", flush=True)
    if engine.batcher.first_decode_error is not None:
        raise engine.batcher.first_decode_error
    check(decode.interpret is False, "decode ran in the interpreter")
    check(c["decode_errors"] == 0, f"{c['decode_errors']} decode errors")
    check(rep.dropped == 0 and rep.rejected == 0,
          f"dropped={rep.dropped} rejected={rep.rejected}")
    check(rep.committed == total, f"{rep.committed} of {total} committed")


def phase_train() -> None:
    from repro.core.state import Decision
    from repro.launch.train import RunConfig, model_config, train

    with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt_") as ckpt_dir:
        base = dict(arch=ARCH, use_smoke=False, n_layers=TRAIN_LAYERS,
                    batch=8, seq_len=128, ckpt_every=2, n_hosts=2,
                    ckpt_dir=ckpt_dir, log_every=1, seed=0)
        cfg = model_config(RunConfig(**base))
        print(f"[train] {ARCH} cut to {cfg.n_layers} of "
              f"{model_config(RunConfig(arch=ARCH, use_smoke=False)).n_layers}"
              f" layers; widths as published: d_model={cfg.d_model} "
              f"heads={cfg.n_heads}/{cfg.n_kv_heads} d_ff={cfg.d_ff} "
              f"vocab={cfg.vocab_size}; params={cfg.param_count()}",
              flush=True)
        first = train(RunConfig(steps=3, **base))
        print(f"[train] losses {first.losses!r}", flush=True)
        check(len(first.losses) == 3 and all(map(math.isfinite,
                                                 first.losses)),
              f"losses not finite: {first.losses}")
        check(len(first.ckpt_outcomes) == 1, "expected one checkpoint epoch")
        out = first.ckpt_outcomes[0]
        print(f"[train] checkpoint epoch {out.epoch}: {out.decision.name} "
              f"across {base['n_hosts']} hosts (smoke timing: vote "
              f"{out.vote_ms!r} ms, resolve {out.resolve_ms!r} ms); "
              f"store holds {_du(ckpt_dir)} bytes", flush=True)
        check(out.decision == Decision.COMMIT, f"epoch {out.epoch} "
                                               f"{out.decision.name}")

        again = train(RunConfig(steps=3, resume=True, **base))
        print(f"[train] resume: restored_from={again.restored_from} "
              f"steps_done={again.steps_done} losses {again.losses!r} "
              f"(first run's step 3: {first.losses[2]!r})", flush=True)
        check(again.restored_from == out.epoch,
              f"restored_from={again.restored_from}, want {out.epoch}")
        check(len(again.losses) >= 1 and all(map(math.isfinite,
                                                 again.losses)),
              "resumed run took no finite step")
        # Same restored state, same data: the replayed step matches.
        np.testing.assert_allclose(again.losses[0], first.losses[2],
                                   rtol=1e-5)
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    print(f"[train] device peak_bytes_in_use="
          f"{peak if peak is not None else 'not measured'}", flush=True)


def _du(path: str) -> int:
    return sum(os.path.getsize(os.path.join(d, f))
               for d, _, files in os.walk(path) for f in files)


def main() -> int:
    device = phase_device()
    cache_dir = enable_compile_cache()
    print(f"[cache] compilation cache: {cache_dir}", flush=True)
    failed = []
    for name, phase in (("kernel", phase_kernel), ("serve", phase_serve),
                        ("train", phase_train)):
        t0 = time.perf_counter()
        try:
            phase()
        except Exception:
            traceback.print_exc()
            failed.append(name)
            print(f"[{name}] FAILED", flush=True)
        else:
            print(f"[{name}] ok; smoke timing {time.perf_counter() - t0!r} s",
                  flush=True)
    entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    print(f"[cache] {entries} entries in {cache_dir}", flush=True)
    if failed:
        print(f"chip_smoke: failed phases: {', '.join(failed)}",
              file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
