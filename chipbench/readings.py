"""Readings from which the limits of ``correct`` are set (run on the chip).

    python3 chipbench/readings.py --config minicpm-2b-train.vocab-half \
        --traffic steady --seeds 11,12,... --control 11,12,13 \
        --faults 11,12,13 [--vocab 122753 --rows 122880]

For each seed it runs the program's first ``reference_steps`` steps through
``train()`` under the benchmark's hooks, at the cell's size, and the float32
reference at highest precision, and prints one JSON line with the compared
numbers (the lower readings).  For the ``--control`` seeds it prints the same
numbers for the control, the reference computed in bfloat16 in the
program's place; for the ``--faults`` seeds, those of the program with the
``half_batch`` and ``altered_loss`` faults planted.  A state left unchanged
reads 1 by the gap-of-norms measure and needs no run.  ``--vocab`` and
``--rows`` run another vocabulary, and the program's table of ``rows`` rows
for it, in place of the configuration's.  All in one process, so the
compiled programs are shared.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def first_steps(cfg: dict, traffic: dict, seed: int):
    """The program's readings over its first ``reference_steps`` steps,
    through ``train()`` under the hooks (no window)."""
    import repro.launch.train as T
    from chipbench import archs
    from chipbench.hooks import TrainHooks
    from chipbench.kinds import train as K

    steps = traffic["reference_steps"]
    ckpt_dir = tempfile.mkdtemp(prefix="chipbench_ckpt_")
    try:
        arch = archs.load(cfg)
        hooks = TrainHooks(arch=arch, dm=arch.dims(cfg), seed=seed,
                           tokens=K.tokens_for(cfg, traffic, seed),
                           setup_steps=steps + 1, ref_steps=steps,
                           seconds=math.inf, ckpt_every=0,
                           trace_dir=None)
        run_cfg = K.run_config(T, cfg, traffic, seed, ckpt_dir, steps=steps)
        with hooks.installed(T):
            T.train(run_cfg)
        return K.program_numbers(cfg, traffic, hooks)
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)


def _seeds(text: str):
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--vocab", type=int)
    ap.add_argument("--rows", type=int)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from chipbench import device, faults
    from chipbench.kinds import train as K
    from repro.launch.compile_cache import enable_compile_cache

    device.require_tpu(1)
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    with open(os.path.join(HERE, "configs", args.config + ".json")) as f:
        cfg = json.load(f)
    with open(os.path.join(HERE, "traffic", args.traffic + ".json")) as f:
        traffic = json.load(f)
    if args.vocab:
        cfg["vocab_size"] = args.vocab
        cfg["as_run"]["padded_vocab_rows"] = args.rows or args.vocab

    def emit(kind, seed, numbers):
        row = {"kind": kind, "seed": seed, "vocab": cfg["vocab_size"]}
        row.update({k: c["value"] for k, c in numbers.items()})
        row.update({k + ".leaf": c["leaf"] for k, c in numbers.items()
                    if c.get("leaf")})
        print(json.dumps(row), flush=True)

    todo = sorted(set(_seeds(args.seeds) + _seeds(args.control)
                      + _seeds(args.faults)))
    for seed in todo:
        ref = K.reference_numbers(cfg, traffic, seed)
        if seed in _seeds(args.seeds):
            emit("program", seed, K.step_checks(
                cfg, first_steps(cfg, traffic, seed), ref))
        if seed in _seeds(args.control):
            ctl = K.reference_numbers(cfg, traffic, seed, jnp.bfloat16)
            emit("control", seed, K.step_checks(cfg, ctl, ref))
        if seed in _seeds(args.faults):
            for name in ("half_batch", "altered_loss"):
                with faults.FAULTS[name]():
                    got = first_steps(cfg, traffic, seed)
                emit(name, seed, K.step_checks(cfg, got, ref))
    return 0


if __name__ == "__main__":
    sys.exit(main())
