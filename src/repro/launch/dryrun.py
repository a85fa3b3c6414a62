import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run (deliverable e).

Lowers + compiles every (architecture × input-shape × mesh) cell against the
production meshes — (16,16)=256 chips single-pod, (2,16,16)=512 chips
multi-pod — and extracts the roofline inputs:

  * cost_analysis  FLOPs / bytes   (per-device; while-loop bodies counted
    once by XLA, so the scanned layer stack's body is compiled separately
    and its cost scaled by (n_periods - 1))
  * collective "wire bytes" per device, parsed from optimized HLO with
    replica-group-size-aware factors (ring model):
        all-gather (g-1)/g · out     all-reduce 2(g-1)/g · out
        reduce-scatter (g-1) · out   all-to-all (g-1)/g · out
        collective-permute 1 · out
  * memory_analysis (argument/output/temp bytes per device)

Writes one JSON per cell under --out (default artifacts/dryrun).

NOTE: the XLA_FLAGS line above MUST run before any jax import — this module
is the only place the 512-device world is created.
"""
import argparse
import dataclasses
import json
import re
import sys
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs import ALIASES, ARCH_IDS, get_config
from ..models.config import ALL_SHAPES, ModelConfig, ShapeConfig
from .mesh import make_production_mesh
from .sharding import Rules, make_rules
from . import steps as S


def cost_dict(compiled) -> Dict:
    """``Compiled.cost_analysis()`` as a dict (empty when XLA reports none)."""
    return compiled.cost_analysis() or {}

DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_OP_RE = re.compile(
    r"=\s*(\([^)]*\)|[a-z0-9]+\[[0-9,]*\][^\s]*)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(")
_GROUPS_BRACE_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * DTYPE_BYTES.get(dt, 4)
    return total


def parse_collectives(hlo_text: str) -> Dict[str, Dict[str, float]]:
    """Per-device wire bytes + op counts by collective type."""
    out = {c: {"bytes": 0.0, "count": 0, "result_bytes": 0.0}
           for c in COLLECTIVES}
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        type_str, op, _ = m.groups()
        res = _shape_bytes(type_str)
        g = 1
        mb = _GROUPS_BRACE_RE.search(line)
        if mb:
            g = len(mb.group(1).split(","))
        else:
            mi = _GROUPS_IOTA_RE.search(line)
            if mi:
                g = int(mi.group(2))
        g = max(g, 1)
        if op == "all-gather":
            wire = res * (g - 1) / g
        elif op == "all-reduce":
            wire = res * 2 * (g - 1) / g
        elif op == "reduce-scatter":
            wire = res * (g - 1)
        elif op == "all-to-all":
            wire = res * (g - 1) / g
        else:  # collective-permute
            wire = res
        out[op]["bytes"] += wire
        out[op]["count"] += 1
        out[op]["result_bytes"] += res
    return out


def _merge_scaled(base: Dict, body: Dict, scale: int) -> Dict:
    out = {}
    for k in base:
        out[k] = {f: base[k][f] + scale * body[k][f] for f in base[k]}
    return out


def model_flops(cfg: ModelConfig, shape: ShapeConfig) -> float:
    """Hand-derived 'useful' FLOPs: 6·N_active·D train, 2·N_active·D infer."""
    n = cfg.active_param_count() - cfg.padded_vocab * cfg.d_model  # non-embed
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        base = 6.0 * n * tokens
        # logits matmul fwd+bwd
        base += 6.0 * shape.global_batch * shape.seq_len * \
            cfg.d_model * cfg.padded_vocab
        return base
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens + 2.0 * tokens * cfg.d_model * cfg.padded_vocab
    # decode: one token/seq against cache (attention adds 2·S·d per kv layer)
    tokens = shape.global_batch
    flops = 2.0 * n * tokens + 2.0 * tokens * cfg.d_model * cfg.padded_vocab
    n_attn = sum(1 for k in cfg.full_pattern if k.startswith("attn"))
    flops += (4.0 * cfg.n_kv_heads * cfg.hd * shape.seq_len
              * cfg.n_heads // max(cfg.n_kv_heads, 1)) * n_attn * tokens
    return flops


def run_cell(arch: str, shape: ShapeConfig, multi_pod: bool,
             settings: S.TrainSettings, profile: str = "default") -> Dict:
    cfg = get_config(arch)
    mesh_name = "multi" if multi_pod else "single"
    rec: Dict = {"arch": arch, "shape": shape.name, "mesh": mesh_name,
                 "profile": profile}
    if shape.name == "long_500k" and not cfg.subquadratic:
        rec["skipped"] = ("full-attention arch: 512k context needs "
                          "sub-quadratic attention (DESIGN §5)")
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.devices.size
    rules = make_rules(mesh, profile)
    specs = S.input_specs(cfg, shape, rules, settings)

    if shape.kind == "train":
        fn = S.make_train_step(cfg, settings, rules)
        args = (specs["params"], specs["opt_state"], specs["batch"],
                specs["step"])
        donate = (0, 1)
    elif shape.kind == "prefill":
        fn = S.make_prefill_step(cfg, shape.seq_len, rules)
        args = (specs["params"], specs["batch"])
        donate = ()
    else:
        fn = S.make_decode_step(cfg, rules)
        args = (specs["params"], specs["batch"], specs["cache"], specs["pos"])
        donate = (2,)

    t0 = time.time()
    with mesh:
        lowered = jax.jit(fn, donate_argnums=donate).lower(*args)
        t1 = time.time()
        compiled = lowered.compile()
        t2 = time.time()

        ca = cost_dict(compiled)
        ma = compiled.memory_analysis()
        coll = parse_collectives(compiled.as_text())

        # Scale the scanned-stack body by its trip count.
        body_ca: Dict = {}
        body_coll: Dict = {c: {"bytes": 0.0, "count": 0, "result_bytes": 0.0}
                           for c in COLLECTIVES}
        trips = 0
        body = S.make_period_body(cfg, shape, rules, settings)
        if body is not None:
            body_fn, body_args = body
            bc = jax.jit(body_fn).lower(*body_args).compile()
            body_ca = cost_dict(bc)
            body_coll = parse_collectives(bc.as_text())
            trips = cfg.n_periods - 1

    flops = float(ca.get("flops", 0.0)) + trips * float(
        body_ca.get("flops", 0.0))
    byts = float(ca.get("bytes accessed", 0.0)) + trips * float(
        body_ca.get("bytes accessed", 0.0))
    coll_total = _merge_scaled(coll, body_coll, trips)

    rec.update(
        n_devices=n_dev,
        lower_s=round(t1 - t0, 2),
        compile_s=round(t2 - t1, 2),
        flops_per_device=flops,
        hbm_bytes_per_device=byts,
        collectives=coll_total,
        collective_bytes_per_device=sum(v["bytes"]
                                        for v in coll_total.values()),
        memory=dict(
            argument_bytes=ma.argument_size_in_bytes,
            output_bytes=ma.output_size_in_bytes,
            temp_bytes=ma.temp_size_in_bytes,
            alias_bytes=ma.alias_size_in_bytes,
        ),
        params_total=cfg.param_count(),
        params_active=cfg.active_param_count(),
        model_flops_total=model_flops(cfg, shape),
        trip_scaled_periods=trips,
        sharding_fallbacks=len(rules.fallbacks),
    )
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="all",
                    help="arch id (dash form) or 'all'")
    ap.add_argument("--shape", default="all",
                    help="train_4k|prefill_32k|decode_32k|long_500k|all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi",
                                                       "both"])
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--remat", default="dots",
                    choices=["none", "dots", "full"])
    ap.add_argument("--opt-dtype", default="float32",
                    choices=["float32", "bfloat16"])
    ap.add_argument("--profile", default="default",
                    choices=["default", "fsdp", "sp"])
    args = ap.parse_args(argv)

    from ..optim import AdamWConfig
    settings = S.TrainSettings(
        remat=args.remat,
        opt=AdamWConfig(state_dtype=jnp.bfloat16 if args.opt_dtype ==
                        "bfloat16" else jnp.float32))

    archs = list(ALIASES) if args.arch == "all" else [args.arch]
    shapes = [s for s in ALL_SHAPES
              if args.shape in ("all", s.name)]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    os.makedirs(args.out, exist_ok=True)
    failures = []
    for arch in archs:
        for shape in shapes:
            for multi in meshes:
                mesh_name = "multi" if multi else "single"
                tag = f"{arch}__{shape.name}__{mesh_name}"
                path = os.path.join(args.out, tag + ".json")
                try:
                    rec = run_cell(arch, shape, multi, settings,
                                   args.profile)
                except Exception as e:  # a dry-run failure is a real bug
                    rec = {"arch": arch, "shape": shape.name,
                           "mesh": mesh_name, "error": repr(e)[:2000]}
                    failures.append(tag)
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
                status = ("SKIP" if "skipped" in rec else
                          "FAIL" if "error" in rec else
                          f"ok {rec['compile_s']:6.1f}s "
                          f"flops/dev={rec['flops_per_device']:.3e} "
                          f"coll/dev={rec['collective_bytes_per_device']:.3e}")
                print(f"[dryrun] {tag:55s} {status}", flush=True)
    if failures:
        print(f"[dryrun] {len(failures)} FAILURES: {failures}")
        return 1
    print("[dryrun] all cells passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
