"""Training cells: the program's own ``launch.train.train()`` under the hooks.

Set-up builds the one training object there is, ``train()``'s loop with its
compiled step and state, and drives it through its first ``setup_steps``
steps; the reference follows the first ``reference_steps`` of them.  The
window is the same loop from there on, until the deadline.  A traffic mix
that saves (``ckpt_every``) ends the window at the first save after the
deadline with ``hooks.WHOLE_SAVES`` whole saves behind it, crashes in it
through the program's own ``die_mid_checkpoint_at`` once host 0 has voted,
and then times ``train(resume=True)`` in the same process up to its first
step's loss, ``resumes`` times.  Everything that knows the model's shape is
reached through the configuration's architecture module (``archs``).
"""
from __future__ import annotations

import dataclasses
import gc
import math
import shutil
import tempfile
import time
from typing import Dict, List

import jax
import numpy as np

from .. import archs
from .. import compare as C
from .. import device
from .. import reference as R
from .. import weights as W
from ..hooks import SPANS, TrainHooks, WindowClosed


class ConfigMismatch(RuntimeError):
    """The program would not run the configuration the file states."""


def _check_program(T, run_cfg, cfg: dict, arch, dm: dict) -> None:
    """Hold the program to the configuration file before anything runs."""
    mcfg = arch.configured(T.model_config, dm)(run_cfg)
    for key, value in arch.program_fields(cfg, dm).items():
        got = getattr(mcfg, key)
        same = (math.isclose(got, value, rel_tol=1e-12)
                if isinstance(value, float) else got == value)
        if not same:
            raise ConfigMismatch(f"program's {key} is {got!r}, the "
                                 f"configuration states {value!r}")
    tr, st = cfg["training"], T.train_settings(run_cfg)
    opt = dict(lr=st.opt.lr, b1=st.opt.b1, b2=st.opt.b2, eps=st.opt.eps,
               weight_decay=st.opt.weight_decay, grad_clip=st.opt.grad_clip,
               warmup=st.warmup, stable=st.stable, decay=st.decay)
    for key, got in opt.items():
        if not math.isclose(got, tr[key], rel_tol=1e-12):
            raise ConfigMismatch(f"program's {key} is {got!r}, the "
                                 f"configuration states {tr[key]!r}")
    if st.schedule != tr["schedule"] or st.compress is not None \
            or st.opt.state_dtype != jax.numpy.float32:
        raise ConfigMismatch("program's optimizer departs from the "
                             "configuration")
    mine = jax.eval_shape(lambda: arch.program_weights(dm, 0))
    theirs = jax.eval_shape(
        lambda: T.lm.init_model(mcfg, jax.random.key(0)))
    if jax.tree_util.tree_structure(mine) != \
            jax.tree_util.tree_structure(theirs) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in
            zip(jax.tree_util.tree_leaves(mine),
                jax.tree_util.tree_leaves(theirs))):
        raise ConfigMismatch("program's parameter tree differs from the "
                             "benchmark's weights")


def _key(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


def _state_keys(arch, dm: dict) -> List[str]:
    """Payload keys of the saved state, in the order of its leaves."""
    p = jax.eval_shape(lambda: arch.program_weights(dm, 0))
    tree = {"params": p, "opt": {"m": p, "v": p}}
    return [_key(path) for path, _ in
            jax.tree_util.tree_flatten_with_path(tree)[0]]


def _commit_checks(hooks: TrainHooks, ckpt_dir: str,
                   hosts: List[str], resumed: list,
                   crash_epoch: int) -> Dict[str, dict]:
    from repro.ckpt import fetch_payloads, unpack_tree
    from repro.ckpt.commit import CornusCheckpointer
    from repro.core.state import Decision, Vote
    from repro.core.storage import FileStore

    store = FileStore(ckpt_dir)
    reader = CornusCheckpointer(store, "checker", hosts)
    committed = {s["epoch"]: s for s in hooks.saves
                 if s["outcome"] is not None
                 and s["outcome"].decision == Decision.COMMIT}
    newest = max(committed)
    missing = sum(v != Vote.VOTE_YES for e in committed
                  for v in reader.read_states(e).values())
    mismatched = 0
    for e, save in committed.items():
        saved = [int(x) for x in save["fingerprint"]]
        if e == newest:
            restored = [r for r in hooks.restores if r["epoch"] == e]
            mismatched += (len(restored) != len(resumed)) + sum(
                [int(x) for x in r["fingerprint"]] != saved
                for r in restored)
            continue
        flat = {}
        for payload in fetch_payloads(store, hosts, e).values():
            flat.update(unpack_tree(payload))
        got = [int(np.sum(flat[k].view(np.uint32), dtype=np.uint32))
               if k in flat else -1
               for k in _state_keys(hooks.arch, hooks.dm)]
        mismatched += (len(got) != len(saved)) + sum(
            a != b for a, b in zip(got, saved))
    crashed = reader.global_decision(crash_epoch)
    window_loss = float(hooks.losses[newest])
    replay = max(abs(r.losses[0] - window_loss) if r.losses else math.inf
                 for r in resumed)
    wrong = len(hooks.latest) != len(resumed) or any(
        x["epoch"] != newest for x in hooks.latest) or any(
        r.restored_from != newest for r in resumed)
    return {
        "commit_votes_missing": dict(value=missing, limit=0),
        "restore_bits_mismatched": dict(value=mismatched, limit=0),
        "crashed_epoch_not_aborted": dict(
            value=int(crashed != Decision.ABORT), limit=0),
        "restored_wrong_epoch": dict(value=int(wrong), limit=0),
        "resume_loss_diff": dict(
            value=replay if math.isfinite(replay) else math.inf, limit=0.0),
    }


def run_config(T, cfg: dict, traffic: dict, seed: int, ckpt_dir: str,
               steps: int = 10 ** 9):
    """The ``RunConfig`` the configuration and traffic mix describe."""
    tr = cfg["training"]
    return T.RunConfig(
        arch=cfg["program"]["arch"], use_smoke=cfg["program"]["use_smoke"],
        n_layers=cfg["program"]["n_layers"], steps=steps,
        batch=traffic["batch"], seq_len=traffic["seq_len"],
        ckpt_every=traffic["ckpt_every"] or 10 ** 9, ckpt_dir=ckpt_dir,
        n_hosts=cfg["checkpoint"]["hosts"], lr=tr["lr"],
        warmup=tr["warmup"], seed=seed, log_every=0)


def tokens_for(cfg: dict, traffic: dict, seed: int) -> W.SeededTokens:
    return W.SeededTokens(cfg["vocab_size"], traffic["batch"],
                          traffic["seq_len"], seed,
                          traffic["tokens"]["offset"])


def reference_numbers(cfg: dict, traffic: dict, seed: int,
                      dtype=jax.numpy.float32):
    """(losses, first clipped gradient norms, change norms) of the
    reference over the first ``reference_steps`` steps."""
    arch = archs.load(cfg)
    dm, tokens = arch.dims(cfg), tokens_for(cfg, traffic, seed)
    return R.run_steps(
        cfg, arch,
        lambda: arch.reference_view(dm, arch.program_weights(dm, seed)),
        [tokens.tokens(i) for i in range(traffic["reference_steps"])], dtype)


def program_numbers(cfg: dict, traffic: dict, hooks: TrainHooks):
    """The same three readings, from what the hooks kept of the program's
    first steps; the gradient is worked out from AdamW's m after one step
    (m = (1 - b1) * clipped gradient)."""
    b1 = cfg["training"]["b1"]
    return ([float(hooks.losses[i])
             for i in range(traffic["reference_steps"])],
            {k: float(v) / (1 - b1) for k, v in hooks.grad_norms.items()},
            {k: float(v) for k, v in hooks.change_norms.items()})


def step_checks(cfg: dict, program, reference) -> Dict[str, dict]:
    """The numbers that hold the first steps to the reference."""
    limits = {k: v["limit"] for k, v in cfg["correct"].items()}
    (p_loss, p_grad, p_change), (r_loss, r_grad, r_change) = program, \
        reference
    g_gap, g_leaf = C.worst_norm_gap(p_grad, r_grad)
    u_gap, u_leaf = C.worst_norm_gap(p_change, r_change,
                                     skip=C.negligible(r_grad))
    return {
        "loss_gap": dict(value=C.loss_gap(p_loss, r_loss),
                         limit=limits["loss_gap"]),
        "grad_norm_gap": dict(value=g_gap, limit=limits["grad_norm_gap"],
                              leaf=g_leaf),
        "update_norm_gap": dict(value=u_gap, limit=limits["update_norm_gap"],
                                leaf=u_leaf),
    }


def _resume_parts(hooks: TrainHooks) -> List[Dict[str, float]]:
    """Where each resume's time went, for the run's log line."""
    out = []
    for m, latest, restore in zip(hooks.resume_marks, hooks.latest,
                                  hooks.restores):
        to_step = (m.get("step_dispatched", m["start"]) - m["start"]
                   - restore["own_s"])
        out.append({"total": m["total"], "latest_committed": latest["secs"],
                    "restore_params": restore["secs"],
                    "before_first_step": to_step - latest["secs"]
                    - restore["secs"]})
    return out


def run(cfg: dict, traffic: dict, *, seed: int, seconds: float,
        trace_dir, t_start: float, devices) -> dict:
    import repro.launch.train as T
    from repro.core.state import Decision
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    arch = archs.load(cfg)
    dm = arch.dims(cfg)
    B, S = traffic["batch"], traffic["seq_len"]
    hosts_n = cfg["checkpoint"]["hosts"]
    ckpt_every = traffic["ckpt_every"]
    crash = bool(ckpt_every)
    ckpt_dir = tempfile.mkdtemp(prefix="chipbench_ckpt_")
    try:
        run_cfg = run_config(T, cfg, traffic, seed, ckpt_dir)
        _check_program(T, run_cfg, cfg, arch, dm)
        tokens = tokens_for(cfg, traffic, seed)
        hooks = TrainHooks(
            arch=arch, dm=dm, seed=seed, tokens=tokens,
            setup_steps=traffic["setup_steps"],
            ref_steps=traffic["reference_steps"], seconds=seconds,
            ckpt_every=ckpt_every, trace_dir=trace_dir)
        resumed, resume_s = [], None
        with hooks.installed(T):
            try:
                T.train(run_cfg)
            except WindowClosed:
                pass
            except T.MidCheckpointCrash:
                if not crash:
                    raise
            else:
                raise RuntimeError("train() ended before the window closed")
            gc.collect()
            if crash:
                committed = [s["epoch"] for s in hooks.saves
                             if s["outcome"] is not None and
                             s["outcome"].decision == Decision.COMMIT]
                if not committed:
                    raise RuntimeError("no save committed before the crash")
                hooks.phase = "resume"
                resume_cfg = dataclasses.replace(
                    run_cfg, resume=True, steps=max(committed) + 1,
                    die_mid_checkpoint_at=None)
                # Each resume is a whole restart from the newest committed
                # epoch (the first also terminates the crashed one);
                # resume_s is their mean.
                for _ in range(traffic["resumes"]):
                    hooks.resume_marks.append({"start": time.perf_counter()})
                    resumed.append(T.train(resume_cfg))
                    mark = hooks.resume_marks[-1]
                    # Less the fingerprint of the restored state.
                    mark["total"] = (time.perf_counter() - mark["start"]
                                     - hooks.restores[-1]["own_s"])
                    gc.collect()
                resume_s = float(np.mean([m["total"]
                                          for m in hooks.resume_marks]))
        device_peak = device.memory_peak(devices)
        gc.collect()

        # --- correctness: the reference follows the first steps ---------
        reference = reference_numbers(cfg, traffic, seed)
        program = program_numbers(cfg, traffic, hooks)
        checks = step_checks(cfg, program, reference)
        if crash:
            checks.update(_commit_checks(hooks, ckpt_dir,
                                         [f"host{i}" for i in range(hosts_n)],
                                         resumed, hooks.crash_epoch))

        window_losses = [float(hooks.losses[s]) for s in
                         range(traffic["setup_steps"],
                               traffic["setup_steps"] + hooks.window_steps)]
        saves = hooks.window_saves()
        failed = sum(not math.isfinite(x) for x in window_losses) + sum(
            s["outcome"] is None or s["outcome"].decision != Decision.COMMIT
            for s in saves)
        window_s = hooks.window_s
        gaps = np.diff(hooks.step_marks)
        step_ms = ([float(np.percentile(gaps, q)) * 1e3 for q in (10, 50, 90)]
                   + [float(gaps.max()) * 1e3]) if len(gaps) else []
        slowest = {}
        if len(gaps):
            i = int(np.argmax(gaps))
            slowest = dict(index=i, gap_ms=float(gaps[i]) * 1e3,
                           get_ms=hooks.step_parts[i][0] * 1e3,
                           dispatch_ms=hooks.step_parts[i][1] * 1e3)
        e2e = {
            "train_tokens_per_s": hooks.window_steps * B * S / window_s,
            "setup_s": hooks.t_window - t_start,
        }
        if resume_s is not None:
            e2e["resume_s"] = resume_s
        return dict(
            e2e=e2e, checks=checks, device_peak=device_peak,
            attempted=hooks.window_steps + len(saves), failed=failed,
            ctx=dict(flops_per_step=arch.train_step_flops(dm, B, S),
                     window_saves=saves, latest=hooks.latest,
                     restores=hooks.restores),
            info=dict(window_s=window_s, own_work_in_window_s=hooks.paused_s,
                      window_steps=hooks.window_steps,
                      window_saves=len(saves),
                      save_stalls_s=[s["stall_s"] for s in saves],
                      compiles_in_window=hooks.compiles_in_window,
                      step_ms_p10_p50_p90_max=step_ms,
                      slowest_step=slowest,
                      gc_in_window_s=hooks.gc_in_window_s,
                      resume_parts_s=_resume_parts(hooks),
                      crash_epoch=hooks.crash_epoch,
                      ref_losses=reference[0], prog_losses=program[0]),
            spans=SPANS)
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)
