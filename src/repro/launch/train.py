"""End-to-end training driver with Cornus-committed checkpointing.

Runs a real (reduced-config or custom) model on the local device(s):
  data pipeline → jitted train_step (fwd+bwd+AdamW, WSD schedule) →
  every ``ckpt_every`` steps, a Cornus checkpoint epoch: the state is pulled
  to the host once (``ckpt.shards.to_host``), the process acts as all
  ``n_hosts`` fleet members (size-balanced shard partitioning), votes each
  host's shard set into the FileStore, and the epoch commits iff the
  collective votes are durable — Algorithm 1, deployed.

Each step and each save is marked with ``repro.obs`` spans: ``step`` ⊃
``data``, ``h2d``, ``train_step``, ``loss_sync``; ``checkpoint`` ⊃ ``d2h``,
``partition``, ``pack`` and ``vote`` per host, ``resolve``.  ``pack``
lays a host's leaves out in the ``CKS1`` format and checksums each
(``ckpt.shards``); its ``copied`` attribute counts the leaf bytes that had
to be made contiguous, 0 when the pulled leaves are written as they are.

Restart semantics: ``resume=True`` restores the newest COMMITTED epoch
(in-flight epochs are resolved by the termination protocol, never waited
on) and the stateless data pipeline replays from the restored step, so a
killed-and-restarted run produces the exact same loss curve as an unkilled
one — asserted in tests/test_train_loop.py.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..ckpt import (CheckpointOutcome, CornusCheckpointer, latest_committed,
                    pack_tree, partition_leaves, restore_params, to_host)
from ..ckpt.commit import AsyncCheckpointer
from ..core.state import Decision
from ..core.storage import FileStore
from ..data import DataConfig, Prefetcher, make_pipeline
from ..models import config as mc
from ..models import lm
from ..optim import AdamWConfig, adamw_init
from . import steps as S
from .compile_cache import enable_compile_cache


@dataclass
class RunConfig:
    arch: str = "llama3.2-1b"
    use_smoke: bool = True              # reduced config (CPU-trainable)
    # Depth cut: layers to build (None = the config's own).  Widths and
    # vocabulary stay as configured, so a published model can be cut to
    # what one chip's memory holds.
    n_layers: Optional[int] = None
    steps: int = 50
    batch: int = 8
    seq_len: int = 128
    ckpt_every: int = 20
    ckpt_dir: str = "/tmp/repro_ckpt"
    n_hosts: int = 4                    # fleet size this process acts as
    resume: bool = False
    async_ckpt: bool = False
    data_source: str = "synthetic"
    lr: float = 1e-3
    warmup: int = 20
    seed: int = 0
    remat: str = "none"
    log_every: int = 10
    # Fault injection: kill the run (raise) right AFTER this step's vote of
    # host 0 only — leaves the epoch in-flight for restart tests.
    die_mid_checkpoint_at: Optional[int] = None


@dataclass
class RunResult:
    losses: List[float] = field(default_factory=list)
    steps_done: int = 0
    restored_from: Optional[int] = None
    ckpt_outcomes: List = field(default_factory=list)
    wall_s: float = 0.0


class MidCheckpointCrash(RuntimeError):
    pass


def _hosts(n: int) -> List[str]:
    return [f"host{i}" for i in range(n)]


def model_config(run: RunConfig) -> mc.ModelConfig:
    """The model ``train(run)`` builds: the arch's config, reduced when
    ``use_smoke``, with depth cut to ``n_layers`` when that is set."""
    cfg = _arch_cfg(run.arch)
    if run.use_smoke:
        cfg = mc.smoke(cfg)
    if run.n_layers is not None:
        if run.n_layers < 1:
            raise ValueError(f"n_layers must be >= 1, got {run.n_layers}")
        cfg = dataclasses.replace(cfg, n_layers=run.n_layers)
    return cfg


def train_settings(run: RunConfig) -> S.TrainSettings:
    return S.TrainSettings(remat=run.remat,
                           opt=AdamWConfig(lr=run.lr, weight_decay=0.01),
                           warmup=run.warmup, stable=10**6, decay=1)


def jit_train_step(cfg: mc.ModelConfig, run: RunConfig):
    """The jitted step ``train(run)`` runs: fwd + bwd + AdamW, with params
    and optimizer state donated."""
    return jax.jit(S.make_train_step(cfg, train_settings(run)),
                   donate_argnums=(0, 1))


def train(run: RunConfig) -> RunResult:
    t_start = time.time()
    cfg = model_config(run)
    if run.data_source.startswith("bytes:"):
        assert cfg.vocab_size >= 256
    dcfg = DataConfig(batch=run.batch, seq_len=run.seq_len,
                      vocab_size=cfg.vocab_size, source=run.data_source,
                      seed=run.seed)
    pipeline = make_pipeline(dcfg)

    params = lm.init_model(cfg, jax.random.key(run.seed))
    opt_state = adamw_init(params, train_settings(run).opt)

    store = FileStore(run.ckpt_dir)
    hosts = _hosts(run.n_hosts)
    result = RunResult()
    start_step = 0

    if run.resume:
        epoch = latest_committed(store, hosts)
        if epoch is not None:
            full = {"params": params, "opt": {"m": opt_state["m"],
                                              "v": opt_state["v"]}}
            full = restore_params(store, hosts, epoch, full)
            params, opt_state["m"], opt_state["v"] = \
                full["params"], full["opt"]["m"], full["opt"]["v"]
            opt_state["count"] = jnp.asarray(epoch, jnp.int32)
            start_step = epoch
            result.restored_from = epoch

    train_step = jit_train_step(cfg, run)
    checkpointers = {h: CornusCheckpointer(store, h, hosts,
                                           straggler_timeout_s=10.0)
                     for h in hosts}
    async_ck = {h: AsyncCheckpointer(c) for h, c in checkpointers.items()} \
        if run.async_ckpt else None

    prefetch = Prefetcher(pipeline, start_step)
    try:
        for step in range(start_step, run.steps):
            with obs.span("step", step=step):
                with obs.span("data"):
                    got_step, batch = prefetch.get()
                assert got_step == step
                with obs.span("h2d"):
                    jbatch = {k: jnp.asarray(v) for k, v in batch.items()}
                    jstep = jnp.asarray(step, jnp.int32)
                with obs.span("train_step"):
                    params, opt_state, loss = train_step(
                        params, opt_state, jbatch, jstep)
                with obs.span("loss_sync"):
                    result.losses.append(float(loss))
            result.steps_done = step + 1
            if run.log_every and (step + 1) % run.log_every == 0:
                print(f"[train] step {step+1:5d} loss {float(loss):.4f}",
                      flush=True)

            if (step + 1) % run.ckpt_every == 0:
                outcome = _checkpoint(run, cfg, params, opt_state, step + 1,
                                      hosts, checkpointers, async_ck)
                if outcome is not None:
                    result.ckpt_outcomes.append(outcome)
    finally:
        prefetch.stop()
    if async_ck:
        for h in hosts:
            result.ckpt_outcomes.extend(async_ck[h].join())
    result.wall_s = time.time() - t_start
    return result


def _checkpoint(run, cfg, params, opt_state, epoch, hosts, checkpointers,
                async_ck):
    with obs.span("checkpoint", epoch=epoch):
        with obs.span("d2h", epoch=epoch) as sp:
            full = to_host({"params": params,
                            "opt": {"m": opt_state["m"], "v": opt_state["v"]}})
            sp.set(bytes=sum(leaf.nbytes
                             for leaf in jax.tree_util.tree_leaves(full)))
        with obs.span("partition", epoch=epoch):
            parts = partition_leaves(full, len(hosts))
        payloads = {}
        for h, keys in zip(hosts, parts):
            with obs.span("pack", epoch=epoch, host=h) as sp:
                payloads[h] = pack_tree(full, keys)
                sp.set(bytes=len(payloads[h]), copied=payloads[h].copied)

        if run.die_mid_checkpoint_at == epoch:
            # Crash after host0's vote only: the epoch is left UNDETERMINED
            # on storage.
            with obs.span("vote", epoch=epoch, host=hosts[0]):
                checkpointers[hosts[0]].vote(epoch, payloads[hosts[0]])
            raise MidCheckpointCrash(f"injected crash in epoch {epoch}")

        if async_ck is not None:
            for h in hosts:
                async_ck[h].save(epoch, payloads[h])
            return None
        # This process acts as the whole fleet: all hosts vote first (in a
        # real deployment these are concurrent), then the collective state
        # resolves.
        vote_ms = 0.0
        for h in hosts:
            with obs.span("vote", epoch=epoch, host=h) as voted:
                checkpointers[h].vote(epoch, payloads[h])
            vote_ms += voted.ms
        with obs.span("resolve", epoch=epoch) as resolved:
            decision, forced = checkpointers[hosts[0]].resolve(epoch)
        return CheckpointOutcome(epoch, decision, vote_ms=vote_ms,
                                 resolve_ms=resolved.ms, forced_aborts=forced)


def _arch_cfg(arch: str):
    from ..configs import get_config
    return get_config(arch)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--n-hosts", type=int, default=4)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--async-ckpt", action="store_true")
    ap.add_argument("--data", default="synthetic")
    ap.add_argument("--lr", type=float, default=1e-3)
    args = ap.parse_args(argv)
    enable_compile_cache()
    run = RunConfig(arch=args.arch, steps=args.steps, batch=args.batch,
                    seq_len=args.seq_len, ckpt_every=args.ckpt_every,
                    ckpt_dir=args.ckpt_dir, n_hosts=args.n_hosts,
                    resume=args.resume, async_ckpt=args.async_ckpt,
                    data_source=args.data, lr=args.lr)
    res = train(run)
    print(f"[train] done: {res.steps_done} steps, "
          f"final loss {res.losses[-1]:.4f}, "
          f"{len(res.ckpt_outcomes)} checkpoints, {res.wall_s:.1f}s")


if __name__ == "__main__":
    main()
