"""Helpers for the tests that drive whole runs on the CPU at the smoke
size, with the harness's look for a chip skipped."""
from __future__ import annotations

import time

import jax
import pytest

from chipbench import archs, run, smoke


@pytest.fixture
def cpu_run(monkeypatch, tmp_path):
    """Drive ``run.measure`` for a cell at the smoke size on the CPU, cut by
    the architecture its configuration names.  The persistent compilation
    cache stays off (its directory is named by the environment after JAX has
    read it), so nothing outlives the test."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    old = jax.config.jax_persistent_cache_min_compile_time_secs

    def go(name: str, seed: int = 2 ** 32 + 17, seconds: float = 0.5):
        bench, cell, cfg, _traffic = run.load_cell(name)
        return run.measure(bench, cell, archs.load(cfg).smoke(cfg),
                           smoke.traffic(cell["traffic"]), seed=seed,
                           seconds=seconds, trace=False,
                           devices=jax.devices(), t_start=time.perf_counter())

    yield go
    jax.config.update("jax_persistent_cache_min_compile_time_secs", old)
