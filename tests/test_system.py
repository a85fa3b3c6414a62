"""System-level behaviour tests: the three layers compose.

(The per-layer suites live in test_protocol_properties / test_txn_bench /
test_arch_smoke / test_kernels / test_ckpt_commit / test_train_loop; this
file asserts the cross-layer contracts.)
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (AZURE_BLOB, AZURE_REDIS, Cluster, Decision,
                        ProtocolConfig, Sim, SimStorage, TxnSpec,
                        predicted_caller_latency_ms, rtt_table)


def commit_latency(proto: str, model, n=4, seed=0):
    sim = Sim()
    cluster = Cluster(sim, SimStorage(sim, model, seed=seed),
                      [f"n{i}" for i in range(n)],
                      ProtocolConfig(protocol=proto))
    done = cluster.run_txn(TxnSpec(
        txn_id="t", coordinator="n0",
        participants=[f"n{i}" for i in range(n)]))
    sim.run(until=10_000)
    return done.value


def test_cornus_eliminates_commit_phase():
    """The paper's core mechanism: caller latency = prepare phase only."""
    for model in (AZURE_REDIS, AZURE_BLOB):
        c = commit_latency("cornus", model)
        t = commit_latency("2pc", model)
        assert c.decision == t.decision == Decision.COMMIT
        assert c.commit_ms < 0.01, "Cornus must not log a decision"
        assert t.commit_ms > model.plain_write_ms * 0.8
        # Commit-level speedup approaches the Table-3 5/3 ratio as storage
        # latency dominates the 0.5ms RTT.
        ratio = t.caller_latency_ms / c.caller_latency_ms
        assert 1.3 < ratio < 2.2, ratio


def test_table3_consistency_with_simulator():
    """The analytic RTT model and the simulator agree on the 2PC/Cornus gap
    when one 'Paxos RTT' equals one storage write."""
    rows = rtt_table()
    assert rows["2pc"]["total"] / rows["cornus"]["total"] == pytest.approx(
        5.0 / 3.0)
    assert predicted_caller_latency_ms("cornus", 10.0) == 30.0


def test_roofline_reader_on_artifacts():
    """benchmarks.roofline parses whatever dry-run artifacts exist."""
    import os
    if not os.path.isdir("artifacts/dryrun"):
        pytest.skip("no dry-run artifacts in this checkout")
    from benchmarks.roofline import load_cells
    cells = load_cells("artifacts/dryrun")
    assert len(cells) >= 1
    ok = [c for c in cells if not c.skipped and not c.error]
    assert ok, "no successful cells recorded"
    for c in ok:
        assert c.compute_s >= 0 and c.memory_s >= 0 and c.collective_s >= 0
        assert c.bottleneck in ("compute", "memory", "collective")


def test_dryrun_lowering_path_smoke():
    """The dry-run machinery (input_specs -> jit -> lower -> compile ->
    cost/collective extraction) works on a 1-device mesh with a smoke
    config — the 512-device run just changes the mesh."""
    from repro.configs import get_config
    from repro.launch import steps as S
    from repro.launch.dryrun import cost_dict, parse_collectives
    from repro.launch.mesh import make_host_mesh
    from repro.launch.sharding import Rules
    from repro.models.config import ShapeConfig, smoke

    mesh = make_host_mesh()
    rules = Rules(mesh)
    cfg = smoke(get_config("llama3.2-1b"))
    shape = ShapeConfig("tiny_train", seq_len=32, global_batch=2,
                        kind="train")
    settings = S.TrainSettings(remat="dots")
    specs = S.input_specs(cfg, shape, rules, settings)
    fn = S.make_train_step(cfg, settings, rules)
    with mesh:
        compiled = jax.jit(fn).lower(specs["params"], specs["opt_state"],
                                     specs["batch"], specs["step"]).compile()
    ca = cost_dict(compiled)
    assert ca["flops"] > 1e6
    coll = parse_collectives(compiled.as_text())
    assert set(coll) == {"all-reduce", "all-gather", "reduce-scatter",
                         "all-to-all", "collective-permute"}


def test_grad_compression_roundtrip_and_error_feedback():
    from repro.optim import (CompressionConfig, compress_gradients,
                             decompress_gradients, error_feedback_update)
    rng = np.random.RandomState(0)
    grads = {"a": jnp.asarray(rng.randn(64, 32).astype(np.float32)),
             "b": jnp.asarray(rng.randn(128).astype(np.float32) * 1e-3)}
    ccfg = CompressionConfig()
    q, s, pre = compress_gradients(grads, ccfg)
    deq = decompress_gradients(q, s)
    for k in grads:
        assert q[k].dtype == jnp.int8
        rel = float(jnp.max(jnp.abs(deq[k] - grads[k])) /
                    jnp.max(jnp.abs(grads[k])))
        assert rel < 0.02, f"{k}: int8 error {rel}"
    # error feedback: residual + dequantized == original
    resid = error_feedback_update(pre, deq)
    for k in grads:
        np.testing.assert_allclose(np.asarray(deq[k] + resid[k]),
                                   np.asarray(grads[k]), rtol=1e-5,
                                   atol=1e-6)


def test_data_pipeline_stateless_resume():
    from repro.data import DataConfig, SyntheticTokens
    cfg = DataConfig(batch=4, seq_len=16, vocab_size=100, seed=5)
    a = SyntheticTokens(cfg).batch_at(37)
    b = SyntheticTokens(cfg).batch_at(37)   # fresh instance, same step
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    c = SyntheticTokens(cfg).batch_at(38)
    assert not np.array_equal(a["tokens"], c["tokens"])


def test_wsd_schedule_shape():
    from repro.optim import wsd_schedule
    mult = [float(wsd_schedule(s, warmup=10, stable=50, decay=20))
            for s in (0, 5, 10, 40, 60, 70, 80, 200)]
    assert mult[0] == 0.0 and mult[1] == pytest.approx(0.5)
    assert mult[2] == mult[3] == 1.0       # stable plateau
    assert mult[4] == 1.0                   # decay starts at 60
    assert 0.1 <= mult[5] < 1.0
    assert mult[7] == pytest.approx(0.1)    # decayed to final_frac


def test_run_config_cuts_depth_and_keeps_widths():
    from repro.configs import get_config
    from repro.launch.train import RunConfig, model_config
    full = get_config("llama3.2-1b")
    cut = model_config(RunConfig(arch="llama3.2-1b", use_smoke=False,
                                 n_layers=4))
    assert cut.n_layers == 4
    assert cut == dataclasses.replace(full, n_layers=4)
    assert model_config(RunConfig(use_smoke=False)) == full
    with pytest.raises(ValueError):
        model_config(RunConfig(n_layers=0))


_CACHE_PROBE = """
import os
import jax, jax.numpy as jnp
from repro.launch.compile_cache import enable_compile_cache
got = enable_compile_cache()
print(got)
print(jax.config.jax_compilation_cache_dir)
if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()
"""


@pytest.mark.parametrize("from_env", [True, False], ids=["env", "default"])
def test_compile_cache_directory(tmp_path, from_env):
    """With JAX_COMPILATION_CACHE_DIR set, JAX's own reading of it stands
    and entries land there; without it the cache is the fixed
    ``<checkout>/.jax_cache``."""
    import os
    import subprocess
    import sys

    from repro.launch.compile_cache import CHECKOUT
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(CHECKOUT, "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    want = str(tmp_path / "cache")
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = want
    else:
        want = os.path.join(CHECKOUT, ".jax_cache")
    out = subprocess.run([sys.executable, "-c", _CACHE_PROBE], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == [want, want]
    if from_env:
        assert os.listdir(want), "no cache entry written"
