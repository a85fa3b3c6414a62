"""Compile the device path for a TPU v5e that is described, not attached.

The TPU compiler is installed wherever jax[tpu] is, so these tests run
without a chip: they lower and compile for one chip of a described
``v5e:2x2`` topology, and fail where the chip's compiler would refuse the
program (a kernel Mosaic cannot lower, a step that does not fit HBM).
Nothing runs, so they say nothing about results or times.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and under pytest-xdist
every worker imports every test file.  Keep every such test in this file,
so that one worker owns the library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.decode_attention import flash_decode
from repro.launch.train import RunConfig, jit_train_step, model_config, \
    train_settings
from repro.models import config as mc
from repro.models import lm
from repro.optim import adamw_init

LLAMA = "llama3.2-1b"


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _on(tree, sharding):
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _compile_decode(sharding, B, Hq, Hkv, hd, T, block_kv, dtype):
    q = jax.ShapeDtypeStruct((B, Hq, 1, hd), dtype, sharding=sharding)
    kv = jax.ShapeDtypeStruct((B, Hkv, T, hd), dtype, sharding=sharding)
    kv_len = jax.ShapeDtypeStruct((), jnp.int32, sharding=sharding)
    fn = jax.jit(lambda q, k, v, n: flash_decode(q, k, v, n,
                                                 block_kv=block_kv))
    return fn.lower(q, kv, kv, kv_len).compile()


def test_flash_decode_compiles_at_pool_default_widths(one_chip):
    # PallasDecode's defaults: 4 query heads over 2 KV heads, head_dim 64,
    # a 256-token pool, 128-row KV blocks; batch 8.
    compiled = _compile_decode(one_chip, B=8, Hq=4, Hkv=2, hd=64, T=256,
                               block_kv=128, dtype=jnp.float32)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_flash_decode_compiles_at_llama_decode_widths(one_chip, dtype):
    cfg = get_config(LLAMA)
    compiled = _compile_decode(one_chip, B=8, Hq=cfg.n_heads,
                               Hkv=cfg.n_kv_heads, hd=cfg.hd, T=2048,
                               block_kv=128, dtype=dtype)
    assert "tpu_custom_call" in compiled.as_text()


def test_train_step_compiles_and_fits_one_chip(one_chip):
    """llama3.2-1b at its published widths and vocabulary, cut to the smoke
    config's depth: the donated train step ``train()`` runs must compile
    for one v5e chip, which includes fitting its HBM."""
    depth = mc.smoke(get_config(LLAMA)).n_layers
    run = RunConfig(arch=LLAMA, use_smoke=False, n_layers=depth, batch=8,
                    seq_len=128)
    cfg = model_config(run)
    params = jax.eval_shape(lambda: lm.init_model(cfg, jax.random.key(0)))
    opt = jax.eval_shape(lambda p: adamw_init(p, train_settings(run).opt),
                         params)
    batch = {k: jax.ShapeDtypeStruct((run.batch, run.seq_len), jnp.int32)
             for k in ("tokens", "labels")}
    step = jax.ShapeDtypeStruct((), jnp.int32)
    compiled = jit_train_step(cfg, run).lower(
        _on(params, one_chip), _on(opt, one_chip), _on(batch, one_chip),
        _on(step, one_chip)).compile()
    ma = compiled.memory_analysis()
    # Donation: the new params and moments reuse the argument buffers.
    assert ma.alias_size_in_bytes > 0.9 * ma.argument_size_in_bytes
