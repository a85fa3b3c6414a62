"""Flash-decode for TPU (Pallas): single-query attention over a long cache.

Decode reads ONE query token against a seq_len KV cache — the op is purely
memory-bound (arithmetic intensity ≈ 1 flop/byte), so the kernel's job is to
stream K/V through VMEM exactly once with fp32 online-softmax carries.

grid = (batch, q_heads, kv_blocks); kv innermost-sequential with VMEM
scratch (m, l, acc) — same carry discipline as flash_attention but with a
q tile of the GQA group size instead of a seq block.  kv_len masks the
valid prefix of the preallocated cache.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_KV = 512
NEG_INF = -1e30


def _kernel(qlen_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale, block_kv, n_kv_blocks, softcap):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32) * scale            # (g, hd)
    k = k_ref[0, 0].astype(jnp.float32)                    # (bk, hd)
    v = v_ref[0, 0].astype(jnp.float32)
    kv_len = qlen_ref[0]

    # fp32 contraction asked for, not left to Mosaic's default: decode is
    # bound by HBM bytes, not by the MXU passes this adds.
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)  # (g, bk)
    if softcap > 0:
        s = softcap * jnp.tanh(s / softcap)
    k_pos = ki * block_kv + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 1)
    s = jnp.where(k_pos < kv_len, s, NEG_INF)

    m_prev, l_prev = m_scr[...], l_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_new = l_prev * alpha + p.sum(axis=-1)
    acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ki == n_kv_blocks - 1)
    def _finish():
        o_ref[0, 0] = (acc_scr[...] /
                       jnp.maximum(l_scr[...], 1e-30)[:, None]
                       ).astype(o_ref.dtype)


def flash_decode(q, k, v, kv_len, *, softcap=0.0,
                 block_kv=DEFAULT_BLOCK_KV, interpret=False):
    """q: (B,Hq,1,hd)  k,v: (B,Hkv,T,hd)  kv_len: scalar int32.

    Returns (B,Hq,1,hd).  The GQA group (g = Hq/Hkv) rides in the q tile so
    the MXU sees a (g × hd)·(hd × bk) matmul per block.
    """
    B, Hq, one, hd = q.shape
    assert one == 1
    Hkv, T = k.shape[1], k.shape[2]
    g = Hq // Hkv
    block_kv = min(block_kv, T)
    n_kv = -(-T // block_kv)
    pad = n_kv * block_kv - T
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    # regroup q: (B, Hkv, g, hd)
    qg = q[:, :, 0].reshape(B, Hkv, g, hd)
    kv_len_arr = jnp.full((1,), kv_len, jnp.int32) if jnp.ndim(kv_len) == 0 \
        else kv_len.reshape(1).astype(jnp.int32)

    kernel = functools.partial(
        _kernel, scale=1.0 / math.sqrt(hd), block_kv=block_kv,
        n_kv_blocks=n_kv, softcap=softcap)

    out = pl.pallas_call(
        kernel,
        grid=(B, Hkv, n_kv),
        in_specs=[
            # kv_len is read as a scalar in the kernel body: Mosaic loads
            # scalars only from SMEM (an ANY ref would need an async copy).
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, g, hd), lambda b, h, ki: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_kv, hd), lambda b, h, ki: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, block_kv, hd), lambda b, h, ki: (b, h, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, hd), lambda b, h, ki: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, g, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g,), jnp.float32),
            pltpu.VMEM((g,), jnp.float32),
            pltpu.VMEM((g, hd), jnp.float32),
        ],
        interpret=interpret,
    )(kv_len_arr, qg.reshape(B, Hkv, g, hd), k, v)
    return out.reshape(B, Hq, 1, hd)
