"""Pallas TPU flash-decode kernel: one query token vs a long KV cache.

TPU mapping
-----------
Decode attention is *memory-bound*: the whole KV cache (bytes ~ 2*S*Hkv*D)
streams HBM->VMEM once while compute is tiny, so the kernel's job is to keep
the streams dense and the online-softmax state resident in VMEM.

Grid ``(B, Hkv, num_kv_blocks)`` — kv sweep innermost/sequential. For each
(batch, kv-head) the ``g = Hq/Hkv`` grouped query heads form the MXU row
block: scores tile is ``(g_pad, block_k)`` where ``g_pad`` pads the GQA group
to the 8-row sublane minimum. Running (m, l, acc) live in fp32 VMEM scratch.
Ragged sequence lengths are masked via an iota compare against a per-batch
length scalar read from the SMEM-resident length vector.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(
    len_ref, q_ref, k_ref, v_ref, o_ref,
    m_scr, l_scr, acc_scr,
    *, scale: float, block_k: int,
):
    ki = pl.program_id(2)
    last_k = pl.num_programs(2) - 1

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[pl.program_id(0)]

    # Skip blocks entirely past the valid prefix (dense stream otherwise).
    @pl.when(ki * block_k < length)
    def _body():
        q = q_ref[0].astype(jnp.float32) * scale          # (g_pad, D)
        k = k_ref[0].astype(jnp.float32)                  # (bk, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                  # (g_pad, bk)
        kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos < length, s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[0].astype(jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[...] = m_new

    @pl.when(ki == last_k)
    def _finalize():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def decode_attention_kernel(
    q: jax.Array,         # (B*Hkv, g_pad, D)  grouped query heads
    k: jax.Array,         # (B*Hkv, Smax_pad, D)
    v: jax.Array,
    lengths: jax.Array,   # (B*Hkv,) int32
    *,
    scale: float,
    block_k: int = 512,
    interpret: bool = True,
) -> jax.Array:
    bh, g_pad, d = q.shape
    _, smax, _ = k.shape
    block_k = min(block_k, smax)
    grid = (bh, 1, smax // block_k)

    kernel = functools.partial(_decode_kernel, scale=scale, block_k=block_k)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            # whole (B*Hkv,) length vector in SMEM: Mosaic refuses rank-1
            # blocks narrower than the array's 128-wide tiling
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, g_pad, d), lambda b, qi, ki: (b, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, qi, ki: (b, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, g_pad, d), lambda b, qi, ki: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g_pad, 1), jnp.float32),
            pltpu.VMEM((g_pad, 1), jnp.float32),
            pltpu.VMEM((g_pad, d), jnp.float32),
        ],
        interpret=interpret,
    )(lengths, q, k, v)
