"""Pallas TPU flash attention (prefill hot spot for the LM zoo).

Online-softmax attention with KV-block streaming; causal masking skips fully
masked KV blocks via grid predication. Layout: (batch*heads, seq, head_dim)
folded so the grid is (bh, q_blocks, kv_blocks) — GQA head broadcasting is
done by the caller (``ops.flash_mha``) so the kernel stays MHA-shaped.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["flash_attention"]

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                  *, scale: float, causal: bool, block_q: int, block_k: int,
                  kv_steps: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _body():
        q = q_ref[0]                                   # (bq, d)
        k = k_ref[0]                                   # (bk, d)
        v = v_ref[0]                                   # (bk, d)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_prev = m_scr[...]                            # (bq, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, -1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    if causal:
        # skip KV blocks strictly above the diagonal band
        pl.when(ki * block_k <= qi * block_q + block_q - 1)(_body)
    else:
        _body()

    @pl.when(ki == kv_steps - 1)
    def _finish():
        o_ref[0] = (acc_scr[...]
                    / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "block_q", "block_k", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False) -> jax.Array:
    """(BH, Sq, D) × (BH, Sk, D) → (BH, Sq, D), softmax(QKᵀ/√D)V."""
    bh, sq, d = q.shape
    _, sk, _ = k.shape
    assert sq % block_q == 0 and sk % block_k == 0, (sq, sk)
    scale = 1.0 / (d ** 0.5)
    kv_steps = sk // block_k
    grid = (bh, sq // block_q, kv_steps)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, kv_steps=kv_steps)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, qi, ki: (b, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
