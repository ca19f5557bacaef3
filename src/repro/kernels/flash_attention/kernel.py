"""Pallas TPU kernel: causal / sliding-window flash attention with GQA.

Motivation (see EXPERIMENTS.md §Roofline): the baseline pure-JAX chunked
attention materializes (bq, T) f32 score panels in HBM every chunk — the
dominant memory-roofline term for the train/prefill shapes. This kernel
keeps the running softmax state (m, l, acc) in VMEM scratch and streams
K/V blocks HBM->VMEM once, so score traffic never touches HBM.

Grid: (B, H, nq, nk) — the trailing kv axis is sequential on TPU, so the
VMEM scratch accumulates across kv blocks and flushes to the output on the
last one. Block shapes default to (bq, hd) = (512, model hd) and bk = 512:
VMEM ~ bq*bk f32 scores + 2*bk*hd kv + bq*hd acc ≈ 1.6 MB at hd=128.

GQA: kv-head index = q-head // (H // K) via the BlockSpec index maps.
Masking: causal and sliding-window; fully-masked kv blocks are skipped with
pl.when (zero compute, zero traffic beyond the prefetch).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# The kernel computes in f32, as its oracle does. At the default precision
# Mosaic lowers an f32 dot to one bf16 MXU pass (an error near 1e-2 at
# unit scale); HIGHEST makes it the multi-pass f32 product.
F32 = jax.lax.Precision.HIGHEST


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  bq: int, bk: int, causal: bool, window: int, scale: float,
                  nk: int, seq_len: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = iq * bq
    k_start = ik * bk
    # block-level reachability: does any (q, k) pair in the tile attend?
    reachable = jnp.bool_(True)
    if causal:
        # newest q must be at or after the oldest key
        reachable = jnp.logical_and(reachable,
                                    k_start <= q_start + bq - 1)
    if window > 0:
        # oldest q must still be within the window of the newest key
        reachable = jnp.logical_and(
            reachable, q_start - (k_start + bk - 1) < window)

    @pl.when(reachable)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)              # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)              # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)              # (bk, hd)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), precision=F32,
            preferred_element_type=jnp.float32) * scale  # (bq, bk)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = kpos < seq_len
        if causal:
            mask = jnp.logical_and(mask, kpos <= qpos)
        if window > 0:
            mask = jnp.logical_and(mask, qpos - kpos < window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                              # (bq, 1)
        l_prev = l_scr[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                           # (bq, bk)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), precision=F32,
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(ik == nk - 1)
    def _flush():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "bq", "bk", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    bq: int = 512, bk: int = 512, interpret: bool = False):
    """q: (B, H, Sq, hd); k, v: (B, K, Sk, hd); H % K == 0.

    window = 0 means unwindowed. Returns (B, H, Sq, hd).
    """
    B, H, Sq, hd = q.shape
    K = k.shape[1]
    Sk = k.shape[2]
    assert H % K == 0
    G = H // K
    bq = min(bq, Sq)
    bk = min(bk, Sk)
    pad_q = (-Sq) % bq
    pad_k = (-Sk) % bk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    nq = q.shape[2] // bq
    nk = k.shape[2] // bk
    grid = (B, H, nq, nk)
    kern = functools.partial(
        _flash_kernel, bq=bq, bk=bk, causal=causal, window=window,
        scale=hd ** -0.5, nk=nk, seq_len=Sk)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    if pad_q:
        out = out[:, :, :Sq]
    return out
