"""Pallas TPU kernel: staleness-compensated buffered aggregation (eq. 4).

    new_w[n] = w[n] + sum_m weights[m] * updates[m, n]

The server hot spot: at aggregation time the GS reduces a buffer of M
satellite updates (M up to the constellation size) over the flat model.
The reduction is memory-bound; we tile the parameter axis into VMEM blocks
and stream the (M, BN) update panel HBM->VMEM once, accumulating in f32.

Grid: (cdiv(N, BN),). BlockSpecs keep `weights` resident (it is tiny) and
march `updates`/`params` along the parameter axis; a partial last block is
masked on write, so no leaf is padded.

Block size: the whole buffer M sits in every panel, so BN is derived from M
(`block_for`). One (M, BN) f32 panel is capped at `PANEL_BYTES`; the kernel
holds about three of them (the double-buffered input and the f32 product),
which keeps it well inside v5e's 16 MiB default scoped VMEM at any M up to
several thousand. A fixed BN of 16,384 ran out of VMEM at M = 96 once a
leaf spanned more than one block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

PANEL_BYTES = 2 * 2**20         # one (M, BN) f32 update panel in VMEM
MAX_BLOCK = 16_384              # lane cap: (1, BN) rows pad to 8 sublanes
LANES, SUBLANES = 128, 8


def block_for(m: int, n: int) -> int:
    """Parameter block for an (M, N) update stack: the largest multiple of
    128 lanes whose sublane-padded f32 panel fits `PANEL_BYTES` (at least
    one lane tile), or the whole axis when N is smaller than that."""
    rows = -(-m // SUBLANES) * SUBLANES
    bn = PANEL_BYTES // (rows * 4) // LANES * LANES
    bn = min(max(bn, LANES), MAX_BLOCK)
    return n if n <= bn else bn


def _agg_kernel(w_ref, upd_ref, p_ref, out_ref):
    """w: (M, 1) f32; upd: (M, BN); p, out: (1, BN)."""
    upd = upd_ref[...].astype(jnp.float32)
    acc = jnp.sum(upd * w_ref[...], axis=0, keepdims=True)
    out_ref[...] = (p_ref[...].astype(jnp.float32) + acc).astype(
        out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def weighted_aggregate(params_flat, updates, weights, *, block=None,
                       interpret: bool = False):
    """params_flat: (N,), updates: (M, N), weights: (M,) -> (N,).

    `block` overrides the derived parameter block (a multiple of 128 when
    it is smaller than N)."""
    m, n = updates.shape
    bn = block_for(m, n) if block is None else min(block, n)
    out = pl.pallas_call(
        _agg_kernel,
        grid=(pl.cdiv(n, bn),),
        in_specs=[
            pl.BlockSpec((m, 1), lambda i: (0, 0)),          # weights
            pl.BlockSpec((m, bn), lambda i: (0, i)),         # updates panel
            pl.BlockSpec((1, bn), lambda i: (0, i)),         # params block
        ],
        out_specs=pl.BlockSpec((1, bn), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n), params_flat.dtype),
        interpret=interpret,
    )(weights.astype(jnp.float32)[:, None], updates, params_flat[None])
    return out[0]
