"""Chunked SSD (Mamba-2) scan as a Pallas kernel.

The recurrent archs' compute hot spot (zamba2 backbone; same chunkwise
structure as the mLSTM).  The pure-jnp implementation (`models/ssm.py`)
materializes the (B, NC, L, S, H) decay tensor in HBM; this kernel keeps
everything chunk-local in VMEM: per grid step it loads one (L, P) x-tile and
its (L, N) B/C tiles, runs the quadratic intra-chunk form on the MXU, and
carries the (N, P) inter-chunk state in scratch across the sequential chunk
dimension — HBM traffic is exactly one pass over x/B/C/dt plus the y write.

Grid: (B*H, n_chunks), chunk dim innermost (sequential state carry).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import interpret_mode

F32 = jnp.float32


def _kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, y_ref, h_scr, *, n_chunks: int):
    c_idx = pl.program_id(1)

    @pl.when(c_idx == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    x = x_ref[0].astype(F32)            # (L, P)
    dt = dt_ref[0].astype(F32)          # (L,)
    bm = b_ref[0].astype(F32)           # (L, N)
    cm = c_ref[0].astype(F32)           # (L, N)
    a_h = a_ref[0, 0]                   # scalar A (negative)

    al = dt * a_h                       # (L,) <= 0
    cum = jnp.cumsum(al)                # (L,)
    l = x.shape[0]

    # intra-chunk quadratic form: y_i = sum_{j<=i} C_i.B_j e^{cum_i-cum_j} dt_j x_j
    dexp = cum[:, None] - cum[None, :]
    mask = jnp.tril(jnp.ones((l, l), bool))
    dexp = jnp.where(mask, dexp, -1e30)
    cb = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=F32)       # (L, L)
    w = cb * jnp.exp(dexp) * dt[None, :]
    y = jax.lax.dot_general(w, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=F32)        # (L, P)

    # inter-chunk contribution: y_i += e^{cum_i} C_i . H_prev
    y += jnp.exp(cum)[:, None] * jax.lax.dot_general(
        cm, h_scr[...], (((1,), (0,)), ((), ())),
        preferred_element_type=F32)

    # state update: H = e^{sum(a)} H_prev + sum_j e^{sum(a)-cum_j} dt_j B_j x_j^T
    wj = jnp.exp(cum[-1] - cum) * dt                           # (L,)
    h_new = (jnp.exp(cum[-1]) * h_scr[...]
             + jax.lax.dot_general(bm * wj[:, None], x,
                                   (((0,), (0,)), ((), ())),
                                   preferred_element_type=F32))
    h_scr[...] = h_new
    y_ref[0] = y.astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(
    x: jax.Array,      # (BH, T, P) head inputs (x * nothing pre-applied)
    dt: jax.Array,     # (BH, T) softplus'd step sizes
    b: jax.Array,      # (BH, T, N)
    c: jax.Array,      # (BH, T, N)
    a: jax.Array,      # (BH, 1) negative per-head decay
    chunk: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """Returns y (BH, T, P): the SSD sequence output (no D-skip, no gating)."""
    bh, t, p = x.shape
    n = b.shape[-1]
    assert t % chunk == 0, (t, chunk)
    n_chunks = t // chunk
    kernel = functools.partial(_kernel, n_chunks=n_chunks)
    return pl.pallas_call(
        kernel,
        grid=(bh, n_chunks),
        in_specs=[
            pl.BlockSpec((1, chunk, p), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, chunk), lambda i, j: (i, j)),
            pl.BlockSpec((1, chunk, n), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, chunk, n), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, p), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, t, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((n, p), F32)],
        interpret=interpret_mode(interpret),
    )(x, dt, b, c, a)
