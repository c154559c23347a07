"""Pallas TPU kernel: RG-LRU blocked linear recurrence.

Grid = (B, num_chunks) with chunks sequential; the [1, W] hidden state
persists in VMEM scratch. Within a chunk the recurrence is evaluated by the
blocked two-pass form: for lane-width W the chunk does L sequential
vector FMAs (VPU), while the chunk-to-chunk handoff stays in VMEM — HBM
traffic is exactly one read of (a, bx) and one write of y.

Each step reads its gate and input row straight from the VMEM refs with a
``pl.ds`` slice and writes its output row the same way: Mosaic lowers a
dynamic ref slice, but not a dynamic index into a loaded value. A sequence
length that is not a multiple of the chunk is zero-padded at the end; the
padded steps only produce rows that are sliced off.

The gate matmuls (W×W) stay outside (XLA already MXU-pipelines them);
this kernel owns the part XLA serializes badly: the length-S dependence.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, b_ref, y_ref, h_ref, *, L: int):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    def step(t, h):
        a = a_ref[0, pl.ds(t, 1), :].astype(jnp.float32)     # [1, W]
        bx = b_ref[0, pl.ds(t, 1), :].astype(jnp.float32)
        h = a * h + bx
        y_ref[0, pl.ds(t, 1), :] = h.astype(y_ref.dtype)
        return h

    h_ref[...] = jax.lax.fori_loop(0, L, step, h_ref[...])


def _chunk_len(S: int, chunk: int) -> int:
    """One chunk spanning the whole sequence when it fits; otherwise
    ``chunk`` rounded up to the 8-row sublane tile, so every block of a
    multi-chunk grid is tile-aligned."""
    if S <= chunk:
        return S
    return -(-chunk // 8) * 8


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rglru_scan_pallas(a, bx, *, chunk: int = 128, interpret: bool = False):
    """a, bx: [B, S, W] → y [B, S, W] with y_t = a_t·y_{t−1} + bx_t."""
    B, S, W = a.shape
    L = _chunk_len(S, chunk)
    nc = -(-S // L)
    pad = nc * L - S
    if pad:
        a = jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
        bx = jnp.pad(bx, ((0, 0), (0, pad), (0, 0)))
    y = pl.pallas_call(
        functools.partial(_kernel, L=L),
        grid=(B, nc),
        in_specs=[pl.BlockSpec((1, L, W), lambda ib, ic: (ib, ic, 0)),
                  pl.BlockSpec((1, L, W), lambda ib, ic: (ib, ic, 0))],
        out_specs=pl.BlockSpec((1, L, W), lambda ib, ic: (ib, ic, 0)),
        out_shape=jax.ShapeDtypeStruct((B, nc * L, W), a.dtype),
        scratch_shapes=[pltpu.VMEM((1, W), jnp.float32)],
        interpret=interpret,
    )(a, bx)
    return y[:, :S] if pad else y
