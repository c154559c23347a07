"""Pallas TPU kernel: one fused Sinkhorn iteration over the cost matrix.

The WaterWise MILP's TPU-native solver (DESIGN.md §4) runs log-domain
Sinkhorn on the [M jobs × N regions] cost matrix. M can reach tens of
thousands in a burst window (Alibaba trace: 8.5× Borg rate), N stays small
(regions). One iteration is

    f_i ← ε·(log aᵢ − LSE_j (g_j − C_ij)/ε)        (row update)
    g_j ← ε·(log b_j − LSE_i (f_i − C_ij)/ε)        (col update)

Fused single pass: grid over M row-blocks (sequential); each step computes
its f tile (row LSE over the in-VMEM [bm, N] cost tile) and accumulates the
column LSE online (running max + rescaled sum in scratch, flash-attention
style), finalizing g on the last block. C is streamed through VMEM exactly
once per iteration — the HBM-optimal schedule.

N is lane-padded to 128; padding columns are masked with −∞ contributions.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _kernel(C_ref, g_ref, loga_ref, logb_ref, f_ref, gout_ref,
            m_ref, s_ref, *, eps: float, n_true: int, bm: int, nm: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        s_ref[...] = jnp.zeros_like(s_ref)

    C = C_ref[...].astype(jnp.float32)                    # [bm, Np]
    Np = C.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (bm, Np), 1)
    valid = lane < n_true

    # Row update: f tile from fixed g.
    z = jnp.where(valid, (g_ref[0] - C) / eps, NEG)
    zmax = z.max(axis=1)
    lse = zmax + jnp.log(jnp.sum(jnp.exp(z - zmax[:, None]), axis=1))
    f = eps * (loga_ref[0, :] - lse)
    f_ref[0, :] = f

    # Column accumulation: online LSE of (f_i − C_ij)/ε over all row blocks.
    w = jnp.where(valid, (f[:, None] - C) / eps, NEG)     # [bm, Np]
    m_prev = m_ref[0, :]
    m_new = jnp.maximum(m_prev, w.max(axis=0))
    s_ref[0, :] = (s_ref[0, :] * jnp.exp(m_prev - m_new)
                   + jnp.sum(jnp.exp(w - m_new[None, :]), axis=0))
    m_ref[0, :] = m_new

    @pl.when(i == nm - 1)
    def _finalize():
        lse_col = m_ref[0, :] + jnp.log(jnp.maximum(s_ref[0, :], 1e-30))
        gout_ref[0, :] = eps * (logb_ref[0, :] - lse_col)


@functools.partial(jax.jit, static_argnames=("eps", "bm", "interpret"))
def sinkhorn_iteration_pallas(C, g, log_a, log_b, *, eps: float,
                              bm: int = 256, interpret: bool = False):
    """C: [M, N]; g/log_b: [N]; log_a: [M]. Returns (f [M], g_new [N])."""
    M, N = C.shape
    Np = 128 * ((N + 127) // 128)
    bm = min(bm, M)
    assert M % bm == 0, (M, bm)
    nm = M // bm
    Cp = jnp.pad(C.astype(jnp.float32), ((0, 0), (0, Np - N)),
                 constant_values=0.0)
    gp = jnp.pad(g.astype(jnp.float32), (0, Np - N), constant_values=NEG)
    lbp = jnp.pad(log_b.astype(jnp.float32), (0, Np - N),
                  constant_values=NEG)

    kernel = functools.partial(_kernel, eps=float(eps), n_true=N, bm=bm,
                               nm=nm)
    f, g_new = pl.pallas_call(
        kernel,
        grid=(nm,),
        in_specs=[
            pl.BlockSpec((bm, Np), lambda i: (i, 0)),
            pl.BlockSpec((1, Np), lambda i: (0, 0)),
            pl.BlockSpec((1, bm), lambda i: (0, i)),
            pl.BlockSpec((1, Np), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bm), lambda i: (0, i)),
            pl.BlockSpec((1, Np), lambda i: (0, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((1, M), jnp.float32),
                   jax.ShapeDtypeStruct((1, Np), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((1, Np), jnp.float32),
                        pltpu.VMEM((1, Np), jnp.float32)],
        interpret=interpret,
        name="sinkhorn_iteration_pallas",
    )(Cp, gp[None], log_a[None].astype(jnp.float32), lbp[None])
    return f[0], g_new[0, :N]
