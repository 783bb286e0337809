"""Pallas TPU kernel: WKV6 (RWKV6 'Finch') recurrence, chunked over time.

Grid (B*H, nT): the time axis is sequential; the (hd, hd) state lives in
VMEM scratch across chunks. Inside a chunk a fori_loop applies the rank-1
recurrence per step:

    y_t = r_t @ S + (sum(r_t * u * k_t)) * v_t
    S   = exp(-exp(w_t))[:, None] * S + k_t^T v_t
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref,
            y_ref, sT_ref, state_scr, *, chunk: int):
    it = pl.program_id(1)
    nt = pl.num_programs(1)

    @pl.when(it == 0)
    def _init():
        state_scr[...] = s0_ref[0].astype(jnp.float32)

    r = r_ref[0].astype(jnp.float32)          # (ct, hd)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    w = w_ref[0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)          # (1, hd)
    decay = jnp.exp(-jnp.exp(w))              # (ct, hd)

    S0 = state_scr[...]

    def step(t, carry):
        S, ys = carry
        rt = jax.lax.dynamic_slice_in_dim(r, t, 1, 0)      # (1, hd)
        kt = jax.lax.dynamic_slice_in_dim(k, t, 1, 0)
        vt = jax.lax.dynamic_slice_in_dim(v, t, 1, 0)
        dt = jax.lax.dynamic_slice_in_dim(decay, t, 1, 0)
        y = jax.lax.dot_general(rt, S, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        bonus = jnp.sum(rt * u * kt, axis=-1, keepdims=True)  # (1,1)
        y = y + bonus * vt
        S_new = dt.T * S + kt.T @ vt
        ys = jax.lax.dynamic_update_slice_in_dim(ys, y, t, 0)
        return S_new, ys

    S, ys = jax.lax.fori_loop(
        0, chunk, step, (S0, jnp.zeros((chunk, r.shape[1]), jnp.float32)))
    state_scr[...] = S
    y_ref[0] = ys.astype(y_ref.dtype)

    @pl.when(it == nt - 1)
    def _finish():
        sT_ref[0] = state_scr[...]


def wkv6(r, k, v, w, u, initial_state=None, *, chunk: int = 64,
         interpret: bool = False):
    """r,k,v,w (B,T,H,hd); u (H,hd); initial_state (B,H,hd,hd) fp32.
    Returns (y (B,T,H,hd), final_state (B,H,hd,hd))."""
    b, t, h, n = r.shape
    ct = min(chunk, max(t, 1))
    t_p = -(-t // ct) * ct
    bh = b * h

    def prep(x, pad_value=0.0):
        x = jnp.pad(x, ((0, 0), (0, t_p - t), (0, 0), (0, 0)),
                    constant_values=pad_value)
        return x.transpose(0, 2, 1, 3).reshape(bh, t_p, n)

    rr, kk, vv = prep(r), prep(k), prep(v)
    # padded steps must leave the state unchanged: decay=1 <= w -> -inf,
    # and contribute nothing: k row = 0 (handled since k pads with 0)
    ww = prep(w, pad_value=-1e9)
    uu = jnp.broadcast_to(u[None], (b, h, n)).reshape(bh, 1, n)
    if initial_state is None:
        initial_state = jnp.zeros((b, h, n, n), jnp.float32)
    s0 = initial_state.reshape(bh, n, n).astype(jnp.float32)

    grid = (bh, t_p // ct)
    kernel = functools.partial(_kernel, chunk=ct)

    y, sT = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, ct, n), lambda i, it: (i, it, 0)),
            pl.BlockSpec((1, ct, n), lambda i, it: (i, it, 0)),
            pl.BlockSpec((1, ct, n), lambda i, it: (i, it, 0)),
            pl.BlockSpec((1, ct, n), lambda i, it: (i, it, 0)),
            pl.BlockSpec((1, 1, n), lambda i, it: (i, 0, 0)),
            pl.BlockSpec((1, n, n), lambda i, it: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, ct, n), lambda i, it: (i, it, 0)),
            pl.BlockSpec((1, n, n), lambda i, it: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t_p, n), r.dtype),
            jax.ShapeDtypeStruct((bh, n, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(rr, kk, vv, ww, uu, s0)

    y = y.reshape(b, h, t_p, n).transpose(0, 2, 1, 3)[:, :t]
    return y, sT.reshape(b, h, n, n)
