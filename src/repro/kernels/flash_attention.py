"""Pallas TPU kernel: causal GQA flash attention (prefill / train).

Grid (B, Hq, nq, nk); the nk axis is the sequential ("arbitrary") dimension
with the online-softmax running state (m, l, acc) held in VMEM scratch.
Blocks are MXU-aligned (default 128x128); K/V index maps fold GQA by
mapping query head h to KV head h // group.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, q_block: int, kv_block: int,
            seq_q: int, seq_kv: int, q_offset: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)          # (qb, hd)
    k = k_ref[0, 0].astype(jnp.float32)          # (kb, hd)
    v = v_ref[0, 0].astype(jnp.float32)

    qpos = (iq * q_block + q_offset
            + jax.lax.broadcasted_iota(jnp.int32, (q_block, kv_block), 0))
    kpos = (ik * kv_block
            + jax.lax.broadcasted_iota(jnp.int32, (q_block, kv_block), 1))
    mask = kpos < seq_kv                          # kv padding
    if causal:
        mask = mask & (kpos <= qpos)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + p.sum(axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, q_offset: int = 0,
                    scale: Optional[float] = None,
                    q_block: int = 128, kv_block: int = 128,
                    interpret: bool = False):
    """q (B,Sq,Hq,hd); k,v (B,Sk,Hkv,hd) -> (B,Sq,Hq,hd)."""
    b, sq, hq, hd = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    group = hq // hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    qb = min(q_block, max(sq, 8))
    kb = min(kv_block, max(sk, 8))

    sq_p = -(-sq // qb) * qb
    sk_p = -(-sk // kb) * kb
    qt = jnp.pad(q, ((0, 0), (0, sq_p - sq), (0, 0), (0, 0))
                 ).transpose(0, 2, 1, 3)          # (B,Hq,Sq,hd)
    kt = jnp.pad(k, ((0, 0), (0, sk_p - sk), (0, 0), (0, 0))
                 ).transpose(0, 2, 1, 3)          # (B,Hkv,Sk,hd)
    vt = jnp.pad(v, ((0, 0), (0, sk_p - sk), (0, 0), (0, 0))
                 ).transpose(0, 2, 1, 3)

    grid = (b, hq, sq_p // qb, sk_p // kb)

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, q_block=qb, kv_block=kb,
        seq_q=sq, seq_kv=sk, q_offset=q_offset)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, qb, hd), lambda bi, h, iq, ik: (bi, h, iq, 0)),
            pl.BlockSpec((1, 1, kb, hd),
                         lambda bi, h, iq, ik, g=group: (bi, h // g, ik, 0)),
            pl.BlockSpec((1, 1, kb, hd),
                         lambda bi, h, iq, ik, g=group: (bi, h // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, qb, hd),
                               lambda bi, h, iq, ik: (bi, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq_p, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((qb, 1), jnp.float32),
            pltpu.VMEM((qb, 1), jnp.float32),
            pltpu.VMEM((qb, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        metadata={"kernel": "flash_attention"},
    )(qt, kt, vt)

    return out.transpose(0, 2, 1, 3)[:, :sq]
