"""Pallas TPU kernels: GQA decode attention (flash-decoding).

Two variants share the online-softmax inner loop:

* ``decode_attention`` — slot-contiguous caches ``(B, S, Hkv, hd)``; grid
  (B, Hkv, nk) walks KV blocks sequentially with the softmax state in VMEM
  scratch. Per-sequence valid length ``kv_len`` masks the tail.
* ``paged_decode_attention`` — vLLM-style paged caches: a shared page pool
  ``(N, bs, Hkv, hd)`` addressed through a per-sequence block table
  ``(B, nb)``. The table is a scalar-prefetch operand so the K/V BlockSpec
  index maps gather the right page for each (sequence, step) before the
  kernel body runs. Each grid step loads one page with *every* KV head —
  a ``(1, bs, Hkv, hd)`` block, whose last two dims equal the pool's, as
  the TPU tiling requires — and the body walks the heads.

``kv_len`` rides as a scalar-prefetch (SMEM) operand in both: a ``(1, 1)``
VMEM block over a ``(B, 1)`` array is not a legal TPU block shape.
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


def _softmax_step(q, k, v, kpos, valid_len, m_scr, l_scr, acc_scr, h: int,
                  scale: float):
    """One online-softmax accumulation over a KV tile into the softmax
    state of head ``h`` (scratch index), shared by the contiguous, paged
    and ragged kernels. q (R,hd); k,v (kb,hd); kpos (1,kb) absolute token
    positions of the tile; valid_len a scalar or (R,1). Fully-masked
    tiles (ragged tails, kv_len==0 rows) contribute exactly zero."""
    mask = kpos < valid_len
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask, s, NEG_INF)                   # (R, kb)

    m_prev = m_scr[h]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_scr[h] = l_scr[h] * corr + p.sum(axis=-1, keepdims=True)
    acc_scr[h] = acc_scr[h] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[h] = m_new


def _softmax_init(m_scr, l_scr, acc_scr):
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)


def _softmax_out(l_scr, acc_scr, h: int):
    return acc_scr[h] / jnp.maximum(l_scr[h], 1e-30)


def _softmax_scratch(n_heads: int, rows: int, hd: int):
    """Per-head (m, l, acc) online-softmax state in VMEM."""
    return [pltpu.VMEM((n_heads, rows, 1), jnp.float32),
            pltpu.VMEM((n_heads, rows, 1), jnp.float32),
            pltpu.VMEM((n_heads, rows, hd), jnp.float32)]


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, kv_block: int):
    bi = pl.program_id(0)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        _softmax_init(m_scr, l_scr, acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)               # (G, hd)
    k = k_ref[0, 0].astype(jnp.float32)               # (kb, hd)
    v = v_ref[0, 0].astype(jnp.float32)
    kpos = ik * kv_block + jax.lax.broadcasted_iota(
        jnp.int32, (1, kv_block), 1)                  # (1, kb)
    _softmax_step(q, k, v, kpos, len_ref[bi], m_scr, l_scr, acc_scr, 0,
                  scale)

    @pl.when(ik == nk - 1)
    def _finish():
        o_ref[0, 0] = _softmax_out(l_scr, acc_scr, 0).astype(o_ref.dtype)


def decode_attention(q, k_cache, v_cache, kv_len, *,
                     scale: Optional[float] = None, kv_block: int = 512,
                     interpret: bool = False):
    """q (B,1,Hq,hd); caches (B,S,Hkv,hd); kv_len (B,) -> (B,1,Hq,hd)."""
    b, one, hq, hd = q.shape
    assert one == 1
    s, hkv = k_cache.shape[1], k_cache.shape[2]
    group = hq // hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    kb = min(kv_block, max(s, 8))
    s_p = -(-s // kb) * kb

    qg = q[:, 0].reshape(b, hkv, group, hd)           # (B,Hkv,G,hd)
    kt = jnp.pad(k_cache, ((0, 0), (0, s_p - s), (0, 0), (0, 0))
                 ).transpose(0, 2, 1, 3)              # (B,Hkv,S,hd)
    vt = jnp.pad(v_cache, ((0, 0), (0, s_p - s), (0, 0), (0, 0))
                 ).transpose(0, 2, 1, 3)
    lens = kv_len.astype(jnp.int32).reshape(b)

    kernel = functools.partial(_kernel, scale=scale, kv_block=kb)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, hkv, s_p // kb),
        in_specs=[
            pl.BlockSpec((1, 1, group, hd),
                         lambda bi, h, ik, ln: (bi, h, 0, 0)),
            pl.BlockSpec((1, 1, kb, hd), lambda bi, h, ik, ln: (bi, h, ik, 0)),
            pl.BlockSpec((1, 1, kb, hd), lambda bi, h, ik, ln: (bi, h, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, group, hd),
                               lambda bi, h, ik, ln: (bi, h, 0, 0)),
        scratch_shapes=_softmax_scratch(1, group, hd),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, group, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(lens, qg, kt, vt)

    return out.reshape(b, 1, hq, hd)


def _paged_kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, scale: float, page_size: int):
    bi = pl.program_id(0)
    ib = pl.program_id(1)
    nb = pl.num_programs(1)

    @pl.when(ib == 0)
    def _init():
        _softmax_init(m_scr, l_scr, acc_scr)

    kpos = ib * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, page_size), 1)                 # (1, bs)
    valid = len_ref[bi]
    for h in range(q_ref.shape[1]):                   # every KV head
        q = q_ref[0, h].astype(jnp.float32)           # (G, hd)
        k = k_ref[0, :, h, :].astype(jnp.float32)     # (bs, hd)
        v = v_ref[0, :, h, :].astype(jnp.float32)
        _softmax_step(q, k, v, kpos, valid, m_scr, l_scr, acc_scr, h, scale)

    @pl.when(ib == nb - 1)
    def _finish():
        for h in range(q_ref.shape[1]):
            o_ref[0, h] = _softmax_out(l_scr, acc_scr, h).astype(o_ref.dtype)


def paged_decode_attention(q, k_pages, v_pages, block_tables, kv_len, *,
                           scale: Optional[float] = None,
                           interpret: bool = False):
    """q (B,1,Hq,hd); pages (N,bs,Hkv,hd); block_tables (B,nb) int32 page
    ids; kv_len (B,) -> (B,1,Hq,hd).

    Rows of ``block_tables`` past a sequence's live length may hold any
    valid page id (conventionally 0): the ``kv_len`` mask zeroes their
    contribution.
    """
    b, one, hq, hd = q.shape
    assert one == 1
    n_pages, page_size, hkv, _ = k_pages.shape
    nb = block_tables.shape[1]
    group = hq // hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)

    qg = q[:, 0].reshape(b, hkv, group, hd)           # (B,Hkv,G,hd)
    lens = kv_len.astype(jnp.int32).reshape(b)
    tables = block_tables.astype(jnp.int32)

    kernel = functools.partial(_paged_kernel, scale=scale,
                               page_size=page_size)
    page = lambda bi, ib, bt, ln: (bt[bi, ib], 0, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, nb),
        in_specs=[
            pl.BlockSpec((1, hkv, group, hd),
                         lambda bi, ib, bt, ln: (bi, 0, 0, 0)),
            pl.BlockSpec((1, page_size, hkv, hd), page),
            pl.BlockSpec((1, page_size, hkv, hd), page),
        ],
        out_specs=pl.BlockSpec((1, hkv, group, hd),
                               lambda bi, ib, bt, ln: (bi, 0, 0, 0)),
        scratch_shapes=_softmax_scratch(hkv, group, hd),
    )

    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, group, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(tables, lens, qg, k_pages, v_pages)

    return out.reshape(b, 1, hq, hd)
