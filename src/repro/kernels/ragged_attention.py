"""Pallas TPU kernel: fused ragged-batch paged attention.

One launch serves a whole mixed ``ScheduleBatch``: the step's query
tokens — prefill chunks of varying length and history, plus decode rows —
are flattened into a ragged ``(total_tokens, Hq, hd)`` layout with
per-token ``(row, pos)`` descriptors. The block-table row ids, the block
tables and the per-token positions all ride as scalar-prefetch (SMEM)
operands: the K/V BlockSpec index maps gather each tile's pages before
the body runs (same machinery as ``paged_decode_attention``), and the
positions drive the causal mask ``kpos <= pos[t]``, which makes history
length *dynamic* — no per-(chunk_len, hist_len) recompiles.

Each grid step loads one page with every KV head — a ``(1, bs, Hkv, hd)``
block whose last two dims equal the pool's, as the TPU tiling requires —
and the body walks the heads. The query tile is laid out head-major,
``(T/tile_q, Hkv, tile_q*G, hd)``, so a head's rows are one contiguous
``(tile_q*G, hd)`` slab (row ``r`` is token ``r // G`` of the tile).

Layout contract (enforced by the host wrapper): ``total_tokens`` is a
multiple of ``tile_q`` and every request's token span is ``tile_q``
aligned, so each q tile reads exactly one block-table row
(``row[it * tile_q]``). Pad tokens carry ``pos = -1`` → fully masked →
exactly zero output.

The int8 variant loads quantized pages plus their per-row scale/zero
pools and fuses the dequant into the K/V loads — the pools never hold a
dequantized copy.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.decode_attention import (_softmax_init, _softmax_out,
                                            _softmax_scratch, _softmax_step)

TILE_Q = 8


def _ragged_kernel(row_ref, bt_ref, pos_ref, q_ref, k_ref, v_ref, *rest,
                   scale: float, page_size: int, tile_q: int, group: int,
                   quantized: bool):
    if quantized:
        ks_ref, kz_ref, vs_ref, vz_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
    it = pl.program_id(0)
    ib = pl.program_id(1)
    nb = pl.num_programs(1)

    @pl.when(ib == 0)
    def _init():
        _softmax_init(m_scr, l_scr, acc_scr)

    kpos = ib * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, page_size), 1)                 # (1, bs)
    # per-row valid length: token at pos p attends kpos <= p, i.e.
    # kpos < p + 1; pad rows (pos = -1) mask everything
    rows = tile_q * group
    r = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    vlen = jnp.zeros((rows, 1), jnp.int32)
    for i in range(tile_q):
        tok = (r >= i * group) & (r < (i + 1) * group)
        vlen = jnp.where(tok, pos_ref[it * tile_q + i] + 1, vlen)
    for h in range(q_ref.shape[1]):                   # every KV head
        q = q_ref[0, h].astype(jnp.float32)           # (TQ*G, hd)
        k = k_ref[0, :, h, :].astype(jnp.float32)     # (bs, hd)
        v = v_ref[0, :, h, :].astype(jnp.float32)
        if quantized:
            # dequant fused into the K/V loads: pages are int8, scale and
            # zero f32 per (token, head)
            k = k * ks_ref[0, :, h:h + 1] + kz_ref[0, :, h:h + 1]
            v = v * vs_ref[0, :, h:h + 1] + vz_ref[0, :, h:h + 1]
        _softmax_step(q, k, v, kpos, vlen, m_scr, l_scr, acc_scr, h, scale)

    @pl.when(ib == nb - 1)
    def _finish():
        for h in range(q_ref.shape[1]):
            o_ref[0, h] = _softmax_out(l_scr, acc_scr, h).astype(o_ref.dtype)


def ragged_paged_attention(q, k_pages, v_pages, tables, row, pos, *,
                           kv_quant=None, scale: Optional[float] = None,
                           tile_q: int = TILE_Q, interpret: bool = False):
    """q (T,Hq,hd) ragged query tokens; pages (N,bs,Hkv,hd); tables (B,nb)
    int32 page ids; row (T,) table row per token; pos (T,) absolute
    position per token (-1 = pad) -> (T,Hq,hd).

    T must be a multiple of ``tile_q`` and ``row`` constant within each
    tile (the host flattener aligns request spans to ``tile_q``).
    ``kv_quant`` switches to the fused-dequant int8 variant."""
    t, hq, hd = q.shape
    n_pages, page_size, hkv, _ = k_pages.shape
    nb = tables.shape[1]
    group = hq // hkv
    assert t % tile_q == 0, f"T={t} not a multiple of tile_q={tile_q}"
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    nt = t // tile_q
    rows = tile_q * group

    # head-major q tiles: (nt, Hkv, TQ*G, hd), row r = token r // G
    qg = q.reshape(nt, tile_q, hkv, group, hd).transpose(0, 2, 1, 3, 4
                                                         ).reshape(
        nt, hkv, rows, hd)
    row32 = row.astype(jnp.int32)
    tables32 = tables.astype(jnp.int32)
    pos32 = pos.astype(jnp.int32)

    page = lambda it, ib, rw, bt, ps: (bt[rw[it * tile_q], ib], 0, 0, 0)
    tile = lambda it, ib, rw, bt, ps: (it, 0, 0, 0)
    in_specs = [
        pl.BlockSpec((1, hkv, rows, hd), tile),
        pl.BlockSpec((1, page_size, hkv, hd), page),
        pl.BlockSpec((1, page_size, hkv, hd), page),
    ]
    operands = [qg, k_pages, v_pages]
    if kv_quant is not None:
        qpage = lambda it, ib, rw, bt, ps: (bt[rw[it * tile_q], ib], 0, 0)
        in_specs += [pl.BlockSpec((1, page_size, hkv), qpage)] * 4
        operands += [kv_quant["k_scale"], kv_quant["k_zero"],
                     kv_quant["v_scale"], kv_quant["v_zero"]]

    kernel = functools.partial(_ragged_kernel, scale=scale,
                               page_size=page_size, tile_q=tile_q,
                               group=group, quantized=kv_quant is not None)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(nt, nb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, hkv, rows, hd), tile),
        scratch_shapes=_softmax_scratch(hkv, rows, hd),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nt, hkv, rows, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(row32, tables32, pos32, *operands)

    return out.reshape(nt, hkv, tile_q, group, hd).transpose(
        0, 2, 1, 3, 4).reshape(t, hq, hd)
