"""Kernel dispatch layer.

Models call these wrappers; the backend is selected once per process:
  * 'pallas'     — real TPU kernels (pl.pallas_call, compiled)
  * 'interpret'  — same kernels, interpret=True (CPU correctness runs)
  * 'ref'        — blocked pure-jnp implementations (default on CPU; also
                   what the dry-run lowers, so the compiled HLO is flash-like)

Env knobs (read once, overridable via the setters):
  REPRO_KERNEL_BACKEND = pallas | interpret | ref
  REPRO_DECODE_MODE    = scatter | append | paged
  REPRO_ATTN_MODE      = masked_full | causal_skip
  REPRO_SANITIZE       = 1 | 0 — correctness tooling (analysis/): the
                         engine KV-lifecycle sanitizer and the Pallas
                         launch checker run on the traced kernel calls
"""

from __future__ import annotations

import os
from typing import Optional

import jax

from repro.kernels import ref as _ref

DECODE_MODES = ("scatter", "append", "paged")

_BACKEND = None
_ATTN_MODE = os.environ.get("REPRO_ATTN_MODE", "masked_full")
_DECODE_MODE = os.environ.get("REPRO_DECODE_MODE", "scatter")
_SANITIZE = os.environ.get("REPRO_SANITIZE", "0").lower() \
    not in ("", "0", "off", "false")
assert _ATTN_MODE in ("masked_full", "causal_skip"), \
    f"REPRO_ATTN_MODE={_ATTN_MODE!r}: want masked_full|causal_skip"
assert _DECODE_MODE in DECODE_MODES, \
    f"REPRO_DECODE_MODE={_DECODE_MODE!r}: want {'|'.join(DECODE_MODES)}"


def set_decode_mode(mode: str):
    global _DECODE_MODE
    assert mode in DECODE_MODES
    _DECODE_MODE = mode


def decode_mode() -> str:
    return _DECODE_MODE


def set_sanitize_mode(on: bool):
    global _SANITIZE
    _SANITIZE = bool(on)


def sanitize_mode() -> bool:
    return _SANITIZE


def set_attention_mode(mode: str):
    global _ATTN_MODE
    assert mode in ("masked_full", "causal_skip")
    _ATTN_MODE = mode


def attention_mode() -> str:
    return _ATTN_MODE


BACKENDS = ("pallas", "interpret", "ref")


def _check_backend(name: str) -> str:
    if name not in BACKENDS:
        raise ValueError(f"kernel backend {name!r}: want {'|'.join(BACKENDS)}")
    if name != "pallas" and jax.default_backend() == "tpu":
        raise RuntimeError(
            f"kernel backend {name!r} on a TPU would run the model without "
            "its Pallas kernels; unset REPRO_KERNEL_BACKEND or use 'pallas'")
    return name


def backend() -> str:
    """The process's kernel backend: ``REPRO_KERNEL_BACKEND`` if set, else
    ``pallas`` on a TPU and ``ref`` elsewhere. A TPU accepts only
    ``pallas``."""
    global _BACKEND
    if _BACKEND is None:
        forced = os.environ.get("REPRO_KERNEL_BACKEND")
        if forced:
            _BACKEND = _check_backend(forced)
        else:
            _BACKEND = "pallas" if jax.default_backend() == "tpu" else "ref"
    return _BACKEND


def set_backend(name: str):
    global _BACKEND
    _BACKEND = _check_backend(name)


def flash_attention(q, k, v, *, causal: bool = True, q_offset: int = 0,
                    kv_len=None, scale: Optional[float] = None,
                    q_block: int = 512, kv_block: int = 1024):
    """Prefill/train attention. q (B,Sq,Hq,hd); k,v (B,Sk,Hkv,hd)."""
    be = backend()
    if be in ("pallas", "interpret") and kv_len is None:
        from repro.kernels import flash_attention as _fa
        return _fa.flash_attention(
            q, k, v, causal=causal, q_offset=q_offset, scale=scale,
            interpret=(be == "interpret"))
    if q.shape[1] * k.shape[1] <= 1 << 20:   # tiny: naive is cheaper to trace
        return _ref.mha_reference(q, k, v, causal=causal, q_offset=q_offset,
                                  kv_len=kv_len, scale=scale)
    if causal and _ATTN_MODE == "causal_skip":
        return _ref.flash_attention_blocked_skip(
            q, k, v, q_offset=q_offset, kv_len=kv_len, scale=scale)
    return _ref.flash_attention_blocked(
        q, k, v, causal=causal, q_offset=q_offset, kv_len=kv_len,
        q_block=q_block, kv_block=kv_block, scale=scale)


def decode_attention(q, k_cache, v_cache, kv_len, *,
                     scale: Optional[float] = None, kv_block: int = 512):
    """Single-token decode vs long KV. q (B,1,Hq,hd); cache (B,S,Hkv,hd)."""
    be = backend()
    if be in ("pallas", "interpret"):
        from repro.kernels import decode_attention as _da
        return _da.decode_attention(q, k_cache, v_cache, kv_len, scale=scale,
                                    kv_block=kv_block,
                                    interpret=(be == "interpret"))
    return _ref.decode_attention_reference(q, k_cache, v_cache, kv_len,
                                           scale=scale)


def paged_decode_attention(q, k_pages, v_pages, block_tables, kv_len, *,
                           scale: Optional[float] = None):
    """Single-token decode against a paged KV pool. q (B,1,Hq,hd);
    pages (N,bs,Hkv,hd); block_tables (B,nb) page ids; kv_len (B,)."""
    be = backend()
    if _SANITIZE:
        from repro.analysis import kernelcheck
        kernelcheck.check_paged_decode(q, k_pages, v_pages, block_tables,
                                       kv_len, backend=be)
    if be in ("pallas", "interpret"):
        from repro.kernels import decode_attention as _da
        return _da.paged_decode_attention(
            q, k_pages, v_pages, block_tables, kv_len, scale=scale,
            interpret=(be == "interpret"))
    return _ref.paged_decode_attention_reference(
        q, k_pages, v_pages, block_tables, kv_len, scale=scale)


def ragged_paged_attention(q, k_pages, v_pages, tables, row, pos, *,
                           kv_quant=None, scale: Optional[float] = None,
                           tile_q: int = 8):
    """Fused ragged-batch attention over a paged pool: one launch serves a
    whole mixed prefill-chunk + decode step. q (T,Hq,hd) flattened query
    tokens; pages (N,bs,Hkv,hd); tables (B,nb); row (T,) table row per
    token; pos (T,) absolute position per token (-1 = pad). ``kv_quant``
    carries int8 pools' scale/zero leaves (dequant fused into the K/V
    loads)."""
    be = backend()
    if _SANITIZE:
        from repro.analysis import kernelcheck
        kernelcheck.check_ragged_paged(q, k_pages, v_pages, tables, row,
                                       pos, kv_quant=kv_quant,
                                       tile_q=tile_q, backend=be)
    if be in ("pallas", "interpret"):
        from repro.kernels import ragged_attention as _ra
        return _ra.ragged_paged_attention(
            q, k_pages, v_pages, tables, row, pos, kv_quant=kv_quant,
            scale=scale, tile_q=tile_q, interpret=(be == "interpret"))
    return _ref.ragged_paged_attention_reference(
        q, k_pages, v_pages, tables, row, pos, kv_quant=kv_quant,
        scale=scale)


def wkv6(r, k, v, w, u, initial_state=None, *, chunk: int = 64):
    """RWKV6 recurrence. r,k,v,w (B,T,H,hd); u (H,hd)."""
    be = backend()
    if be in ("pallas", "interpret"):
        from repro.kernels import wkv6 as _wkv
        return _wkv.wkv6(r, k, v, w, u, initial_state, chunk=chunk,
                         interpret=(be == "interpret"))
    return _ref.wkv6_chunked(r, k, v, w, u, initial_state, chunk=chunk)
