"""Operations and bytes that the work needs, from shapes alone.

``cfg`` is a configuration file's dict (Hugging Face key names). Counts
are of what the algorithm needs, not of what an implementation happens
to move: a request's attention reads its own context and nothing past
it, and a model FLOP is a multiply-add of a weight the token passes
through (two operations), over the real vocabulary.
"""

from __future__ import annotations

from typing import Iterable


def matmul_params_per_token(cfg: dict) -> int:
    """Weights one token multiplies through: every layer's projections
    and MLP, and the head over the real vocabulary (the embedding is a
    lookup)."""
    d, ff, hd = cfg["hidden_size"], cfg["intermediate_size"], cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    per_layer = d * hq * hd + 2 * d * hkv * hd + hq * hd * d + 3 * d * ff
    return cfg["num_hidden_layers"] * per_layer + d * cfg["vocab_size"]


def attention_flops(cfg: dict, kv_len: int) -> int:
    """QK^T and PV of one query token over ``kv_len`` keys, one layer."""
    return 4 * kv_len * cfg["num_attention_heads"] * cfg["head_dim"]


def decode_step_flops(cfg: dict, kv_lens: Iterable[int]) -> int:
    """Model FLOPs of one decode step that advances one token for each
    request, given each request's context length after the step."""
    dense = 2 * matmul_params_per_token(cfg)
    layers = cfg["num_hidden_layers"]
    return sum(dense + layers * attention_flops(cfg, n) for n in kv_lens)


def paged_decode_call(cfg: dict, kv_lens: Iterable[int],
                      itemsize: int = 2) -> tuple:
    """(operations, bytes) one layer's decode attention needs: for each
    request, its query and output rows and the K and V rows of its own
    context, ``kv_len`` of them."""
    hq, hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    flops = nbytes = 0
    for n in kv_lens:
        flops += attention_flops(cfg, n)
        nbytes += (2 * n * hkv * hd + 2 * hq * hd) * itemsize
    return flops, nbytes


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> tuple:
    """Least time for the work on the chip, and which bound sets it."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
