"""A fixture architecture for the harness's CPU tests: a decoder whose
period holds two attention slots, the first with a dense SwiGLU MLP and
the second with the program's routed ``moe`` MLP, stacked over
``periods``. Its configuration (``two_slot.json``) names its sizes by
keys of its own, so a generic harness file that read a key of the dense
configurations would fail on it. It gives what the harness asks of an
architecture module, and no reference forward."""

from __future__ import annotations

from typing import Iterable, List

from bench.lib import weights
from bench.lib.weights import Leaf


def model_config(cfg: dict):
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name=cfg["name"], family="moe", n_layers=2 * cfg["periods"],
        d_model=cfg["width"], n_heads=cfg["q_heads"],
        n_kv_heads=cfg["kv_heads"], head_dim=cfg["head_width"],
        d_ff=cfg["mlp_width"], vocab=cfg["vocab_size"],
        n_experts=cfg["experts"], top_k=cfg["experts_per_token"],
        expert_d_ff=cfg["expert_width"], mixer_pattern=("attn", "attn"),
        mlp_pattern=("dense", "moe"), dtype=cfg["torch_dtype"])


def _attention(cfg: dict, slot: tuple) -> List[Leaf]:
    d, n, hd = cfg["width"], cfg["periods"], cfg["head_width"]
    q, kv = cfg["q_heads"] * hd, cfg["kv_heads"] * hd
    return [Leaf(slot + ("mixer", "w_q"), (d, q), n, d ** -0.5),
            Leaf(slot + ("mixer", "w_k"), (d, kv), n, d ** -0.5),
            Leaf(slot + ("mixer", "w_v"), (d, kv), n, d ** -0.5),
            Leaf(slot + ("mixer", "w_o"), (q, d), n, q ** -0.5),
            Leaf(slot + ("mixer", "norm"), (d,), n, 0.0)]


def layout(cfg: dict) -> List[Leaf]:
    d, n, ff = cfg["width"], cfg["periods"], cfg["mlp_width"]
    e, eff = cfg["experts"], cfg["expert_width"]
    vp = weights.padded_vocab(cfg["vocab_size"])
    s0, s1 = ("blocks", "slot00"), ("blocks", "slot01")
    return [
        Leaf(("embed", "tok"), (vp, d), 0, 1.0),
        *_attention(cfg, s0),
        Leaf(s0 + ("mlp", "w_gate"), (d, ff), n, d ** -0.5),
        Leaf(s0 + ("mlp", "w_up"), (d, ff), n, d ** -0.5),
        Leaf(s0 + ("mlp", "w_down"), (ff, d), n, ff ** -0.5),
        Leaf(s0 + ("mlp", "norm"), (d,), n, 0.0),
        *_attention(cfg, s1),
        Leaf(s1 + ("mlp", "router"), (d, e), n, d ** -0.5),
        Leaf(s1 + ("mlp", "w_gate"), (e, d, eff), n, d ** -0.5),
        Leaf(s1 + ("mlp", "w_up"), (e, d, eff), n, d ** -0.5),
        Leaf(s1 + ("mlp", "w_down"), (e, eff, d), n, eff ** -0.5),
        Leaf(s1 + ("mlp", "norm"), (d,), n, 0.0),
        Leaf(("final_norm",), (d,), 0, 0.0),
        Leaf(("lm_head",), (d, vp), 0, d ** -0.5),
    ]


def _attention_layers(cfg: dict) -> int:
    return 2 * cfg["periods"]


def decode_step_flops(cfg: dict, kv_lens: Iterable[int]) -> int:
    """Per token: both slots' projections, the dense MLP, the router and
    the MLPs of the experts the token is routed to, and the head; per
    attention layer, QK^T and PV over the request's context."""
    d, hd = cfg["width"], cfg["head_width"]
    q, kv = cfg["q_heads"] * hd, cfg["kv_heads"] * hd
    attn = 2 * d * q + 2 * d * kv
    moe = d * cfg["experts"] + cfg["experts_per_token"] * 3 * d * \
        cfg["expert_width"]
    params = cfg["periods"] * (2 * attn + 3 * d * cfg["mlp_width"] + moe) \
        + d * cfg["vocab_size"]
    return sum(2 * params + _attention_layers(cfg) * 4 * n * q
               for n in kv_lens)


def paged_decode_work(cfg: dict, kv_lens: Iterable[int]) -> tuple:
    """One paged decode call per attention slot of every period."""
    hd = cfg["head_width"]
    q, kv = cfg["q_heads"] * hd, cfg["kv_heads"] * hd
    layers = _attention_layers(cfg)
    flops = sum(4 * n * q for n in kv_lens)
    nbytes = sum((2 * n * kv + 2 * q) * 2 for n in kv_lens)
    return layers, layers * flops, layers * nbytes
