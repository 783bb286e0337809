"""The dense GQA decoder: what its configuration keys mean, and its plain
reference forward in float32.

As every architecture module under ``bench/references/``, it gives the
harness, which reads no key of an architecture's own:

- ``model_config(cfg)``: the program's ``ModelConfig``;
- ``layout(cfg)``: every leaf of the served tree, in a fixed order
  (``bench.lib.weights``);
- ``decode_step_flops(cfg, kv_lens)``: model FLOPs of one decode step;
- ``paged_decode_work(cfg, kv_lens)``: (kernel calls, operations, bytes)
  of one decode step's paged attention, over every attention layer;
- ``Reference(cfg, seed)``: the plain forward that decides ``correct``.

The reference forward is the published block of granite-3-8b and
internlm2-20b as this benchmark runs them: token embedding; per layer RMSNorm, grouped-query attention
with rotary positions (rotate-half convention) and a causal mask, a
residual add, RMSNorm, a SwiGLU MLP and a residual add; a final RMSNorm
and the output head over the real vocabulary. No cache, no paging, no
batching, no kernels: one whole sequence at a time, with every matmul at
``Precision.HIGHEST``.

The weights are rebuilt one layer at a time from the seed with
``bench.lib.weights``, in the served dtype and then widened to float32,
so the reference holds one layer and the hidden states of the sequences
it compares, and never reads what the system under test made.

``mode="fp8"`` is the control: the same forward with both operands of
every matmul rounded to float8 e4m3 under a per-tensor scale, and
float32 accumulation, one precision step below the bfloat16 the
configurations state.
"""

from __future__ import annotations

import functools
from typing import Dict, Iterable, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib import weights
from bench.lib.weights import Leaf

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


def model_config(cfg: dict):
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name=cfg["name"], family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        head_dim=cfg["head_dim"], rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        dtype=cfg["torch_dtype"])


def layout(cfg: dict) -> List[Leaf]:
    """Every leaf of the served tree of a dense GQA decoder with SwiGLU
    and RMSNorm, in a fixed order (the order fixes each leaf's key): one
    slot a period, so a block leaf is stacked over the layers."""
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    hd = cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    n = cfg["num_hidden_layers"]
    vp = weights.padded_vocab(cfg["vocab_size"])
    blk = ("blocks", "slot00")
    return [
        Leaf(("embed", "tok"), (vp, d), 0, 1.0),
        Leaf(blk + ("mixer", "w_q"), (d, hq * hd), n, d ** -0.5),
        Leaf(blk + ("mixer", "w_k"), (d, hkv * hd), n, d ** -0.5),
        Leaf(blk + ("mixer", "w_v"), (d, hkv * hd), n, d ** -0.5),
        Leaf(blk + ("mixer", "w_o"), (hq * hd, d), n, (hq * hd) ** -0.5),
        Leaf(blk + ("mixer", "norm"), (d,), n, 0.0),
        Leaf(blk + ("mlp", "w_gate"), (d, ff), n, d ** -0.5),
        Leaf(blk + ("mlp", "w_up"), (d, ff), n, d ** -0.5),
        Leaf(blk + ("mlp", "w_down"), (ff, d), n, ff ** -0.5),
        Leaf(blk + ("mlp", "norm"), (d,), n, 0.0),
        Leaf(("final_norm",), (d,), 0, 0.0),
        Leaf(("lm_head",), (d, vp), 0, d ** -0.5),
    ]


# Counts are of what the algorithm needs, not of what an implementation
# happens to move: a request's attention reads its own context and nothing
# past it, and a model FLOP is a multiply-add of a weight the token passes
# through (two operations), over the real vocabulary.

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


def paged_decode_work(cfg: dict, kv_lens: Iterable[int]) -> tuple:
    """(kernel calls, operations, bytes) of one decode step's paged
    attention: one call per layer, each over every request's context."""
    layers = cfg["num_hidden_layers"]
    flops, nbytes = paged_decode_call(cfg, kv_lens)
    return layers, layers * flops, layers * nbytes


def _q8(x):
    """Round to float8 e4m3 under a per-tensor scale (amax -> 448)."""
    s = jnp.max(jnp.abs(x)) / E4M3_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(spec, a, b, mode):
    if mode == "fp8":
        a, b = _q8(a), _q8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos[:, None].astype(jnp.float32) * inv          # (T, half)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(x, w, *, hq, hkv, hd, theta, eps, mode):
    """One layer over a batch of whole sequences, x (S, T, d)."""
    n, t, _ = x.shape
    pos = jnp.arange(t)
    h = _rmsnorm(x, w["attn_norm"], eps)
    q = _mm("std,dh->sth", h, w["w_q"], mode).reshape(n, t, hq, hd)
    k = _mm("std,dh->sth", h, w["w_k"], mode).reshape(n, t, hkv, hd)
    v = _mm("std,dh->sth", h, w["w_v"], mode).reshape(n, t, hkv, hd)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    rep = hq // hkv
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    s = _mm("sqhd,skhd->shqk", q, k, mode) * hd ** -0.5
    s = jnp.where(pos[:, None] >= pos[None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = _mm("shqk,skhd->sqhd", p, v, mode).reshape(n, t, hq * hd)
    x = x + _mm("sth,hd->std", o, w["w_o"], mode)
    h = _rmsnorm(x, w["mlp_norm"], eps)
    g = _mm("std,df->stf", h, w["w_gate"], mode)
    u = _mm("std,df->stf", h, w["w_up"], mode)
    return x + _mm("stf,fd->std", jax.nn.silu(g) * u, w["w_down"], mode)


def _logits(x, rows, norm, head, *, eps, mode):
    """Next-token logits at the chosen rows of each sequence."""
    h = jnp.take_along_axis(x, rows[:, :, None], axis=1)     # (S, R, d)
    return _mm("srd,dv->srv", _rmsnorm(h, norm, eps), head, mode)


def _head(x, rows, tokens, norm, head, *, eps, mode):
    """At the chosen rows of each sequence: the best logit minus the
    logit of ``tokens`` (the gap), and the argmax."""
    logits = _logits(x, rows, norm, head, eps=eps, mode=mode)
    chosen = jnp.take_along_axis(logits, tokens[:, :, None], axis=2)[..., 0]
    return jnp.max(logits, axis=2) - chosen, jnp.argmax(logits, axis=2)


# leaf names of one layer, in ``layout`` order
_LAYER_LEAVES = {"w_q": 1, "w_k": 2, "w_v": 3, "w_o": 4, "attn_norm": 5,
                 "w_gate": 6, "w_up": 7, "w_down": 8, "mlp_norm": 9}


def _pad(xs, n):
    return jnp.asarray([list(x) + [0] * (n - len(x)) for x in xs],
                       jnp.int32)


class Reference:
    """Gaps and argmaxes of the reference's logits at chosen positions of
    whole sequences, which are batched and padded to a fixed length so
    that each of its programs compiles once."""

    def __init__(self, cfg: dict, seed: int):
        self.cfg = cfg
        self.leaves = layout(cfg)
        self.key = weights.seed_key(seed)
        self.served = jnp.dtype(cfg["torch_dtype"])
        c = cfg
        eps = float(c["rms_norm_eps"])
        self._layer = jax.jit(functools.partial(
            _layer, hq=c["num_attention_heads"],
            hkv=c["num_key_value_heads"], hd=c["head_dim"],
            theta=float(c["rope_theta"]), eps=eps),
            static_argnames=("mode",))
        self._head = jax.jit(functools.partial(_head, eps=eps),
                             static_argnames=("mode",))
        self._weights = jax.jit(self._layer_weights)
        self._embed = jax.jit(self._embed_rows)
        self._final = jax.jit(lambda: (self._leaf(10, 0), self._leaf(11, 0)
                                       [:, :c["vocab_size"]]))

    def _leaf(self, index, layer):
        return weights.layer_leaf(self.key, self.leaves, index, layer,
                                  self.served).astype(jnp.float32)

    def _layer_weights(self, layer):
        return {name: self._leaf(i, layer)
                for name, i in _LAYER_LEAVES.items()}

    def _embed_rows(self, ids):
        return jnp.take(self._leaf(0, 0), ids, axis=0)

    def gaps(self, seqs: Sequence[Sequence[int]],
             rows: Sequence[Sequence[int]],
             tokens: Sequence[Sequence[int]], *, length: int, width: int,
             control: bool = False) -> Dict[str, List[np.ndarray]]:
        """Per sequence, at the positions ``rows[i]`` whose next tokens
        are ``tokens[i]``: ``served``, by how much each token's logit lies
        below the float32 reference's best; with ``control``, also
        ``control``, the same for the token that the float8 forward puts
        first. Sequences are padded to ``length`` tokens, rows to
        ``width``, so that every program compiles once."""
        if any(len(s) > length for s in seqs) or \
                any(len(r) > width for r in rows):
            raise ValueError("a sequence or its rows exceed the padding")

        r, tok = _pad(rows, width), _pad(tokens, width)
        xs = self._forward(_pad(seqs, length),
                           ("f32", "fp8") if control else ("f32",))
        norm, head = self._final()
        g, _ = self._head(xs["f32"], r, tok, norm, head, mode="f32")
        out = {"served": self._split(g, rows)}
        if control:
            _, a8 = self._head(xs["fp8"], r, tok, norm, head, mode="fp8")
            g8, _ = self._head(xs["f32"], r, a8.astype(jnp.int32), norm,
                               head, mode="f32")
            out["control"] = self._split(g8, rows)
        return out

    def _forward(self, ids, modes):
        x0 = self._embed(ids)
        xs = {m: x0 for m in modes}
        del x0
        for layer in range(self.cfg["num_hidden_layers"]):
            w = self._weights(jnp.int32(layer))
            xs = {m: self._layer(x, w, mode=m) for m, x in xs.items()}
            del w
        return xs

    def logits(self, seqs, rows, *, length: int, width: int):
        """Float32 next-token logits at ``rows[i]`` of each sequence."""
        x = self._forward(_pad(seqs, length), ("f32",))["f32"]
        norm, head = self._final()
        lg = np.asarray(jax.jit(functools.partial(
            _logits, eps=float(self.cfg["rms_norm_eps"]), mode="f32"))(
            x, _pad(rows, width), norm, head))
        return [lg[i, :len(r)] for i, r in enumerate(rows)]

    @staticmethod
    def _split(g, rows) -> List[np.ndarray]:
        g = np.asarray(g)
        return [g[i, :len(x)] for i, x in enumerate(rows)]
