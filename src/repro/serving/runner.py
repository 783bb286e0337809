"""Plan execution for the serving engine: the ModelRunner.

The runner is the compute half of the scheduler/runner split
(serving/scheduler.py): it owns the ``StageWorker`` pipeline and turns a
``ScheduleBatch``'s assignments into forwards — prefill chunks for one
slot, one batched decode over the decode set — returning logits. It
holds **no queue or policy state**; everything it knows about a request
is the slot / tokens / positions the engine hands it.

It also owns the paged layout's batched block table: a ``(B,
table_width)`` int32 array kept **incrementally** current — rows are
updated on allocate / extend / free / preempt instead of being rebuilt
from the BlockManager every step (the pre-split engine rebuilt and
re-uploaded the whole table per forward). The device-side copy is cached
too and only re-uploaded after a row actually changes, so steady-state
decode steps (no block boundary crossed) reuse the same device array.
Idle slots point at the null page so their (unused) writes never land in
a live page; for decode, half-prefilled slots are masked out the same
way — they take no part in the decode batch and their dummy writes must
not land in live (possibly shared) pages.
"""

from __future__ import annotations

from typing import Iterable, List, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from repro import spans
from repro.configs.base import ModelConfig
from repro.serving.kvcache import KVInvariantError
from repro.serving.worker import StageWorker


class ModelRunner:
    def __init__(self, cfg: ModelConfig, stage_params: Sequence[dict],
                 max_batch: int, max_seq: int, *, paged: bool,
                 n_blocks: int, block_size: int, kv_dtype=None):
        self.cfg = cfg
        self.paged = paged
        self.max_batch = max_batch
        self.kv_dtype = kv_dtype
        self._attn_only = (all(m == "attn" for m in cfg.mixer_pattern)
                           and not cfg.is_encdec)
        # one extra trash page: idle slots' block-table rows point here so
        # their (unused) decode writes never land in a live page; the
        # ragged path also routes pad-token writes to it
        self._null_page = n_blocks
        self._block_size = block_size
        self._table_width = max_seq // block_size + 1
        n = len(stage_params)
        self.workers = [StageWorker(cfg, p, n, i, max_batch, max_seq,
                                    paged=paged, n_pages=n_blocks + 1,
                                    page_size=block_size, kv_dtype=kv_dtype)
                        for i, p in enumerate(stage_params)]
        self._bt = np.full((max_batch, self._table_width), self._null_page,
                           np.int32)
        # correctness tracer (analysis/sanitizer.py); None in production
        self.tracer = None
        self._bt_dev = None             # cached device copy, None = dirty
        # masked decode-view cache: (frozen skip set, device array) — a
        # mixed step with the same half-prefilled slots and unchanged rows
        # reuses it instead of re-masking + re-uploading every forward
        self._masked_dev = (None, None)

    # --------------------------------------------------- block-table rows
    def set_row(self, slot: int, blocks: Sequence[int]):
        """(Re)write one slot's block-table row: called on allocate and
        whenever extend crosses a block boundary."""
        if not self.paged:
            return
        if self.tracer is not None:
            self.tracer.on_set_row(slot, list(blocks))
        row = self._bt[slot]
        row[:] = self._null_page
        row[:len(blocks)] = blocks
        self._bt_dev = None
        self._masked_dev = (None, None)

    def clear_row(self, slot: int):
        """Point a vacated slot (finish / preempt) back at the null page."""
        if not self.paged:
            return
        if self.tracer is not None:
            self.tracer.on_clear_row(slot)
        self._bt[slot] = self._null_page
        self._bt_dev = None
        self._masked_dev = (None, None)

    def rebuild_rows(self, requests: Iterable, tables: dict):
        """Full rebuild from BlockManager state — only needed when a
        consolidated engine adopts another engine's residents."""
        if not self.paged:
            return
        self._bt[:] = self._null_page
        for r in requests:
            blocks = tables[r.rid].blocks
            if self.tracer is not None:
                self.tracer.on_set_row(r.slot, list(blocks))
            self._bt[r.slot, :len(blocks)] = blocks
        self._bt_dev = None
        self._masked_dev = (None, None)

    def _tables(self) -> jnp.ndarray:
        if self._bt_dev is None:
            self._bt_dev = jnp.asarray(self._bt)
        return self._bt_dev

    # ------------------------------------------------------------ compute
    def prefill(self, slot: int, tokens: Sequence[int], start: int, n: int,
                prefix_embeds=None):
        """One prefill forward over rows [start, start+n) of a request's
        chain, writing KV through the slot's block-table row (paged) or
        the slot's contiguous strip. Returns the pipeline output — the
        last stage's logits at the final row."""
        if self.paged and self._attn_only and prefix_embeds is None:
            # satellite path: run the chunk as a one-segment ragged batch.
            # History length is *dynamic* there (per-token positions drive
            # the mask), so compiles are bounded by the power-of-two token
            # buckets instead of one executable per (chunk_len, hist_len).
            h = self.forward_batch([(slot, list(tokens), start)])
            return h[0][None, None]
        if self.tracer is not None:
            self.tracer.on_prefill(slot, start, n)
        prefix = None
        if prefix_embeds is not None:
            prefix = jnp.asarray(prefix_embeds)[None]
        h = jnp.asarray([list(tokens)], jnp.int32)
        positions = jnp.arange(start, start + n, dtype=jnp.int32)[None]
        bt = None
        if self.paged:
            bt = self._tables()[slot:slot + 1]
        for w in self.workers:
            h = w.prefill_slot(h, slot, positions, prefix_embeds=prefix,
                               block_tables=bt, hist_len=start)
        return h

    def decode(self, reqs: Sequence, skip_slots: Sequence[int] = ()):
        """One batched decode over ``reqs`` (each contributes its last
        generated token at its next cache position). ``skip_slots`` are
        live-but-not-decoding slots (half-prefilled residents) whose
        table rows are masked to the null page for this forward. One
        ``runner.decode`` span: the inputs' build and the dispatch. On
        the paged layout it counts ``kv_pages_live``, the pages the
        attention kernel reads (Σ ``cdiv(kv_len, block_size)`` over the
        rows), and ``kv_pages_table``, rows × table width."""
        if self.tracer is not None:
            self.tracer.on_decode([(r.slot, r.pos_next) for r in reqs],
                                  list(skip_slots))
        with spans.span("runner.decode", batch=len(reqs)):
            tokens = np.zeros((self.max_batch, 1), np.int32)
            positions = np.zeros((self.max_batch, 1), np.int32)
            for r in reqs:
                tokens[r.slot, 0] = r.generated[-1]
                positions[r.slot, 0] = r.pos_next
            bt = None
            if self.paged:
                bs = self._block_size
                spans.count("kv_pages_live",
                            sum(r.pos_next // bs + 1 for r in reqs))
                spans.count("kv_pages_table", len(reqs) * self._table_width)
                if skip_slots:
                    key = frozenset(skip_slots)
                    if self._masked_dev[0] != key:
                        masked = self._bt.copy()
                        masked[list(skip_slots)] = self._null_page
                        self._masked_dev = (key, jnp.asarray(masked))
                    bt = self._masked_dev[1]
                else:
                    bt = self._tables()
            h = jnp.asarray(tokens)
            pos = jnp.asarray(positions)
            for w in self.workers:
                h = w.decode(h, pos, block_tables=bt)
        return h

    _TILE_Q = 8     # ragged span alignment (kernels/ragged_attention.py)

    def forward_batch(self, segments: Sequence):
        """ONE fused launch over a mixed ragged batch. ``segments`` is a
        list of (slot, tokens, pos0) — prefill chunks (len > 1, pos0 =
        rows already in the pool) and decode rows (len 1) freely mixed,
        at most one segment per slot. Tokens are flattened into a single
        ragged axis; each segment's span is tile-aligned (pad tokens get
        pos = -1 → masked, writes routed to the trash page) and the total
        is bucketed to a power of two so the jit cache stays O(log
        max_tokens). Returns (max_batch, V) logits — row i is segment
        i's last real token's logits."""
        if not (self.paged and self._attn_only):
            raise KVInvariantError(
                "forward_batch requires the paged attention-only layout")
        if not 0 < len(segments) <= self.max_batch:
            raise KVInvariantError(
                f"{len(segments)} segments for max_batch={self.max_batch}")
        if self.tracer is not None:
            self.tracer.on_forward_batch(
                [(s, len(tk), p0) for s, tk, p0 in segments])
        tq = self._TILE_Q
        toks: List[int] = []
        poss: List[int] = []
        rows: List[int] = []
        out_idx = [0] * self.max_batch
        for i, (slot, tokens, pos0) in enumerate(segments):
            n = len(tokens)
            na = -(-n // tq) * tq
            out_idx[i] = len(toks) + n - 1
            toks.extend(int(t) for t in tokens)
            toks.extend([0] * (na - n))
            poss.extend(range(pos0, pos0 + n))
            poss.extend([-1] * (na - n))
            # pad rows inside a segment's aligned span keep its slot so
            # `row` stays constant per tile (the kernel's layout contract)
            rows.extend([slot] * na)
        t = len(toks)
        tb = tq
        while tb < t:
            tb *= 2
        toks.extend([0] * (tb - t))
        poss.extend([-1] * (tb - t))
        rows.extend([0] * (tb - t))
        x = jnp.asarray([toks], jnp.int32)
        pos = jnp.asarray([poss], jnp.int32)
        row = jnp.asarray(rows, jnp.int32)
        valid = jnp.asarray([p >= 0 for p in poss])
        oi = jnp.asarray(out_idx, jnp.int32)
        bt = self._tables()
        h = x
        for w in self.workers:
            h = w.forward_ragged(h, pos, row, valid, bt, oi)
        return h[0]

    # -------------------------------------------------------- maintenance
    def copy_pages(self, src: int, dst: int):
        """Apply a prefix-cache copy-on-write to every stage's pools."""
        for w in self.workers:
            w.copy_pages(src, dst)

    def read_pages(self, blk: int):
        """One block's KV across the whole model, as a pipeline-shape
        independent payload: ordered (cache_slot_name, k, v) triples whose
        page arrays are concatenated over the stages along the period
        axis — a payload read from a 2-stage engine writes back into its
        consolidated 1-stage successor (or any same-model replica)
        unchanged. Quantized pools append a 4th element per entry: a dict
        of the scale/zero leaves, concatenated the same way."""
        out = []
        for name, sub in self.workers[0].cache.items():
            if "k_pages" not in sub:
                continue
            parts = [w.read_page(name, blk) for w in self.workers]
            k = np.concatenate([p["k_pages"] for p in parts], axis=0)
            v = np.concatenate([p["v_pages"] for p in parts], axis=0)
            extra = [l for l in parts[0] if l not in ("k_pages", "v_pages")]
            if extra:
                aux = {l: np.concatenate([p[l] for p in parts], axis=0)
                       for l in extra}
                out.append((name, k, v, aux))
            else:
                out.append((name, k, v))
        return out

    def write_pages(self, blk: int, payload):
        """Scatter a spilled block's payload (see ``read_pages``) back
        into the stage pools, splitting the period axis by each stage's
        share."""
        for entry in payload:
            name, k, v = entry[0], entry[1], entry[2]
            aux = entry[3] if len(entry) > 3 else {}
            off = 0
            for w in self.workers:
                p = w.cache[name]["k_pages"].shape[0]
                extras = {l: a[off:off + p] for l, a in aux.items()} or None
                w.write_page(name, blk, k[off:off + p], v[off:off + p],
                             extras=extras)
                off += p
            if off != k.shape[0]:
                raise KVInvariantError(
                    f"payload periods {k.shape[0]} != pipeline periods {off}")

    def clear_slot(self, slot: int):
        """Zero a vacated slot's recurrent state on every stage."""
        for w in self.workers:
            w.clear_slot(slot)

    def retire(self):
        """Drop caches and params so a retired engine's stale runner
        fails fast instead of writing into pools it no longer owns."""
        for w in self.workers:
            w.retire()
        self.workers = []
