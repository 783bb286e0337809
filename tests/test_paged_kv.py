"""Paged KV-cache serving path.

Four layers of guarantees:
  * kernel — the Pallas paged decode kernel (interpret mode) and the
    blocked jnp reference agree with the contiguous-gather oracle for
    ragged lengths, both dtypes, both page sizes;
  * engine — a paged engine produces the same greedy tokens as the
    slot-contiguous engine on identical prompts;
  * prefix cache / chunked prefill — the content-addressed pool shares
    prefix blocks (hit / miss / copy-on-write / LRU eviction), suffix-only
    and chunked prefill stay bit-exact with monolithic uncached prefill,
    and mixed steps keep decodes flowing while a long prompt prefills;
  * consolidation — §6.2 migration at block granularity: in-flight
    requests (including half-prefilled ones) continue bit-exactly after
    ``consolidated()`` and the bytes gathered equal the BlockManager's
    dedup-aware ``migration_bytes`` quote (each shared block once).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import smoke
from repro.kernels import ops, ref
from repro.kernels.decode_attention import paged_decode_attention
from repro.models import build_model
from repro.serving.api import SamplingParams
from repro.serving.endpoint import ServingEndpoint
from repro.serving.engine import Engine
from repro.serving.kvcache import BlockManager

PROMPTS = [[5, 7, 9, 11], [3, 1, 4, 1, 5, 9, 2], [42] * 6, [8, 6, 7]]


def _tol(dtype):
    return 2e-5 if dtype == jnp.float32 else 3e-2


def _paged_case(rng, b, hq, hkv, hd, page_size, nb, dtype, lens=None):
    """Random q and pools and a shuffled table that leaves page 0 (the
    null page) to no row. With ``lens`` given, each row's table past its
    live pages holds garbage but valid ids, the null page among them."""
    n_pages = b * nb + 1
    q = jnp.asarray(rng.standard_normal((b, 1, hq, hd)), dtype)
    kp = jnp.asarray(rng.standard_normal((n_pages, page_size, hkv, hd)),
                     dtype)
    vp = jnp.asarray(rng.standard_normal((n_pages, page_size, hkv, hd)),
                     dtype)
    perm = rng.permutation(n_pages - 1) + 1
    bt = perm[: b * nb].reshape(b, nb)
    if lens is None:
        lens = rng.integers(1, nb * page_size + 1, b)
    else:
        for r, n in enumerate(lens):
            live = -(-n // page_size)
            tail = rng.integers(0, n_pages, nb - live)
            tail[::2] = 0
            bt[r, live:] = tail
    return (q, kp, vp, jnp.asarray(bt, jnp.int32),
            jnp.asarray(lens, jnp.int32))


# At page 16 the kernel reads blocks of 8 pages (128 tokens); a table of
# 19 pages is no multiple of that. The edge lengths: an empty row, one
# token, a page, a page and one, a block, a block and one, two blocks and
# one, the whole table.
EDGES = [0, 1, 16, 17, 128, 129, 257, 19 * 16]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,hq,hkv,hd,page_size,nb,lens", [
    pytest.param(2, 8, 2, 64, 16, 5, None, id="2-8-2-64-16-5"),
    pytest.param(3, 4, 4, 32, 64, 3, None, id="3-4-4-32-64-3"),
    pytest.param(2, 6, 1, 64, 16, 4, None, id="2-6-1-64-16-4"),
    pytest.param(1, 16, 8, 128, 64, 2, None, id="1-16-8-128-64-2"),
    pytest.param(8, 8, 2, 128, 16, 19, EDGES, id="edges-group4"),
    pytest.param(8, 12, 2, 128, 16, 19, EDGES[::-1], id="edges-group6"),
])
def test_paged_decode_kernel_matches_oracle(b, hq, hkv, hd, page_size, nb,
                                            lens, dtype):
    rng = np.random.default_rng(11)
    q, kp, vp, bt, lens = _paged_case(rng, b, hq, hkv, hd, page_size, nb,
                                      dtype, lens)
    # oracle: gather the table into a contiguous cache, masked attention;
    # a row with no context reads nothing and returns zeros
    kc = kp[bt].reshape(b, nb * page_size, hkv, hd)
    vc = vp[bt].reshape(b, nb * page_size, hkv, hd)
    want = ref.decode_attention_reference(q, kc, vc, lens)
    want = jnp.where((lens > 0)[:, None, None, None], want, 0)

    got_kernel = paged_decode_attention(q, kp, vp, bt, lens, interpret=True)
    got_ref = ref.paged_decode_attention_reference(q, kp, vp, bt, lens)
    for got in (got_kernel, got_ref):
        err = jnp.max(jnp.abs(got.astype(jnp.float32)
                              - want.astype(jnp.float32)))
        assert float(err) < _tol(dtype), err


def test_paged_ref_handles_zero_length_rows():
    """Idle batch rows (kv_len == 0, table all null) must not produce NaNs."""
    rng = np.random.default_rng(3)
    q, kp, vp, bt, _ = _paged_case(rng, 2, 4, 2, 32, 16, 3, jnp.float32)
    lens = jnp.asarray([5, 0], jnp.int32)
    out = ref.paged_decode_attention_reference(q, kp, vp, bt, lens)
    assert bool(jnp.all(jnp.isfinite(out)))
    out_k = paged_decode_attention(q, kp, vp, bt, lens, interpret=True)
    assert bool(jnp.all(jnp.isfinite(out_k)))


def test_ops_dispatch_paged_mode():
    prev = ops.decode_mode()
    try:
        ops.set_decode_mode("paged")
        assert ops.decode_mode() == "paged"
    finally:
        ops.set_decode_mode(prev)
    with pytest.raises(AssertionError):
        ops.set_decode_mode("bogus")


@pytest.fixture(scope="module")
def granite():
    cfg = smoke("granite-3-8b")
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    return cfg, params


def test_engine_paged_matches_contiguous(granite):
    cfg, params = granite
    outs = {}
    for paged in (False, True):
        ep = ServingEndpoint(Engine(cfg, [params], max_batch=3, max_seq=64,
                                    paged=paged))
        reqs = [ep.submit(p, SamplingParams(max_new=8)) for p in PROMPTS]
        ep.run()
        assert all(r.done for r in reqs)
        outs[paged] = [r.generated for r in reqs]
        bm = ep.engine.block_mgr
        assert bm.free_blocks == bm.n_blocks
    assert outs[True] == outs[False]


@pytest.mark.parametrize("arch", ["granite-3-8b", "jamba-v0.1-52b"])
def test_paged_consolidation_block_exact(arch, rng):
    """In-flight requests continue bit-exactly across a paged scale-down,
    and the gather moves exactly the bytes the BlockManager quotes."""
    cfg = smoke(arch)
    m = build_model(cfg)
    params = m.init(rng)

    ref_ep = ServingEndpoint(Engine(cfg, [params], max_batch=2, max_seq=48,
                                    paged=True))
    ref_reqs = [ref_ep.submit(p, SamplingParams(max_new=8))
                for p in PROMPTS[:2]]
    ref_ep.run()

    sp = [m.slice_stage_params(params, 2, i) for i in range(2)]
    ep = ServingEndpoint(Engine(cfg, sp, max_batch=2, max_seq=48,
                                paged=True))
    reqs = [ep.submit(p, SamplingParams(max_new=8)) for p in PROMPTS[:2]]
    for _ in range(3):
        ep.step()
    live_rids = [r.rid for r in ep.active()]
    n_remote = ep.engine.n_attn_layers(migrated_only=True)
    quoted = ep.engine.block_mgr.migration_bytes(live_rids, n_remote)
    ep.consolidate(params)
    assert ep.last_migration_bytes == quoted
    # only a degenerate split (all periods on the surviving stage, e.g.
    # jamba-smoke's single period) legitimately ships zero KV bytes
    assert (quoted > 0) == (n_remote > 0)
    ep.run()
    assert [r.generated for r in reqs] == [r.generated for r in ref_reqs]


def test_admission_defers_instead_of_raising(granite):
    """When the pool can't hold a request, it waits in the queue — no
    MemoryError mid-flight — and is served once blocks free up."""
    cfg, params = granite
    eng = Engine(cfg, [params], max_batch=2, max_seq=64, paged=True)
    bs = eng.block_mgr.block_size
    # a co-tenant hogs the whole pool
    eng.block_mgr.allocate(-1, eng.block_mgr.n_blocks * bs)
    r = eng.submit(PROMPTS[0], SamplingParams(max_new=4))
    eng.step()
    assert r.slot is None and not r.done and len(eng.queue) == 1
    eng.block_mgr.free(-1)
    eng.run()
    assert r.done and len(r.generated) == 4


def test_submit_rejects_requests_larger_than_max_seq(granite):
    """prompt + max_new beyond max_seq can't be cached in either layout —
    reject at submit instead of overflowing block tables mid-flight."""
    cfg, params = granite
    eng = Engine(cfg, [params], max_batch=2, max_seq=64, paged=True)
    with pytest.raises(ValueError, match="max_seq"):
        eng.submit([1] * 60, SamplingParams(max_new=60))
    # boundary case fits exactly
    r = eng.submit([1] * 60, SamplingParams(max_new=4))
    eng.run()
    assert r.done and len(r.generated) == 4


def test_engine_paged_default_follows_decode_mode(granite):
    cfg, params = granite
    prev = ops.decode_mode()
    try:
        ops.set_decode_mode("paged")
        eng = Engine(cfg, [params], max_batch=2, max_seq=64)
        assert eng.paged
    finally:
        ops.set_decode_mode(prev)


# ---------------------------------------------------------------------------
# BlockManager: content-addressed pool (unit level, no model)
# ---------------------------------------------------------------------------


def test_block_manager_prefix_hit_miss_and_refcounts():
    bm = BlockManager(n_blocks=8, block_size=4, bytes_per_token=2,
                      prefix_cache=True)
    toks = list(range(10))                       # 2 full blocks + partial
    t0 = bm.allocate(0, 10, tokens=toks)
    assert t0.cached_tokens == 0 and len(t0.blocks) == 3
    bm.commit(0, 10)                             # registers blocks 0 and 1
    t1 = bm.allocate(1, 10, tokens=toks)         # hit: shares both full blocks
    assert t1.cached_tokens == 8
    assert t1.blocks[:2] == t0.blocks[:2]        # shared
    assert t1.blocks[2] != t0.blocks[2]          # private partial block
    assert bm.refcount(t0.blocks[0]) == 2
    # dedup-aware gathering: 4 unique blocks back 2 requests (6 table rows)
    assert len(bm.blocks_of([0, 1])) == 4
    assert bm.migration_bytes([0, 1], n_layers=1) == 4 * 4 * 2
    miss = bm.allocate(2, 10, tokens=[99] * 10)  # different chain: miss
    assert miss.cached_tokens == 0
    bm.free(0)
    assert bm.refcount(t0.blocks[0]) == 1        # still referenced by req 1
    bm.free(1)
    bm.free(2)
    assert bm.free_blocks == 8                   # cached blocks stay claimable
    assert bm.n_cached > 0                       # ...but keep their content
    t3 = bm.allocate(3, 10, tokens=toks)         # prefix survives free()
    assert t3.cached_tokens == 8


def test_block_manager_cow_on_fully_cached_prompt():
    """A full-prompt hit recomputes the last token into a private
    copy-on-write block — the shared page is never written through."""
    bm = BlockManager(n_blocks=8, block_size=4, bytes_per_token=2,
                      prefix_cache=True)
    toks = list(range(8))                        # exactly 2 blocks
    t0 = bm.allocate(0, 8, tokens=toks)
    bm.commit(0, 8)
    t1 = bm.allocate(1, 8, tokens=toks)
    assert t1.cached_tokens == 7                 # always >= 1 token computed
    copies = bm.drain_copies()
    assert copies == [(t0.blocks[1], t1.blocks[1])]
    assert t1.blocks[0] == t0.blocks[0]          # first block shared
    assert t1.blocks[1] != t0.blocks[1]          # last block private
    assert bm.refcount(t0.blocks[1]) == 1        # COW pin released at drain
    bm.free(0)
    bm.free(1)
    assert bm.free_blocks == 8


def test_block_manager_lru_eviction_prunes_index():
    """Eviction takes refcount-zero cached blocks LRU-first (and within a
    freed request tail-before-head, so shorter prefixes outlive longer
    ones) and drops their index entries."""
    bm = BlockManager(n_blocks=4, block_size=4, bytes_per_token=2,
                      prefix_cache=True)
    bm.allocate(0, 8, tokens=[1] * 8)
    bm.commit(0, 8)
    bm.free(0)                                   # chain A cached (LRU-old)
    bm.allocate(1, 8, tokens=[2] * 8)
    bm.commit(1, 8)
    bm.free(1)                                   # chain B cached (recent)
    assert bm.free_blocks == 4 and bm.n_cached == 4
    bm.allocate(2, 8, tokens=[3] * 8)            # miss: evicts chain A
    assert bm.evictions == 2
    bm.free(2)
    # chain A's index entries are gone: full miss; chain B intact
    assert bm.allocate(3, 8, tokens=[1] * 8).cached_tokens == 0
    bm.free(3)
    t = bm.allocate(4, 8, tokens=[2] * 8)        # full-prompt COW hit
    assert t.cached_tokens == 7
    bm.drain_copies()


def test_block_manager_commit_gates_registration():
    """Blocks enter the index only once their KV is committed — a
    half-prefilled request never exposes unwritten pages for sharing."""
    bm = BlockManager(n_blocks=8, block_size=4, bytes_per_token=2,
                      prefix_cache=True)
    toks = list(range(12))
    bm.allocate(0, 12, tokens=toks)              # nothing committed yet
    assert bm.allocate(1, 12, tokens=toks).cached_tokens == 0
    bm.free(1)
    bm.commit(0, 5)                              # only block 0 is material
    assert bm.allocate(2, 12, tokens=toks).cached_tokens == 4


def test_block_manager_legacy_token_free_path():
    """Callers that never pass token ids get plain ref-counted blocks:
    no hashing, no caching on free."""
    bm = BlockManager(n_blocks=10, block_size=4, bytes_per_token=8,
                      prefix_cache=True)
    bm.allocate(0, 9)
    bm.commit(0, 9)                              # no-op without tokens
    bm.free(0)
    assert bm.n_cached == 0 and bm.free_blocks == 10
    assert bm.blocks_needed(0) == 0
    assert bm.blocks_needed(1) == bm.blocks_needed(4) == 1
    assert bm.blocks_needed(5) == 2


# ---------------------------------------------------------------------------
# Engine: prefix cache + chunked prefill (bit-exactness and scheduling)
# ---------------------------------------------------------------------------

SHARED_PREFIX = list(range(1, 17))               # 2 blocks at block_size=8
TAILS = [[101, 103], [7, 9, 11]]


def _prefix_engine(cfg, stage_params, **kw):
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_seq", 64)
    kw.setdefault("block_size", 8)
    kw.setdefault("paged", True)
    return Engine(cfg, stage_params, **kw)


def _run_pair(cfg, params, **kw):
    eng = _prefix_engine(cfg, [params], **kw)
    reqs = [eng.submit(SHARED_PREFIX + t, SamplingParams(max_new=6))
            for t in TAILS]
    eng.run()
    return reqs, eng


def test_prefix_cache_suffix_only_prefill_bit_exact(granite):
    """The second request of a shared-prefix pair prefills only its
    suffix (cached_tokens == shared prefix) and its greedy stream is
    bit-exact with the uncached paged AND contiguous engines."""
    cfg, params = granite
    ref_c, _ = _run_pair(cfg, params, paged=False)
    ref_p, _ = _run_pair(cfg, params)
    hit, eng = _run_pair(cfg, params, prefix_cache=True)
    for a, b, c in zip(ref_c, ref_p, hit):
        assert a.generated == b.generated == c.generated
    assert hit[0].metrics.cached_tokens == 0     # first writer: cold
    assert hit[1].metrics.cached_tokens == len(SHARED_PREFIX)
    bm = eng.block_mgr
    assert bm.cache_hit_tokens >= len(SHARED_PREFIX)
    assert bm.free_blocks == bm.n_blocks         # all reclaimed (or cached)


def test_prefix_cache_cow_rehit_bit_exact(granite):
    """Submitting an identical prompt after the first finished hits the
    whole prompt (minus the resampled last token) through COW."""
    cfg, params = granite
    eng = _prefix_engine(cfg, [params], prefix_cache=True)
    r1 = eng.submit(SHARED_PREFIX, SamplingParams(max_new=4))
    eng.run()
    r2 = eng.submit(SHARED_PREFIX, SamplingParams(max_new=4))
    eng.run()
    assert r2.generated == r1.generated
    assert r2.metrics.cached_tokens == len(SHARED_PREFIX) - 1


def test_chunked_prefill_bit_exact_and_mixed_steps(granite):
    """A long prompt prefilling in chunks (a) produces the same greedy
    stream as monolithic prefill, and (b) shares its steps with the
    in-flight decodes (mixed StepOutputs) instead of stalling them."""
    cfg, params = granite
    long_prompt = list(range(3, 27))             # 24 tokens
    ref = _prefix_engine(cfg, [params])
    want_long = ref.submit(long_prompt, SamplingParams(max_new=4))
    want_short = ref.submit([9, 8, 7], SamplingParams(max_new=10))
    ref.run()

    eng = _prefix_engine(cfg, [params], prefill_chunk=7)
    short = eng.submit([9, 8, 7], SamplingParams(max_new=10))
    eng.step()                                   # short is decoding...
    long = eng.submit(long_prompt, SamplingParams(max_new=4))
    mixed = 0
    while not long.done or not short.done:
        out = eng.step()
        assert out.prefill_tokens <= 7           # budget respected
        if out.prefill_tokens and out.events:
            mixed += 1
        if not long.prefill_done:
            # decode-heavy traffic keeps flowing during the long prefill
            assert any(ev.rid == short.rid for ev in out.events)
    assert mixed >= 3                            # ceil(24 / 7) chunk steps
    assert long.generated == want_long.generated
    assert short.generated == want_short.generated
    assert long.metrics.queue_steps >= 3         # chunking shows up in TTFT


def test_eviction_frees_cached_blocks_before_deferring(granite):
    """A cold pool full of refcount-zero cached blocks must admit (and
    LRU-evict), not defer."""
    cfg, params = granite
    eng = _prefix_engine(cfg, [params], max_batch=1, max_seq=32,
                         prefix_cache=True)      # pool: 5 blocks of 8
    a = eng.submit(list(range(40, 64)), SamplingParams(max_new=8))
    eng.run()
    assert a.done
    bm = eng.block_mgr
    assert bm.n_cached > 0                       # finished request cached
    b = eng.submit(list(range(70, 86)), SamplingParams(max_new=8))
    eng.step()
    assert b.slot is not None                    # admitted, not deferred
    assert bm.evictions > 0
    eng.run()
    assert b.done and len(b.generated) == 8


def test_half_prefilled_request_survives_consolidation(granite):
    """§6.2 scale-down mid-prefill: the chunked request's committed
    blocks migrate, the remaining chunks run on the consolidated engine,
    and the stream is bit-exact with the single-worker reference."""
    cfg, params = granite
    m = build_model(cfg)
    long_prompt = list(range(3, 27))
    ref = _prefix_engine(cfg, [params])
    want = ref.submit(long_prompt, SamplingParams(max_new=6))
    ref.run()

    sp = [m.slice_stage_params(params, 2, i) for i in range(2)]
    ep = ServingEndpoint(_prefix_engine(cfg, sp, prefix_cache=True,
                                        prefill_chunk=7))
    r = ep.submit(long_prompt, SamplingParams(max_new=6))
    ep.step()
    assert 0 < r.prefilled < r.prompt_total      # genuinely half-prefilled
    live = [x.rid for x in ep.active()]
    n_remote = ep.engine.n_attn_layers(migrated_only=True)
    quoted = ep.engine.block_mgr.migration_bytes(live, n_remote)
    ep.consolidate(params)
    assert ep.last_migration_bytes == quoted
    ep.run()
    assert r.generated == want.generated


def test_consolidation_ships_shared_blocks_once(granite):
    """Dedup-aware §6.2 accounting: with two in-flight requests sharing a
    2-block prefix, the gathered bytes equal the BlockManager quote and
    undercut the per-request (duplicated) block count."""
    cfg, params = granite
    m = build_model(cfg)
    sp = [m.slice_stage_params(params, 2, i) for i in range(2)]
    ep = ServingEndpoint(_prefix_engine(cfg, sp, prefix_cache=True))
    reqs = [ep.submit(SHARED_PREFIX + t, SamplingParams(max_new=6))
            for t in TAILS]
    for _ in range(2):
        ep.step()
    bm = ep.engine.block_mgr
    live_rids = [r.rid for r in ep.active()]
    n_remote = ep.engine.n_attn_layers(migrated_only=True)
    quoted = bm.migration_bytes(live_rids, n_remote)
    unique = len(bm.blocks_of(live_rids))
    duplicated = sum(len(bm.tables[r].blocks) for r in live_rids)
    assert unique < duplicated                   # sharing is real
    per_block = bm.block_size * bm.bytes_per_token * n_remote
    assert quoted == unique * per_block          # each shared block once
    ep.consolidate(params)
    assert ep.last_migration_bytes == quoted
    ep.run()
    # streams unaffected by dedup'd migration
    ref, _ = _run_pair(cfg, params)
    assert [r.generated for r in reqs] == [r.generated for r in ref]


def test_prefix_and_chunk_knobs_need_paged_attention_only(granite):
    cfg, params = granite
    with pytest.raises(ValueError, match="paged"):
        Engine(cfg, [params], paged=False, prefix_cache=True)
    with pytest.raises(ValueError, match="paged"):
        Engine(cfg, [params], paged=False, prefill_chunk=8)
    with pytest.raises(ValueError, match="prefill_chunk"):
        Engine(cfg, [params], paged=True, prefill_chunk=0)
    jcfg = smoke("jamba-v0.1-52b")               # hybrid: has mamba periods
    jp = build_model(jcfg).init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="attention-only"):
        Engine(jcfg, [jp], paged=True, prefix_cache=True)
