"""The program's spans and counters (``repro.spans``): nesting, counts on
the innermost span, JAX's build events, the ring's bound, and the span
tree of a served cold start, consolidation included, on both step paths.
"""

from collections import Counter

import jax
import jax.numpy as jnp
import pytest

from conftest import smoke
from repro import spans
from repro.core.types import GB, SLO, Gbps, ModelProfile, ServerSpec, \
    TimingProfile
from repro.models import build_model
from repro.serving.api import SamplingParams
from repro.serving.endpoint import ServerlessFrontend
from repro.serving.engine import Engine

STEP_CHILDREN = {"engine.schedule", "engine.prefill", "runner.decode",
                 "engine.sample", "engine.emit"}


def since(mark: int):
    """Records opened after the record numbered ``mark``."""
    return [r for r in spans.records() if r.index > mark]


def last_index() -> int:
    with spans.span("test.mark") as rec:
        pass
    return rec.index


def kids(records):
    out = {}
    for r in records:
        out.setdefault(r.parent, []).append(r)
    return out


def subtree(root, tree):
    out, todo = [], [root]
    while todo:
        r = todo.pop()
        out.append(r)
        todo.extend(tree.get(r.index, ()))
    return out


def test_nesting_parents_and_attrs():
    mark = last_index()
    with spans.span("outer", model="m") as outer:
        with spans.span("inner", stage=1) as inner:
            assert inner.end_ns is None
        with spans.span("inner", stage=2):
            pass
    recs = since(mark)
    assert [r.name for r in recs] == ["outer", "inner", "inner"]
    assert outer.parent is None
    assert [r.parent for r in recs[1:]] == [outer.index] * 2
    assert [r.attrs for r in recs] == [{"model": "m"}, {"stage": 1},
                                       {"stage": 2}]
    for r in recs:
        assert r.start_ns <= r.end_ns
    assert outer.start_ns <= inner.start_ns <= inner.end_ns <= outer.end_ns


def test_step_span_takes_step_num():
    with spans.span("engine.step", step_num=7) as rec:
        pass
    assert rec.attrs == {"step_num": 7} and rec.end_ns is not None


def test_count_lands_on_innermost_open_span():
    before = dict(spans.unattributed)
    with spans.span("outer") as outer:
        spans.count("rows", 2)
        with spans.span("inner") as inner:
            spans.count("rows", 5)
            spans.count("rows")
        spans.count("bytes", 10)
    spans.count("test_spans_outside", 3)
    assert outer.counters == {"rows": 2, "bytes": 10}
    assert inner.counters == {"rows": 6}
    assert spans.unattributed["test_spans_outside"] == \
        before.get("test_spans_outside", 0) + 3


def test_jit_builds_are_counted_once_per_shape():
    f = jax.jit(lambda x: jnp.sin(x) * 3 + 1)
    x = jnp.ones((3, 17), jnp.float32)
    with spans.span("first") as first:
        f(x).block_until_ready()
    with spans.span("again") as again:
        f(x).block_until_ready()
    assert first.counters["executables"] == 1
    assert first.counters["trace_ns"] > 0
    assert first.counters["lower_ns"] > 0
    assert first.counters["compile_ns"] > 0
    assert again.counters.get("executables", 0) == 0
    assert again.counters.get("trace_ns", 0) == 0


def test_ring_keeps_the_newest_records():
    mark = last_index()
    n = spans.RING + 10
    for i in range(n):
        with spans.span("test.fill", i=i):
            pass
    recs = spans.records()
    assert len(recs) == spans.RING
    assert [r.attrs["i"] for r in recs[-3:]] == [n - 3, n - 2, n - 1]
    assert recs[0].index == mark + 11
    assert [r.index for r in recs] == list(range(recs[0].index,
                                                 recs[0].index + spans.RING))


# ============================================================== serving
@pytest.fixture(scope="module")
def deployed():
    cfg = smoke("granite-3-8b", n_layers=4)
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    front = ServerlessFrontend({
        f"srv{i}": ServerSpec(f"srv{i}", 16 * Gbps, 12e9, 24 * GB)
        for i in range(4)})
    front.deploy(cfg, params, ModelProfile(cfg.name, int(12.5 * GB),
                                           TimingProfile(),
                                           SLO(ttft=7.5, tpot=0.2)))
    return cfg, params, front


def _check_steps(recs, outs, first_tokens):
    """One ``engine.step`` per step, carrying that step's counts, with
    only step-phase spans inside it."""
    tree = kids(recs)
    steps = [r for r in recs if r.name == "engine.step"]
    assert len(steps) == len(outs)
    for rec, out, first in zip(steps, outs, first_tokens):
        assert rec.attrs == {"step_num": out.step}
        assert rec.counters["prefill_tokens"] == out.prefill_tokens
        assert rec.counters["preempted"] == len(out.preempted)
        assert rec.counters["decode_rows"] == len(out.events) - first
        inside = {r.name for r in subtree(rec, tree)} - {"engine.step"}
        assert inside <= STEP_CHILDREN
    return steps


def _drive(ep, handles, n):
    """``n`` steps; each step's output and how many first tokens (emitted
    at prefill) it held."""
    outs, firsts = [], []
    for _ in range(n):
        had = {h.rid: len(h.generated) for h in handles}
        out = ep.step()
        outs.append(out)
        firsts.append(sum(1 for h in handles
                          if had[h.rid] == 0 and h.generated))
    return outs, firsts


def test_cold_start_and_consolidation_span_tree(deployed):
    cfg, _, front = deployed
    store = front.store_of(cfg.name)
    mark = last_index()
    ep = front.cold_start(cfg.name, min_stages=2, paged=True, max_batch=2,
                          max_seq=64)
    handles = [ep.submit([5, 3, 8, 1], SamplingParams(max_new=12)),
               ep.submit([9, 2], SamplingParams(max_new=12))]
    outs, firsts = _drive(ep, handles, 4)
    front.consolidate(ep, cfg.name)
    more, more_firsts = _drive(ep, handles, 3)
    recs = since(mark)
    tree = kids(recs)

    cold = [r for r in recs if r.name == "frontend.cold_start"]
    assert len(cold) == 1 and cold[0].attrs == {"model": cfg.name,
                                                "stages": 2}
    under = tree[cold[0].index]
    assert [r.name for r in under] == ["frontend.plan", "loader.stage",
                                       "loader.stage", "engine.init"]
    for i, st in enumerate(under[1:3]):
        assert st.attrs == {"stage": i, "bytes": store.stage_bytes(2, i)}
        assert st.counters["tensors"] == len(store.stage_plan(2, i))
        assert st.counters["read_ns"] > 0 and st.counters["put_ns"] > 0
    init = under[3]
    assert init.attrs == {"workers": 2} and init.counters["pool_bytes"] > 0

    cons = [r for r in recs if r.name == "frontend.consolidate"]
    assert len(cons) == 1
    under = tree[cons[0].index]
    assert [r.name for r in under] == ["frontend.full_fetch", "engine.init",
                                       "engine.kv_gather",
                                       "engine.rebuild_rows"]
    fetch, init, gather, _ = under
    assert fetch.counters["bytes"] == store.total_bytes
    [loaded] = tree[fetch.index]
    assert loaded.name == "loader.stage"
    assert loaded.attrs["bytes"] == store.total_bytes
    assert init.attrs == {"workers": 1}
    assert gather.counters["moved_bytes"] == ep.last_migration_bytes > 0
    assert gather.attrs["live_blocks"] > 0

    steps = _check_steps(recs, outs + more, firsts + more_firsts)
    assert steps[0].counters["prefill_tokens"] == 6
    # new workers trace and lower their executables again, the
    # consolidated one included
    for first in (steps[0], steps[len(outs)]):
        built = sum(r.counters.get("executables", 0)
                    for r in subtree(first, tree))
        assert built > 0


def test_fused_step_records_the_same_spans(deployed):
    cfg, params, _ = deployed
    names = {}
    for fused in (False, True):
        eng = Engine(cfg, [params], max_batch=2, max_seq=64, paged=True,
                     fused=fused)
        handles = [eng.submit([5, 3, 8, 1], SamplingParams(max_new=5)),
                   eng.submit([9, 2], SamplingParams(max_new=5))]
        mark = last_index()
        outs, firsts = _drive(eng, handles, 4)
        recs = since(mark)
        _check_steps(recs, outs, firsts)
        names[fused] = Counter(r.name for r in recs)
    assert set(names[True]) == set(names[False]) == \
        STEP_CHILDREN | {"engine.step"}
    # one sample and one emit per decoding step, not one per request
    for fused in (False, True):
        assert names[fused]["engine.sample"] == \
            names[fused]["engine.emit"] == 4


def test_paged_decode_counts_live_and_table_pages(deployed, monkeypatch):
    """``runner.decode`` counts the pages the paged kernel reads, Σ
    ``cdiv(kv_len, block_size)`` over the decoded rows, beside the rows ×
    table width the whole-table walk read."""
    cfg, params, _ = deployed
    eng = Engine(cfg, [params], max_batch=2, max_seq=64, block_size=16,
                 paged=True, fused=False)
    lens_seen = []
    decode = eng.runner.decode

    def recording(reqs, skip_slots=()):
        lens_seen.append([r.pos_next + 1 for r in reqs])
        return decode(reqs, skip_slots=skip_slots)

    monkeypatch.setattr(eng.runner, "decode", recording)
    # the first prompt's rows cross a page boundary while decoding
    handles = [eng.submit(list(range(1, 15)), SamplingParams(max_new=6)),
               eng.submit([9, 2], SamplingParams(max_new=6))]
    mark = last_index()
    _drive(eng, handles, 6)
    recs = [r for r in since(mark) if r.name == "runner.decode"]
    assert len(recs) == len(lens_seen) > 0
    assert any(n > 16 for lens in lens_seen for n in lens)
    width = 64 // 16 + 1
    for rec, lens in zip(recs, lens_seen):
        assert rec.counters["kv_pages_live"] == sum(-(-n // 16)
                                                    for n in lens)
        assert rec.counters["kv_pages_table"] == len(lens) * width
