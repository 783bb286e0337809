"""The readers of the program's spans on hand-made ring records and
hand-made trace events with known answers; each leaves its metric out
(None) where there is nothing to read, a program without spans included;
and a tiny traced run on the CPU reports the ring's metrics."""

import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench.lib import check
from bench.lib.trace import Event
from bench.metrics import _spans as sp
from repro.spans import Record

ROOT = Path(__file__).resolve().parents[2]
RING_METRICS = ("store_read_s", "first_step_builds", "consolidate_fetch_s",
                "consolidated_build_s")
TRACE_METRICS = ("decode_sample_idle_ms", "decode_host_idle_ms")
S = 1_000_000_000


def reader(name):
    return check.load_module(ROOT / "bench" / "metrics" / f"{name}.py",
                             f"bench_metric_{name}")


class Ring:
    """Hand-made records, each given in seconds."""

    def __init__(self):
        self.recs = []

    def add(self, name, start, end, parent=None, **counters):
        r = Record(len(self.recs), name,
                   None if parent is None else parent.index, {})
        r.start_ns, r.end_ns = int(start * S), int(end * S)
        r.counters = counters
        self.recs.append(r)
        return r

    def cycle(self, t, reads, builds, fetch_s, after_ns):
        """One cycle starting at ``t``: a cold start reading ``reads``
        ns per stage, a first step whose tree builds ``builds``
        executables, a consolidation whose fetch takes ``fetch_s``, then
        two steps whose trees spend ``after_ns`` each on builds."""
        cs = self.add("frontend.cold_start", t + 0.1, t + 1)
        for i, ns in enumerate(reads):
            self.add("loader.stage", t + 0.2 + i * 0.1, t + 0.3 + i * 0.1,
                     cs, read_ns=ns, put_ns=7)
        step = self.add("engine.step", t + 1, t + 2, executables=1)
        self.add("engine.prefill", t + 1.1, t + 1.5, step,
                 executables=builds - 3)
        self.add("runner.decode", t + 1.6, t + 1.9, step, executables=2)
        self.add("engine.step", t + 2, t + 3, executables=5)
        cons = self.add("frontend.consolidate", t + 3, t + 5)
        full = self.add("frontend.full_fetch", t + 3, t + 3 + fetch_s, cons)
        self.add("loader.stage", t + 3, t + 3 + fetch_s, full,
                 read_ns=10 ** 9)
        self.add("engine.init", t + 4, t + 5, cons, trace_ns=999)
        for k in range(2):
            st = self.add("engine.step", t + 6 + k, t + 6.5 + k,
                          trace_ns=after_ns // 2)
            self.add("runner.decode", t + 6.1 + k, t + 6.2 + k, st,
                     lower_ns=after_ns // 4, compile_ns=after_ns // 4)


@pytest.fixture
def ring(monkeypatch):
    r = Ring()
    # a warm-up cycle before the window's first arrival drops out
    r.cycle(0, [5 * S], 99, 9.0, 9 * S)
    r.cycle(100, [S // 10, S // 5], 10, 0.25, S)
    r.cycle(200, [S // 5, S // 5], 12, 0.75, 3 * S)
    monkeypatch.setattr(sp, "ring", lambda: r.recs)
    return r


def ring_run(arrivals=(100.0, 200.0)):
    return SimpleNamespace(cycles=[SimpleNamespace(arrival=a)
                                   for a in arrivals], trace=None)


def test_ring_readers_median_over_cycles(ring):
    run = ring_run()
    assert reader("store_read_s").read(run) == pytest.approx(0.35)
    assert reader("first_step_builds").read(run) == 11
    assert reader("consolidate_fetch_s").read(run) == pytest.approx(0.5)
    assert reader("consolidated_build_s").read(run) == pytest.approx(4.0)


def test_ring_readers_split_cycles_by_arrival(ring):
    run = ring_run((200.0,))
    assert reader("store_read_s").read(run) == pytest.approx(0.4)
    assert reader("first_step_builds").read(run) == 12
    assert reader("consolidate_fetch_s").read(run) == pytest.approx(0.75)
    assert reader("consolidated_build_s").read(run) == pytest.approx(6.0)


@pytest.mark.parametrize("name", RING_METRICS)
def test_ring_readers_without_records(name, monkeypatch):
    monkeypatch.setattr(sp, "ring", lambda: None)
    assert reader(name).read(ring_run()) is None
    r = Ring()
    r.add("engine.other", 150, 151)
    monkeypatch.setattr(sp, "ring", lambda: r.recs)
    assert reader(name).read(ring_run()) is None
    assert reader(name).read(ring_run(())) is None


def test_ring_is_none_without_the_program_spans(monkeypatch):
    import repro
    monkeypatch.setitem(sys.modules, "repro.spans", None)
    monkeypatch.delattr(repro, "spans", raising=False)
    assert sp.ring() is None


MS = 1_000_000


def dev(s, t):
    return Event("%fusion bf16[8]", s * MS, t * MS, "")


def host(name, s, t):
    return Event(name, s * MS, t * MS, name)


def traced(host_events, n_steps=2):
    events = [dev(0, 30), dev(32, 45), dev(50, 80), dev(83, 95)]
    return SimpleNamespace(trace=SimpleNamespace(
        events=events, host=host_events, lo=0, hi=100 * MS,
        n_steps=n_steps))


STEPS = [host("engine.step#_r=1,step_num=1#", 0, 48),
         host("engine.step#_r=1,step_num=2#", 50, 98),
         # the same span seen twice counts once
         host("engine.step", 50, 98),
         host("engine.sample", 28, 36), host("engine.sample", 78, 86),
         host("bench.step", 0, 100), host("engine.emit", 95, 98)]


def test_trace_readers_split_idle_between_sample_and_the_rest():
    run = traced(STEPS)
    # idle inside the steps: 30-32, 45-48, 80-83, 95-98 (11 ms); inside
    # the samples 30-32 and 80-83 (5 ms); per step of 2
    assert reader("decode_sample_idle_ms").read(run) == pytest.approx(2.5)
    assert reader("decode_host_idle_ms").read(run) == pytest.approx(3.0)


def test_trace_readers_clip_to_the_traced_steps():
    run = traced([host("engine.step", -50, 48),
                  host("engine.sample", -20, 36)], n_steps=1)
    assert reader("decode_sample_idle_ms").read(run) == pytest.approx(2.0)
    assert reader("decode_host_idle_ms").read(run) == pytest.approx(3.0)


@pytest.mark.parametrize("name", TRACE_METRICS)
def test_trace_readers_without_spans(name):
    assert reader(name).read(SimpleNamespace(trace=None)) is None
    no_steps = [e for e in STEPS if not e.name.startswith("engine.step")]
    assert reader(name).read(traced(no_steps)) is None
    assert reader(name).read(traced(STEPS, n_steps=0)) is None


def test_tiny_traced_run_reports_the_ring_metrics():
    """On the CPU the ring metrics read (the program keeps its spans);
    the trace metrics find no device plane and are left out."""
    from bench.tests.test_faults import tiny_run
    res = tiny_run(trace=True)
    got = res["metrics"]
    assert set(got) == {"load_s", "build_s", "consolidate_s",
                        *RING_METRICS}
    assert got["first_step_builds"]["value"] >= 1
    assert 0 < got["store_read_s"]["value"] <= got["load_s"]["value"]
    assert 0 < got["consolidate_fetch_s"]["value"] \
        <= got["consolidate_s"]["value"]
    assert got["consolidated_build_s"]["value"] > 0
