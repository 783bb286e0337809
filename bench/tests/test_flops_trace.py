"""The analytic operation and byte counts, the table of peaks, and the
trace reduction, on hand-made events and on a small recorded trace of a
TPU v5e (``data/decode_steps.xplane.pb``)."""

import json
from pathlib import Path

import pytest

from bench.lib import flops, peaks
from bench.lib import trace as tr

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).parent / "data"


def cfg(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())


def test_matmul_params_per_token_granite():
    c = cfg("granite-3-8b-d12")
    per_layer = 4096 * 4096 + 2 * 4096 * 1024 + 4096 * 4096 \
        + 3 * 4096 * 12800
    assert flops.matmul_params_per_token(c) == 12 * per_layer + 4096 * 49155


def test_internlm2_head_share_of_bytes():
    """The head is about a fifth of internlm2-20b-d4's weights, and under
    a tenth of granite-3-8b-d12's (both with an untied head)."""
    def head_share(c):
        d, v = c["hidden_size"], c["vocab_size"]
        return d * v / (flops.matmul_params_per_token(c) + d * v)
    assert 0.19 < head_share(cfg("internlm2-20b-d4")) < 0.23
    assert head_share(cfg("granite-3-8b-d12")) < 0.08


def test_paged_decode_call_counts_only_live_context():
    c = cfg("granite-3-8b-d12")
    f, b = flops.paged_decode_call(c, [100, 1])
    assert f == 4 * 101 * 32 * 128
    assert b == (2 * 101 * 8 * 128 + 2 * 2 * 32 * 128) * 2
    assert flops.decode_step_flops(c, []) == 0


def test_roofline_picks_the_larger_bound():
    p = peaks.peaks("TPU v5 lite")
    t, bound = flops.roofline_seconds(1e12, 1e6, p)
    assert bound == "compute" and t == pytest.approx(1e12 / 197e12)
    t, bound = flops.roofline_seconds(1e6, 819e9, p)
    assert bound == "memory" and t == pytest.approx(1.0)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        peaks.peaks("cpu")


def ev(name, s, t):
    return tr.Event(name, s, t, name)


def test_busy_is_the_union_and_gaps_are_named_by_host():
    ops = [ev("a", 0, 10), ev("b", 5, 20), ev("a", 40, 50), ev("c", 90, 95)]
    host = [ev("bench.step", 0, 60), ev("bench.step", 60, 100),
            ev("argmax", 22, 38), ev("python", 0, 100)]
    assert tr.busy_intervals(ops, 0, 100) == [(0, 20), (40, 50), (90, 95)]
    assert tr.busy_ns(ops, 0, 100) == 35
    assert tr.busy_ns(ops, 8, 45) == 17
    assert tr.time_by_name(ops, 0, 100) == {"a": 20, "b": 15, "c": 5}
    assert tr.kernel_ns(ops, 0, 100, "a") == (20, 2)
    gaps = tr.idle_gaps(ops, host, 0, 100)
    assert [g[0] for g in gaps] == ["bench.step", "argmax", "bench.step"]
    assert gaps[0][1] == pytest.approx(40e-9)


@pytest.fixture(scope="module")
def recorded():
    """32 consolidated decode steps of granite-3-8b-d12 with 8 requests
    on one TPU v5e (the benchmark's traced span, seed 11)."""
    return tr.load(str(DATA / "decode_steps.xplane.pb.gz"))


def test_recorded_trace_reduces(recorded):
    steps = tr.spans(recorded, "bench.step")
    ev = tr.device_events(recorded)
    assert len(steps) == 32 and sorted(recorded.ops) == ["/device:TPU:0"]
    lo, hi = steps[0].start, steps[-1].end
    busy = tr.busy_ns(ev, lo, hi)
    assert busy == pytest.approx(585486248.0)
    assert hi - lo == pytest.approx(682875795.0)
    # one Pallas kernel (the paged decode attention) per layer per step
    ns, n = tr.kernel_ns(ev, lo, hi, 'custom_call_target="tpu_custom_call"')
    assert n == 12 * 32 and ns == pytest.approx(254051730.0)
    assert ns < busy


def test_recorded_breakdown_leaves_out_the_layer_loop(recorded):
    ev = tr.device_events(recorded)
    steps = tr.spans(recorded, "bench.step")
    lo, hi = steps[0].start, steps[-1].end
    leaf = tr.leaves(ev)
    assert not any(e.name.startswith("%while") for e in leaf)
    assert any(e.name.startswith("%while") for e in ev)
    by_name = tr.time_by_name(leaf, lo, hi)
    assert sum(by_name.values()) <= tr.busy_ns(ev, lo, hi) * (1 + 1e-9)
    top = max(by_name, key=by_name.get)
    assert top.startswith("%tpu_custom_call") and "bf16[8,8,4,128]" in top
    gaps = tr.idle_gaps(ev, recorded.host, lo, hi)
    assert len(gaps) == 10 and all(g[1] > 0 for g in gaps)
    assert gaps == sorted(gaps, key=lambda g: -g[1])
