"""The analytic operation and byte counts of the dense architecture, the
table of peaks, and the trace reduction, on hand-made events and on a
small recorded trace of a TPU v5e (``data/decode_steps.xplane.pb``)."""

import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench.lib import check, flops, peaks
from bench.lib import trace as tr
from bench.references import dense_gqa

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).parent / "data"


def cfg(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())


def test_matmul_params_per_token_granite():
    c = cfg("granite-3-8b-d12")
    per_layer = 4096 * 4096 + 2 * 4096 * 1024 + 4096 * 4096 \
        + 3 * 4096 * 12800
    assert dense_gqa.matmul_params_per_token(c) == 12 * per_layer + 4096 * 49155


def test_internlm2_head_share_of_bytes():
    """The head is about a fifth of internlm2-20b-d4's weights, and under
    a tenth of granite-3-8b-d12's (both with an untied head)."""
    def head_share(c):
        d, v = c["hidden_size"], c["vocab_size"]
        return d * v / (dense_gqa.matmul_params_per_token(c) + d * v)
    assert 0.19 < head_share(cfg("internlm2-20b-d4")) < 0.23
    assert head_share(cfg("granite-3-8b-d12")) < 0.08


def test_paged_decode_call_counts_only_live_context():
    c = cfg("granite-3-8b-d12")
    f, b = dense_gqa.paged_decode_call(c, [100, 1])
    assert f == 4 * 101 * 32 * 128
    assert b == (2 * 101 * 8 * 128 + 2 * 2 * 32 * 128) * 2
    assert dense_gqa.decode_step_flops(c, []) == 0


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
    # one Pallas kernel (the paged decode attention) per layer per step;
    # the program then gave its kernels no name and no metadata
    ns, n = tr.custom_call_ns(ev, lo, hi, "tpu_custom_call")
    assert n == 12 * 32 and ns == pytest.approx(254051730.0)
    assert ns < busy
    assert tr.kernel_ns(ev, lo, hi, "paged_decode_attention") == (0.0, 0)


def test_recorded_roofline_reading_is_unchanged(recorded, traced_lengths):
    """The kernel reader on the recorded trace, over the lengths of a
    coldburst cycle's traced steps, reads what it read when it matched a
    needle over each operation's whole text."""
    steps = tr.spans(recorded, "bench.step")
    ev = tr.device_events(recorded)
    lo, hi = steps[0].start, steps[-1].end
    run = SimpleNamespace(
        cfg=cfg("granite-3-8b-d12"), traced_steps=traced_lengths,
        peak=peaks.peaks("TPU v5 lite"),
        trace=SimpleNamespace(events=ev, lo=lo, hi=hi,
                              window_s=(hi - lo) * 1e-9))
    roofline = check.load_module(
        ROOT / "bench" / "metrics" / "paged_decode_roofline.py", "roofline")
    assert roofline.read(run) == 2.0396338452790967


def test_kernel_found_by_own_name_or_metadata():
    """A call is the kernel by its own instruction name or by the
    ``kernel_metadata`` among its own attributes (as the compiler writes
    it for v5e); an operation that reads its output names it as an
    operand and is not a call."""
    meta = ('%tpu_custom_call.3 = bf16[8,4]{1,0:T(8,128)} custom-call('
            '%a, %b), custom_call_target="tpu_custom_call", '
            'frontend_attributes={kernel_metadata={\n"kernel":"k_attn"\n}}')
    named = ('%k_attn.7 = (bf16[8,4]{1,0}, f32[8]{0}) custom-call(%a), '
             'custom_call_target="tpu_custom_call"')
    other = ('%k_attn_b.2 = bf16[8,4]{1,0} custom-call(%a), '
             'custom_call_target="tpu_custom_call", frontend_attributes='
             '{kernel_metadata={"kernel":"k_attn_b"}}')
    readers = ['%bitcast.1 = bf16[32]{0} bitcast(%k_attn.7)',
               '%fusion.2 = bf16[32]{0} fusion(%tpu_custom_call.3), '
               'calls=%fused_computation, metadata={op_name="k_attn"}']
    ops = [tr.Event(str(i), 10 * i, 10 * i + 1 + i, text)
           for i, text in enumerate([meta, named, other] + readers)]
    assert tr.kernel_metadata(meta) == {"kernel": "k_attn"}
    assert tr.kernel_metadata(other) == {"kernel": "k_attn_b"}
    assert tr.kernel_metadata(readers[0]) == {}
    assert [tr.op_name(t) for t in (meta, named, readers[1])] == \
        ["tpu_custom_call", "k_attn", "fusion"]
    assert tr.kernel_ns(ops, 0, 100, "k_attn") == (3, 2)
    assert tr.kernel_ns(ops, 0, 100, "k_attn_b") == (3, 1)
    assert tr.custom_call_ns(ops, 0, 100, "tpu_custom_call") == (6, 3)


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
