"""What a configuration's keys mean lives in its architecture module
(``bench/references/<reference>.py``), never in the generic harness.

The dense module took the dense code over unchanged: its layout, its
work counts and the weights it makes are pinned to the values the
harness gave before the move. A fixture architecture
(``data/two_slot.*``: a period of two attention slots, the second with
the program's MoE MLP, sizes under keys of its own) is served, rebuilt
and counted by the same generic code, which names neither it nor any key
of the dense configurations."""

import hashlib
import json
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.lib import check, peaks, program, weights
from bench.lib.trace import Event
from bench.references import dense_gqa

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).parent / "data"
GENERIC = ("lib", "drivers", "metrics")


def config(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())


def fixture():
    return json.loads((DATA / "two_slot.json").read_text())


def reader(name):
    return check.load_module(ROOT / "bench" / "metrics" / f"{name}.py",
                             f"bench_metric_{name}")


# The layouts as bench/lib/weights.py listed them before the move:
# (path, one layer's shape, stack length, std).
BLK = ("blocks", "slot00")
DENSE_LAYOUTS = {
    "granite-3-8b-d12": [
        (("embed", "tok"), (49408, 4096), 0, 1.0),
        (BLK + ("mixer", "w_q"), (4096, 4096), 12, 0.015625),
        (BLK + ("mixer", "w_k"), (4096, 1024), 12, 0.015625),
        (BLK + ("mixer", "w_v"), (4096, 1024), 12, 0.015625),
        (BLK + ("mixer", "w_o"), (4096, 4096), 12, 0.015625),
        (BLK + ("mixer", "norm"), (4096,), 12, 0.0),
        (BLK + ("mlp", "w_gate"), (4096, 12800), 12, 0.015625),
        (BLK + ("mlp", "w_up"), (4096, 12800), 12, 0.015625),
        (BLK + ("mlp", "w_down"), (12800, 4096), 12, 0.008838834764831844),
        (BLK + ("mlp", "norm"), (4096,), 12, 0.0),
        (("final_norm",), (4096,), 0, 0.0),
        (("lm_head",), (4096, 49408), 0, 0.015625),
    ],
    "internlm2-20b-d4": [
        (("embed", "tok"), (92672, 6144), 0, 1.0),
        (BLK + ("mixer", "w_q"), (6144, 6144), 4, 0.01275775907699572),
        (BLK + ("mixer", "w_k"), (6144, 1024), 4, 0.01275775907699572),
        (BLK + ("mixer", "w_v"), (6144, 1024), 4, 0.01275775907699572),
        (BLK + ("mixer", "w_o"), (6144, 6144), 4, 0.01275775907699572),
        (BLK + ("mixer", "norm"), (6144,), 4, 0.0),
        (BLK + ("mlp", "w_gate"), (6144, 16384), 4, 0.01275775907699572),
        (BLK + ("mlp", "w_up"), (6144, 16384), 4, 0.01275775907699572),
        (BLK + ("mlp", "w_down"), (16384, 6144), 4, 0.0078125),
        (BLK + ("mlp", "norm"), (6144,), 4, 0.0),
        (("final_norm",), (6144,), 0, 0.0),
        (("lm_head",), (6144, 92672), 0, 0.01275775907699572),
    ],
}


@pytest.mark.parametrize("name", sorted(DENSE_LAYOUTS))
def test_dense_layout_is_unchanged(name):
    got = [tuple(leaf) for leaf in dense_gqa.layout(config(name))]
    assert got == DENSE_LAYOUTS[name]


# (decode_step_flops, paged calls, paged operations, paged bytes) summed
# over the steps, as bench/lib/flops.py and the roofline reader counted
# them before the move.
DENSE_COUNTS = {
    ("granite-3-8b-d12", "100_1"): (10388226048, 12, 19857408, 5357568),
    ("granite-3-8b-d12", "none"): (0, 12, 0, 0),
    ("granite-3-8b-d12", "traced"): (1338741792768, 384, 16774791168,
                                     4243832832),
    ("internlm2-20b-d4", "100_1"): (8525414400, 4, 9928704, 1851392),
    ("internlm2-20b-d4", "none"): (0, 4, 0, 0),
    ("internlm2-20b-d4", "traced"): (1094111821824, 128, 8387395584,
                                     1422966784),
}


@pytest.mark.parametrize("name,lens", sorted(DENSE_COUNTS))
def test_dense_counts_are_unchanged(name, lens, traced_lengths):
    cfg = config(name)
    steps = {"100_1": [[100, 1]], "none": [[]],
             "traced": traced_lengths}[lens]
    flops = sum(dense_gqa.decode_step_flops(cfg, s) for s in steps)
    work = [dense_gqa.paged_decode_work(cfg, s) for s in steps]
    assert (flops, *map(sum, zip(*work))) == DENSE_COUNTS[(name, lens)]


def test_dense_weights_are_unchanged():
    """The tiny dense tree hashes as it did before the move: every leaf's
    key, path, shape and std are what they were."""
    cfg = json.loads((DATA / "tiny.json").read_text())
    tree = weights.make_params(dense_gqa.layout(cfg), 2 ** 40 + 3,
                               jnp.bfloat16)
    h = hashlib.sha256()
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    for path, leaf in sorted(flat, key=lambda kv:
                             jax.tree_util.keystr(kv[0])):
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == \
        "6fbf762884f67dea511de0eb11ccadacb8add22cdb7a4b68d943cd492b8a283f"


@pytest.fixture
def two_slot(monkeypatch):
    """The fixture architecture, found by its name as a configuration's
    ``reference`` is."""
    monkeypatch.setattr(check, "REFERENCES", DATA)
    return fixture()


def test_fixture_is_served_and_rebuilt_leaf_by_leaf(two_slot):
    seed = 2 ** 33 + 9
    model, params = program.served_params(two_slot, seed)
    shapes = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    assert shapes == jax.tree.map(lambda s: (s.shape, s.dtype),
                                  model.structs())
    cfg = program.model_config(two_slot)
    assert cfg.layer_plan == (("attn", "dense"), ("attn", "moe")) * 2
    leaves = check.reference_module(two_slot).layout(two_slot)
    assert {leaf.stack for leaf in leaves} == {0, 2}
    key = weights.seed_key(seed)
    for i, leaf in enumerate(leaves):
        node = params
        for k in leaf.path:
            node = node[k]
        for layer in range(leaf.stack or 1):
            one = weights.layer_leaf(key, leaves, i, layer, jnp.bfloat16)
            want = node[layer] if leaf.stack else node
            assert np.array_equal(np.asarray(one), np.asarray(want)), \
                (leaf.path, layer)


KERNEL_OP = ('%paged_decode_attention.{i} = bf16[2,2,2,16]{{3,2,1,0}} '
             'custom-call(%tables, %lens, %q, %k_pages, %v_pages), '
             'custom_call_target="tpu_custom_call", frontend_attributes='
             '{{kernel_metadata={{\n"kernel":"paged_decode_attention"\n}}}}')
READER_OP = ('%bitcast.{i} = bf16[2,1,64]{{2,1,0}} '
             'bitcast(%paged_decode_attention.{i})')


def fixture_run(cfg, steps, calls):
    """A traced window of ``len(steps)`` decode steps in which the paged
    kernel ran ``calls`` times (1 us each), each call followed by an
    operation that reads its output."""
    events = []
    for i in range(calls):
        t = 10_000 * i
        events.append(Event("k", t, t + 1000, KERNEL_OP.format(i=i)))
        events.append(Event("b", t + 1000, t + 1100, READER_OP.format(i=i)))
    hi = 10_000 * calls
    trace = SimpleNamespace(events=events, host=[], lo=0, hi=hi,
                            window_s=hi * 1e-9)
    return SimpleNamespace(cfg=cfg, trace=trace, traced_steps=steps,
                           peak=peaks.peaks("TPU v5 lite"))


def test_fixture_counts_reach_the_metrics(two_slot):
    steps = [[5, 9], [6, 10, 3]]
    arch = check.reference_module(two_slot)
    calls, flops, nbytes = map(sum, zip(*[
        arch.paged_decode_work(two_slot, s) for s in steps]))
    assert calls == 2 * 4                     # 2 periods x 2 attention slots
    run = fixture_run(two_slot, steps, calls)
    peak = run.peak
    least = max(flops / peak["bf16_flops_per_s"],
                nbytes / peak["hbm_bytes_per_s"])
    assert reader("paged_decode_roofline").read(run) == \
        pytest.approx(100 * least / (calls * 1e-6), rel=1e-12)
    model_flops = sum(arch.decode_step_flops(two_slot, s) for s in steps)
    assert reader("decode_mfu").read(run) == pytest.approx(
        100 * model_flops / (run.trace.window_s * peak["bf16_flops_per_s"]),
        rel=1e-12)
    # one call fewer than the module counts: the kernel reading is left out
    assert reader("paged_decode_roofline").read(
        fixture_run(two_slot, steps, calls - 1)) is None


ARCH_WORDS = ("intermediate_size", "num_attention_heads",
              "num_key_value_heads", "head_dim", "num_hidden_layers",
              "slot00", "two_slot", "tiny-two-slot", "dense_gqa")


@pytest.mark.parametrize("part", GENERIC)
def test_generic_harness_names_no_architecture(part):
    """No file of the generic harness names an architecture or reads a
    key of one: a new architecture brings only new files."""
    for path in sorted((ROOT / "bench" / part).glob("*.py")):
        text = path.read_text()
        assert not [w for w in ARCH_WORDS if w in text], path
