"""The plain reference against the program's paged prefill and decode,
at a tiny size on the CPU, and the weights both sides are given."""

import json
from pathlib import Path


import jax.numpy as jnp
import numpy as np
import pytest

from bench.lib import program, weights
from bench.lib.check import reference_module
from bench.references import dense_gqa

DATA = Path(__file__).parent / "data"


def tiny(dtype="float32"):
    cfg = json.loads((DATA / "tiny.json").read_text())
    cfg["torch_dtype"] = dtype
    return cfg


def test_weights_single_layer_matches_whole_tree():
    cfg = tiny("bfloat16")
    seed = 2 ** 40 + 3
    leaves = dense_gqa.layout(cfg)
    tree = weights.make_params(leaves, seed, jnp.bfloat16)
    key = weights.seed_key(seed)
    for i, leaf in enumerate(leaves):
        node = tree
        for k in leaf.path:
            node = node[k]
        assert leaf.stack in (0, cfg["num_hidden_layers"])
        for layer in range(leaf.stack or 1):
            one = weights.layer_leaf(key, leaves, i, layer, jnp.bfloat16)
            want = node[layer] if leaf.stack else node
            assert np.array_equal(np.asarray(one), np.asarray(want)), \
                (leaf.path, layer)


def test_weights_depend_on_high_seed_bits():
    cfg = tiny()
    leaves = dense_gqa.layout(cfg)
    a = weights.layer_leaf(weights.seed_key(5), leaves, 1, 0, jnp.float32)
    b = weights.layer_leaf(weights.seed_key(5 + 2 ** 32), leaves, 1, 0,
                           jnp.float32)
    assert not np.array_equal(np.asarray(a), np.asarray(b))
    assert abs(float(jnp.std(a)) * 256 ** 0.5 - 1.0) < 0.05


@pytest.mark.parametrize("n_stages", [1, 2])
def test_reference_matches_paged_prefill_and_decode(n_stages):
    """Logits the program's paged engine computes at every position of
    prefill (its last row) and decode, in float32, against the reference
    over the whole sequence."""
    from repro.serving.api import SamplingParams
    from repro.serving.engine import Engine

    cfg = tiny("float32")
    seed = 17
    model, params = program.served_params(cfg, seed)
    stages = [model.slice_stage_params(params, n_stages, i)
              for i in range(n_stages)]
    eng = Engine(program.model_config(cfg), stages, max_batch=4,
                 max_seq=128, paged=True)
    seen = {}
    fwd, dec = eng.runner.forward_batch, eng.runner.decode

    def forward_batch(segments):
        h = fwd(segments)
        for i, (slot, toks, pos0) in enumerate(segments):
            seen[(eng.scheduler.slots[slot].rid, pos0 + len(toks) - 1)] = \
                np.asarray(h[i])
        return h

    def decode(reqs, skip_slots=()):
        h = dec(reqs, skip_slots)
        for r in reqs:
            seen[(r.rid, r.pos_next)] = np.asarray(h[r.slot, 0])
        return h

    eng.runner.forward_batch, eng.runner.decode = forward_batch, decode
    rng = np.random.default_rng(0)
    reqs = [eng.submit(rng.integers(0, cfg["vocab_size"], n).tolist(),
                       SamplingParams(max_new=m))
            for n, m in [(37, 9), (5, 12), (70, 6)]]
    while eng.has_work():
        eng.step()
    ref = reference_module(cfg).Reference(cfg, seed)
    seqs = [r.prompt + r.generated[:-1] for r in reqs]
    rows = [list(range(len(r.prompt) - 1, len(s))) for r, s in
            zip(reqs, seqs)]
    got_ref = ref.logits(seqs, rows, length=128, width=16)
    vocab = cfg["vocab_size"]
    n = 0
    for r, rw, want in zip(reqs, rows, got_ref):
        for j, pos in enumerate(rw):
            got = seen[(r.rid, pos)][:vocab]
            np.testing.assert_allclose(got, np.asarray(want[j]),
                                       atol=2e-4, rtol=0)
            n += 1
    assert n == sum(len(r.generated) for r in reqs)
