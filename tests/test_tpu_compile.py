"""The serving path's attention kernels compile for a TPU v5e chip.

No chip is needed: the TPU compiler builds for a described ``v5e:2x2``
topology and refuses what the chip would refuse (block shapes that break
the (8, 128) tiling, too much VMEM), which interpret mode never checks.
Shapes are granite-3-8b's widths (32 q / 8 kv heads, head_dim 128,
page 16, bf16) at the serving engine's pool size for 8 x 2048 tokens.

The topology is described inside a fixture: only one process at a time
may load the TPU library, so no module does it while being imported.
"""

import base64
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import compile_cache
from repro.kernels import decode_attention as da
from repro.kernels import flash_attention as fa
from repro.kernels import ragged_attention as ra

B, HQ, HKV, HD, PAGE = 8, 32, 8, 128, 16
MAX_SEQ = 2048
NB = MAX_SEQ // PAGE + 1                 # block-table width (runner.py)
N_PAGES = B * NB + 1                     # pool blocks + the null page
T = 512                                  # ragged tokens in one launch


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _pool(one_chip, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct((N_PAGES, PAGE, HKV, HD), dtype,
                                sharding=one_chip)


def _structs(one_chip, *shapes_dtypes):
    return [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes_dtypes]


@pytest.mark.parametrize("kernel", ["flash_attention", "decode_attention",
                                    "paged_decode_attention",
                                    "ragged_paged_attention",
                                    "ragged_paged_attention_int8"])
def test_kernel_compiles_for_v5e(kernel, one_chip, no_persistent_cache):
    bf, i32, f32 = jnp.bfloat16, jnp.int32, jnp.float32
    if kernel == "flash_attention":
        args = _structs(one_chip, ((1, T, HQ, HD), bf), ((1, T, HKV, HD), bf),
                        ((1, T, HKV, HD), bf))
        fn = lambda q, k, v: fa.flash_attention(q, k, v, causal=True)
    elif kernel == "decode_attention":
        args = _structs(one_chip, ((B, 1, HQ, HD), bf),
                        ((B, MAX_SEQ, HKV, HD), bf),
                        ((B, MAX_SEQ, HKV, HD), bf), ((B,), i32))
        fn = da.decode_attention
    elif kernel == "paged_decode_attention":
        q, tables, lens = _structs(one_chip, ((B, 1, HQ, HD), bf),
                                   ((B, NB), i32), ((B,), i32))
        args = [q, _pool(one_chip), _pool(one_chip), tables, lens]
        fn = da.paged_decode_attention
    else:
        q, tables, row, pos = _structs(one_chip, ((T, HQ, HD), bf),
                                       ((B, NB), i32), ((T,), i32),
                                       ((T,), i32))
        if kernel == "ragged_paged_attention":
            args = [q, _pool(one_chip), _pool(one_chip), tables, row, pos]
            fn = ra.ragged_paged_attention
        else:
            quant = _structs(one_chip, *[((N_PAGES, PAGE, HKV), f32)] * 4)
            args = [q, _pool(one_chip, jnp.int8), _pool(one_chip, jnp.int8),
                    tables, row, pos, *quant]

            def fn(q, kp, vp, tables, row, pos, ks, kz, vs, vz):
                return ra.ragged_paged_attention(
                    q, kp, vp, tables, row, pos,
                    kv_quant={"k_scale": ks, "k_zero": kz,
                              "v_scale": vs, "v_zero": vz})
    assert "tpu_custom_call" in _compiled_text(fn, *args)


@pytest.fixture
def cache_locations(monkeypatch, tmp_path):
    """The source-location settings ``enable_compile_cache`` makes,
    undone afterwards."""
    keys = ("jax_include_full_tracebacks_in_locations",
            "jax_hlo_source_file_canonicalization_regex")
    saved = {k: getattr(jax.config, k) for k in keys}
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    compile_cache.enable_compile_cache()
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def _kernel_body(fn, *args) -> bytes:
    """The Mosaic body of the one Pallas kernel in ``fn``'s program."""
    text = jax.jit(fn).lower(*args).as_text()
    body = re.search(r"\\22body\\22: \\22([A-Za-z0-9+/=]+)", text)
    return base64.b64decode(body.group(1))


def test_kernel_body_is_the_same_from_any_caller(one_chip, cache_locations):
    """The persistent cache keys on the kernel body. After
    ``enable_compile_cache`` it names neither its callers nor the
    checkout's absolute path, so an edit to a caller or another checkout
    of the same code still finds the cached executable."""
    q, tables, lens = _structs(one_chip, ((B, 1, HQ, HD), jnp.bfloat16),
                               ((B, NB), jnp.int32), ((B,), jnp.int32))
    args = [q, _pool(one_chip), _pool(one_chip), tables, lens]

    def one_caller(*a):
        return da.paged_decode_attention(*a)

    def another_caller(*a):
        return da.paged_decode_attention(*a)

    body = _kernel_body(one_caller, *args)
    assert body == _kernel_body(another_caller, *args)
    assert str(compile_cache.CHECKOUT).encode() not in body
    assert b"src/repro/kernels/decode_attention.py" in body
