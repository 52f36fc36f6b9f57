"""The serving kernels compile for a TPU v5e at real widths.

Interpret mode (every other kernel test) never checks Mosaic's tiling
rules, so these compile the paged-decode, chunked-prefill and
paged-prefill kernels for a *described* v5e:2x2 — no chip attached —
at Qwen2.5-14B head shapes (H=40, KV=8, hd=128) and at the per-shard
widths of a TP=4 instance (H=10, KV=2), and the paged-decode kernel at
Phi-4-mini's (H=24, KV=8) over a full serving table.  The compiler refuses a bad
block shape here exactly as it would on the chip.

The topology is described inside a module fixture (never at import):
only one process may load the TPU library, and every test worker
imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.ops import (
    chunked_prefill_attention_op, paged_decode_attention_op,
    paged_prefill_attention_op,
)

HD = 128
WIDTHS = {"qwen2.5-14b": (40, 8), "tp4-shard": (10, 2)}
DTYPES = {"bf16": jnp.bfloat16, "int8": jnp.int8, "fp8": jnp.float8_e4m3fn}


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache; keep it out of the cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _compiles_to_kernel(op, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    text = jax.jit(lambda *a: op(*a, interpret=False)).lower(
        *args).compile().as_text()
    return "tpu_custom_call" in text


def _pool_shapes(KV, page, prec, n_pages=512):
    pages = [((n_pages, KV, page, HD), DTYPES[prec])] * 2
    scales = [((n_pages, 1, page), jnp.float32)] * 2
    return pages, (scales if prec != "bf16" else [])


@pytest.mark.parametrize("width", sorted(WIDTHS))
@pytest.mark.parametrize("prec,page", [("bf16", 8), ("bf16", 32),
                                       ("int8", 32), ("fp8", 32)])
def test_paged_decode_compiles(v5e, width, prec, page):
    H, KV = WIDTHS[width]
    B, n_pp = 8, 16
    pages, scales = _pool_shapes(KV, page, prec)
    assert _compiles_to_kernel(
        paged_decode_attention_op, v5e, ((B, H, HD), jnp.bfloat16),
        *pages, ((B, n_pp), jnp.int32), ((B,), jnp.int32), *scales)


@pytest.mark.parametrize("prec", ["bf16", "int8"])
def test_paged_decode_compiles_at_a_full_table(v5e, prec):
    """Phi-4-mini's widths (24 heads over 8 KV heads) on 16 rows of an
    8-token-page table 512 pages wide, as the serving engine runs them."""
    B, n_pp, page = 16, 512, 8
    pages, scales = _pool_shapes(8, page, prec, n_pages=2560)
    assert _compiles_to_kernel(
        paged_decode_attention_op, v5e, ((B, 24, HD), jnp.bfloat16),
        *pages, ((B, n_pp), jnp.int32), ((B,), jnp.int32), *scales)


@pytest.mark.parametrize("width", sorted(WIDTHS))
@pytest.mark.parametrize("prec", ["bf16", "int8"])
def test_chunked_prefill_compiles(v5e, width, prec):
    H, KV = WIDTHS[width]
    B, T, S = 2, 256, 1024
    kv = [((B, KV, S, HD), DTYPES[prec])] * 2
    scales = [((B, 1, S), jnp.float32)] * 2 if prec != "bf16" else []
    assert _compiles_to_kernel(
        chunked_prefill_attention_op, v5e, ((B, H, T, HD), jnp.bfloat16),
        *kv, ((B,), jnp.int32), *scales)


@pytest.mark.parametrize("width", sorted(WIDTHS))
@pytest.mark.parametrize("prec", ["bf16", "int8"])
def test_paged_prefill_compiles(v5e, width, prec):
    H, KV = WIDTHS[width]
    B, T, n_pp, page = 4, 256, 64, 8
    pages, scales = _pool_shapes(KV, page, prec)
    assert _compiles_to_kernel(
        paged_prefill_attention_op, v5e, ((B, T, H, HD), jnp.bfloat16),
        *pages, ((B, n_pp), jnp.int32), ((B,), jnp.int32), *scales)
