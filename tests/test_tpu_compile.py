"""The Pallas kernels compile for a TPU v5e chip, at smollm-135m widths
and, for the float paged-attention kernel, at the qwen2.5-14b-l12
serving cell's.

Compiled ahead of time for a described (not attached) ``v5e:2x2``
topology: Mosaic refuses here what interpret mode accepts — block shapes
off the (8, 128) tiling, casts it has no lowering for, layouts it cannot
relayout.  Each test asserts that the compiled program holds the Mosaic
kernel (``tpu_custom_call``).  The topology is described inside a fixture,
only once a test of this file runs, and every test skips where it cannot
be described.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config, get_smoke
from repro.kernels.bitplane_matmul.ops import bitplane_matmul_pallas
from repro.kernels.log2quant.ops import log2_quantize_pallas
from repro.kernels.paged_attention.ops import (paged_decode_attention,
                                               paged_decode_attention_quant)

CFG = get_config("smollm-135m")
H, G, D = CFG.n_heads, CFG.n_kv_heads, CFG.head_dim
B, PAGE_LEN, NB = 8, 16, 16
N_PAGES = 1 + B * NB


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe skips
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip are written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    prev = jax.config.values["jax_enable_compilation_cache"]
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def spec(topo):
    from jax.sharding import SingleDeviceSharding

    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


def _assert_mosaic(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("splits", [1, 2])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention(spec, dtype, splits):
    fn = functools.partial(paged_decode_attention, splits=splits,
                           interpret=False)
    _assert_mosaic(fn, spec((B, 1, H, D), dtype),
                   spec((N_PAGES, PAGE_LEN, G, D), dtype),
                   spec((N_PAGES, PAGE_LEN, G, D), dtype),
                   spec((B, NB), jnp.int32), spec((B,), jnp.int32))


def test_paged_attention_cell_geometry(spec):
    """The float kernel at the qwen2.5-14b-l12 serving cell's geometry:
    32 slots, 40/8 heads of 128, a 260-page table (33 blocks of 8 pages)
    over a 2,400-page bf16 pool."""
    b, g, r, d, nb, n_pages = 32, 8, 5, 128, 260, 2400
    fn = functools.partial(paged_decode_attention, splits=1,
                           interpret=False)
    _assert_mosaic(fn, spec((b, 1, g * r, d), jnp.bfloat16),
                   spec((n_pages, PAGE_LEN, g, d), jnp.bfloat16),
                   spec((n_pages, PAGE_LEN, g, d), jnp.bfloat16),
                   spec((b, nb), jnp.int32), spec((b,), jnp.int32))


@pytest.mark.parametrize("splits", [1, 2])
@pytest.mark.parametrize("n_bits,code", [(4, jnp.int8), (8, jnp.int16)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_quant(spec, dtype, n_bits, code, splits):
    fn = functools.partial(paged_decode_attention_quant, n_bits=n_bits,
                           splits=splits, interpret=False)
    pool = spec((N_PAGES, PAGE_LEN, G, D), code)
    scale = spec((N_PAGES, G), jnp.int32)
    tail = spec((B, 2 * PAGE_LEN + 1, G, D), dtype)
    _assert_mosaic(fn, spec((B, 1, H, D), dtype), pool, scale, pool,
                   scale, tail, tail, spec((B, NB), jnp.int32),
                   spec((B,), jnp.int32))


def test_bitplane_matmul(spec):
    fn = functools.partial(bitplane_matmul_pallas, interpret=False)
    _assert_mosaic(fn, spec((B, CFG.d_model), jnp.int8),
                   spec((B, CFG.d_model), jnp.int8),
                   spec((8, CFG.d_model, CFG.d_ff), jnp.uint8))


def test_log2quant(spec):
    fn = functools.partial(log2_quantize_pallas, interpret=False)
    _assert_mosaic(fn, spec((B, CFG.d_model), jnp.float32))


def test_tick_program_keeps_kernel_name(spec, monkeypatch):
    """The paged-attention kernel compiled into the serve tick program
    keeps the instruction name a profile finds it by,
    ``_paged_decode_attention``, and sits under the tick's ``decode`` and
    the model's ``attn`` scopes."""
    import re

    from repro.models import init_params
    from repro.serving import ServeConfig, ServeScheduler

    # the kernel takes Mosaic over interpret mode by the default backend
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = get_smoke("smollm_135m").replace(n_layers=1)
    sched = ServeScheduler(cfg, init_params(jax.random.PRNGKey(0), cfg),
                           ServeConfig(max_slots=B, max_len=NB * PAGE_LEN,
                                       buckets=(64,), paged=True,
                                       page_len=PAGE_LEN, tick_steps=2,
                                       attn_kernel="pallas"))
    fn, args = sched.audit_programs()["tick"]
    args = jax.tree.map(lambda a: spec(a.shape, a.dtype), args)
    text = fn.lower(*args).compile().as_text()
    assert text.startswith("HloModule jit_tick_paged,")
    calls = re.findall(r'%(\S+) = .* custom-call\(.*'
                       r'custom_call_target="tpu_custom_call".*'
                       r'op_name="([^"]+)"', text)
    assert calls
    for name, op in calls:
        assert name.startswith("_paged_decode_attention")
        assert op.startswith("jit(tick_paged)/decode/")
        assert "/attn/" in op
