"""The main-path Pallas kernels compile for a TPU v5e at real widths.

No chip is needed: the TPU compiler is installed and compiles for a chip
that is described, not attached (``jax.experimental.topologies``).  These
are compile checks only — nothing runs, so they say nothing about results
or times; the interpret-mode parity tests and ``chip_smoke.py`` cover
those.  The topology is described inside a module fixture, never at
import, so every test worker collects the same tests and only the worker
that runs them loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.core.watermark.base import FusedTail
from repro.kernels.ops import DEFAULT_STREAMS
from repro.kernels.paged_attention import paged_attention_kernel
from repro.kernels.spec_verify import spec_verify_wm_kernel
from repro.kernels.wkv import wkv_step_kernel


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's compiles can be written to a persistent cache but
    # never read back without the chip: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _compile(fn, shardings, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=shardings)
            for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize("tail", [
    FusedTail("race", stat_dim=1),
    FusedTail("tournament", m=30, stat_dim=30),
    FusedTail("tournament", m=30, stat_dim=30, degenerate=True)],
    ids=["race", "tournament", "tournament-degenerate"])
def test_spec_verify_wm_compiles_at_yi_vocab(one_chip, tail):
    """The fused verify tail at B=8, K=4, V=64000 (yi-6b's vocabulary)."""
    B, K, V = 8, 4, 64000

    def fn(*a):
        return spec_verify_wm_kernel(*a, streams=DEFAULT_STREAMS, tail=tail)

    text = _compile(fn, one_chip,
                    ((B, K + 1, V), jnp.float32), ((B, K, V), jnp.float32),
                    ((B, K), jnp.int32), ((B, K), jnp.float32),
                    ((B,), jnp.uint32), ((B, K + 1), jnp.uint32),
                    ((B, K + 1), jnp.int32), ((B,), jnp.int32))
    assert "spec_verify_wm" in text


@pytest.mark.parametrize("B,Sq,H,Hkv,hd", [
    (8, 5, 32, 4, 128),       # yi-6b verify step (K=4)
    (1, 128, 32, 4, 128),     # yi-6b chunked prefill
    (8, 1, 8, 8, 128)],       # draft_for(yi-6b) decode
    ids=["yi-6b-verify", "yi-6b-prefill", "yi-6b-draft"])
def test_paged_attention_compiles_at_yi_widths(one_chip, B, Sq, H, Hkv, hd):
    """Paged decode attention over a bf16 (P, Hkv, page_size, hd) pool."""
    P, page_size, max_pages = 448, 16, 53
    text = _compile(paged_attention_kernel, one_chip,
                    ((B, Sq, H, hd), jnp.bfloat16),
                    ((P, Hkv, page_size, hd), jnp.bfloat16),
                    ((P, Hkv, page_size, hd), jnp.bfloat16),
                    ((B, max_pages), jnp.int32), ((B,), jnp.int32))
    assert "paged_attention" in text


def test_wkv_step_compiles_at_rwkv6_widths(one_chip):
    """The fused single-step WKV decode at rwkv6-3b widths (40 heads of
    64): the recurrent drafter's decode kernel."""
    B, H, hd = 8, 40, 64
    _compile(wkv_step_kernel, one_chip,
             ((B, H, hd), jnp.float32), ((B, H, hd), jnp.float32),
             ((B, H, hd), jnp.float32), ((B, H, hd), jnp.float32),
             ((H, hd), jnp.float32), ((B, H, hd, hd), jnp.float32))
