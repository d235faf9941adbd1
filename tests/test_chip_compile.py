"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Interpret mode (every other kernel test) runs the kernel body as XLA ops
on the CPU and accepts block shapes the chip's compiler refuses. Here
each kernel is compiled for one chip of a DESCRIBED ``v5e:2x2`` topology
(the TPU compiler is installed; no chip is attached) and the compiled
HLO must hold the Mosaic ``tpu_custom_call``:

  * the paged-attention decode kernel at Minitron-4B's heads (Hq 24,
    Hkv 8) and at Qwen3-14B's per-device share under tp=4 (Hq 10,
    Hkv 2), head dim 128, bf16, 256 mixed-step rows, 16-token pages, a
    64-page table;
  * flash attention at S = T = 2048 with GQA 24/8.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and under several test
workers only the one running this file does. The persistent compile
cache is off around these tests: a compile for a described chip is
written to it but cannot be read back without one.
"""
import os
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention
from repro.kernels.paged_attention import paged_attention


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("hq,hkv", [(24, 8), (10, 2)])
def test_paged_attention_compiles_for_v5e(one_chip, hq, hkv):
    rows, page_size, width, d, n_pages = 256, 16, 64, 128, 1025
    pool = ((n_pages, hkv, page_size, d), jnp.bfloat16)
    text = _compiled_text(
        partial(paged_attention, interpret=False), one_chip,
        ((rows, 1, hq, d), jnp.bfloat16), pool, pool,
        ((rows, width), jnp.int32), ((rows,), jnp.int32))
    assert "tpu_custom_call" in text


def test_flash_attention_compiles_for_v5e(one_chip):
    s, hq, hkv, d = 2048, 24, 8, 128
    text = _compiled_text(
        partial(flash_attention, causal=True, interpret=False), one_chip,
        ((1, s, hq, d), jnp.bfloat16), ((1, s, hkv, d), jnp.bfloat16),
        ((1, s, hkv, d), jnp.bfloat16))
    assert "tpu_custom_call" in text
