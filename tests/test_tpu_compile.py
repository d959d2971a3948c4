"""Compile the Pallas kernels for a described TPU v5e chip, with no chip
attached, at the widths the federation and the models run them at.

Interpret mode (tests/test_kernels.py) checks what a kernel computes; it
cannot see what Mosaic refuses (block shapes off the (8, 128) tiling,
unsupported primitives or casts).  Each test here lowers one entry point
with ``interpret=False``, compiles it with the TPU compiler, and checks
that the compiled program holds the Mosaic kernel (``tpu_custom_call``).

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and several test workers import this
file.  Where it cannot be described the tests skip.
"""

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.quantize import (dequantize_rows_pallas,
                                    quantize_rows_pallas)
from repro.kernels.ssd_scan import ssd_scan_pallas
from repro.kernels.topk_select import (topk_mask_pallas,
                                       topk_mask_pallas_global)

# flat discriminator sizes: the paper's MLP-784 pair and its 64x64 DCGAN
MLP_784_D = 267_009
DCGAN_64_D = 673_536


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no libtpu, or it is held elsewhere
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip cannot be read back from the
        # persistent cache without that chip: keep it out of the cache
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield jax.sharding.SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo, "no Mosaic kernel in the compiled HLO"


@pytest.mark.parametrize("n", [MLP_784_D, DCGAN_64_D])
@pytest.mark.parametrize("mode", ["global", "block"])
def test_topk_mask_compiles_for_v5e(one_chip, mode, n):
    fn = topk_mask_pallas_global if mode == "global" else topk_mask_pallas
    _compile(lambda x: fn(x, 0.1, interpret=False), one_chip,
             ((n,), jnp.float32))


@pytest.mark.parametrize("stochastic", [False, True])
def test_quantize_rows_compiles_for_v5e(one_chip, stochastic):
    if stochastic:
        _compile(lambda x, s: quantize_rows_pallas(
            x, stochastic=True, seed=s, interpret=False), one_chip,
            ((8, MLP_784_D), jnp.float32), ((), jnp.int32))
    else:
        _compile(lambda x: quantize_rows_pallas(x, interpret=False),
                 one_chip, ((8, MLP_784_D), jnp.float32))


def test_dequantize_rows_compiles_for_v5e(one_chip):
    _compile(lambda q, s: dequantize_rows_pallas(q, s, interpret=False),
             one_chip, ((8, MLP_784_D), jnp.int8), ((8,), jnp.float32))


def test_flash_attention_compiles_for_v5e(one_chip):
    shape = ((1, 2048, 32, 64), jnp.bfloat16)
    _compile(lambda q, k, v: flash_attention_pallas(q, k, v,
                                                    interpret=False),
             one_chip, shape, shape, shape)


def test_ssd_scan_compiles_for_v5e(one_chip):
    B, S, H, P, G, N = 1, 1024, 8, 64, 1, 128
    _compile(lambda x, dt, a, b, c: ssd_scan_pallas(x, dt, a, b, c,
                                                    chunk=256,
                                                    interpret=False),
             one_chip, ((B, S, H, P), jnp.float32), ((B, S, H), jnp.float32),
             ((H,), jnp.float32), ((B, S, G, N), jnp.float32),
             ((B, S, G, N), jnp.float32))
