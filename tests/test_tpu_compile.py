"""Compile the Pallas kernels for a described TPU v5e chip, with no chip
attached, at the widths the federation and the models run them at.

Interpret mode (tests/test_kernels.py) checks what a kernel computes; it
cannot see what Mosaic refuses (block shapes off the (8, 128) tiling,
unsupported primitives or casts).  Each test here lowers one entry point
with ``interpret=False``, compiles it with the TPU compiler, and checks
that the compiled program holds the Mosaic kernel (``tpu_custom_call``).
One more compiles the MLP-784 fused-store window and checks what the TPU
compiler made of its store accesses, which no CPU program shows.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and several test workers import this
file.  Where it cannot be described the tests skip.
"""

import re

import jax
import jax.numpy as jnp
import pytest

from repro.core.approaches import DistGANConfig
from repro.core.engine import init_cohort_state, make_fused_store_engine
from repro.core.gan import MLPGanConfig, make_mlp_pair
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


def _computations(hlo: str) -> dict:
    """{name: [instruction lines]} of a compiled HLO module's text."""
    comps, cur = {}, None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) .*\{$", line)
        if head and not line.startswith(" "):
            cur = head.group(1)
            comps[cur] = []
        elif cur and line.startswith("  "):
            comps[cur].append(line)
    return comps


def _reachable(comps: dict, root: str) -> set:
    """Every computation ``root`` calls, loops over or fuses, and root."""
    seen, todo = set(), [root]
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        for line in comps[name]:
            for refs in re.findall(
                    r"(?:calls|body|condition|to_apply|branch_computations)"
                    r"=(\{[^}]*\}|%?[\w.\-]+)", line):
                todo += [r.strip().lstrip("%")
                         for r in refs.strip("{}").split(",")]
    return seen


def test_fused_store_window_touches_only_cohort_rows(one_chip, monkeypatch):
    """The MLP-784 pair's fused-store window (approach 1, top-k kernel,
    ``topk_int8`` with error feedback, K=16, C=8) compiled for v5e at
    U=64: inside the scan body the only ops that produce a (U, N) store
    buffer are the scatter's three row dynamic-update-slices and the
    loops and tuples that carry them — no select, copy or gather slice of
    the whole store — and the window needs fewer temporaries than the
    store holds."""
    import repro.kernels.ops as ops
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    U, C, K, B = 64, 8, 16, 64
    pair = make_mlp_pair(MLPGanConfig(data_dim=784, z_dim=64, g_hidden=256,
                                      d_hidden=256))
    fcfg = DistGANConfig(num_users=U, selection="topk", upload_frac=0.1,
                         use_topk_kernel=True, codec="topk_int8",
                         error_feedback=True)
    spec = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip)
    st = jax.eval_shape(lambda: init_cohort_state(
        pair, fcfg, jax.random.key(0), sync_ds=True))
    st = jax.tree.map(lambda a: spec(a.shape, a.dtype), st)
    compiled = make_fused_store_engine(pair, fcfg, "approach1").lower(
        st, spec((K, C, B, 784), jnp.float32), spec((K, C), jnp.int32),
        valid=spec((K,), jnp.bool_)).compile()

    hlo = compiled.as_text()
    comps = _computations(hlo)
    entry = re.search(r"^ENTRY %?([\w.\-]+)", hlo, re.M).group(1)
    widths = {MLP_784_D, 2 * MLP_784_D + 1}          # D/residual, Adam
    store = re.compile(r"f32\[%d,(%s)\]" % (U, "|".join(map(str, widths))))
    scan, = [l for l in comps[entry] if " while(" in l
             and store.search(l.split(" while(")[0])]
    body = re.search(r"body=%?([\w.\-]+)", scan).group(1)
    ops_seen = []
    for name in _reachable(comps, body):
        for line in comps[name]:
            inst = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = (.*?) ([\w\-]+)\(",
                            line)
            if inst is None:
                continue
            iname, shape, opcode = inst.groups()
            assert "mini-gather" not in iname, line
            if store.search(shape) and opcode not in ("parameter",
                                                      "get-tuple-element",
                                                      "tuple"):
                ops_seen.append(opcode)
    # one row loop per store buffer, each updating its row in place
    assert sorted(ops_seen) == ["dynamic-update-slice"] * 3 + ["while"] * 3
    store_bytes = sum(l.size * 4 for l in jax.tree.leaves(st.store))
    assert compiled.memory_analysis().temp_size_in_bytes < store_bytes
