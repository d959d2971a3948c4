"""The benchmark's operation and byte counts against hand counts, and the
conv tap count against an independent count made by convolving ones."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_toy  # noqa: F401  (puts the checkout on sys.path)
from bench.lib import registry
from bench.lib.federation import round_flops


def test_mlp784_layer_flops_by_hand():
    cfg, mod = registry.config("mlp784")
    f = mod.layer_flops(cfg)
    assert f["g"] == [2 * 64 * 256, 2 * 256 * 256, 2 * 256 * 784]
    assert f["d"] == [2 * 784 * 256, 2 * 256 * 256, 2 * 256]
    # forward 532,992 (D) and 565,248 (G) FLOPs a sample
    assert sum(f["d"]) == 532_992 and sum(f["g"]) == 565_248


def test_mlp784_round_flops_by_hand():
    cfg, mod = registry.config("mlp784")
    fg, fd, g1, d1 = 565_248, 532_992, 2 * 64 * 256, 2 * 784 * 256
    fakes = 64 * fg
    d_updates = 8 * 128 * (3 * fd - d1)
    g_update = 64 * (fg + fd + fd + 2 * fg - g1)
    assert round_flops(mod.layer_flops(cfg), 64, 8) == \
        fakes + d_updates + g_update == 1_437_138_944


def test_convgan64_layer_flops_by_hand():
    cfg, mod = registry.config("convgan64")
    f = mod.layer_flops(cfg)
    # D, stride-2 SAME 4x4: real-input taps a side 126 (64->32), 62, 30;
    # the final 8x8 VALID conv reads every tap
    assert f["d"] == [2 * 126 ** 2 * 1 * 64, 2 * 62 ** 2 * 64 * 128,
                      2 * 30 ** 2 * 128 * 256, 2 * 64 * 256]
    # G: the 8x8 VALID transpose reads one tap an output; the stride-2
    # transposes read 30, 62 and 126 a side
    assert f["g"] == [2 * 64 * 100 * 256, 2 * 30 ** 2 * 256 * 128,
                      2 * 62 ** 2 * 128 * 64, 2 * 126 ** 2 * 64 * 1]
    assert sum(f["d"]) == 124_027_392 and sum(f["g"]) == 127_271_424


def _ones_taps(fn, x_shape, w_shape):
    """Real (input, tap) products of a conv, counted by convolving ones."""
    x = jnp.ones(x_shape, jnp.float32)
    w = jnp.ones(w_shape, jnp.float32)
    with jax.default_matmul_precision("highest"):
        return float(jnp.sum(fn(x, w)))


@pytest.mark.parametrize("n_in,k,s,cin,cout,same", [
    (64, 4, 2, 1, 3, True), (16, 4, 2, 2, 3, True), (8, 8, 1, 3, 1, False)])
def test_conv_taps_match_a_convolution_of_ones(n_in, k, s, cin, cout, same):
    _, mod = registry.config("convgan64")
    got = mod._conv_flops(n_in, k, s, cin, cout, same)
    ones = _ones_taps(lambda x, w: mod._conv(x, w, s, "SAME" if same
                                             else "VALID"),
                      (1, n_in, n_in, cin), (k, k, cin, cout))
    assert got == 2 * ones


@pytest.mark.parametrize("n_in,k,s,cin,cout,same", [
    (8, 4, 2, 3, 2, True), (32, 4, 2, 2, 1, True), (1, 8, 1, 4, 3, False)])
def test_convt_taps_match_a_convolution_of_ones(n_in, k, s, cin, cout, same):
    _, mod = registry.config("convgan64")
    got = mod._convt_flops(n_in, k, s, cin, cout, same)
    ones = _ones_taps(lambda x, w: mod._convt(x, w, s, "SAME" if same
                                              else "VALID"),
                      (1, n_in, n_in, cin), (k, k, cin, cout))
    assert got == 2 * ones


@pytest.mark.parametrize("name", ["mlp784", "convgan64"])
def test_parameter_counts_match_the_config_files(name):
    from bench.lib import reference
    cfg, mod = registry.config(name)
    g, d = jax.eval_shape(lambda k: reference.init_pair(mod, cfg, k),
                          jax.random.key(0))
    count = lambda t: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(t))
    assert count(d) == cfg["d_params"] and count(g) == cfg["g_params"]


def test_least_bytes_of_the_kernel_rooflines():
    import importlib.util
    import os

    def reader(name):
        path = os.path.join(registry.BENCH, "metrics", name + ".py")
        spec = importlib.util.spec_from_file_location("m_" + name, path)
        m = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(m)
        return m.read

    from bench.lib.trace import Trace

    class R:
        # 1 s of one top-k kernel and 1 s of one int8 kernel per window
        trace = Trace({"/device:TPU:0": [
            ("%topk_mask.1 = s32[264,1024] custom-call []", 0.0, 1e9),
            ("%quantize_rows.12 = f32[264] custom-call []", 1e9, 2e9)]},
            [], (0.0, 3e9))
        facts = {"rounds": 10, "cohort": 8, "d_params": 267_009}
        peaks = {"hbm_bytes_per_s": 819e9}
        kernels = registry.kernels()
        chips = 1

    n = 267_009
    assert reader("topk_roofline")(R) == pytest.approx(
        100 * 8 * (4 * n + n / 8) * 10 / 819e9)
    assert reader("int8_roofline")(R) == pytest.approx(
        100 * 8 * (8 * n + 4) * 10 / 819e9)
