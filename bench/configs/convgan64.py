"""convgan64: the conv pair of arXiv:1911.08128 Tables 3-4 at 64x64, at one
channel and less its fourth strided block, which the system does not
build (see the config's ``reduced_why``).

D: Conv(s2) -> LReLU -> [Conv(s2) -> BN -> LReLU] x2 -> Conv(8x8 valid)
G: ConvT(8x8 valid) -> BN -> ReLU -> [ConvT(s2) -> BN -> ReLU] x2
   -> ConvT(s2) -> tanh

BatchNorm uses the batch's statistics (train mode).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_DN = ("NHWC", "HWIO", "NHWC")


def decls(cfg):
    f, c, z, s0 = cfg["base_filters"], cfg["channels"], cfg["z_dim"], \
        cfg["image_size"] // 8

    def conv(i, o, k=4):
        return {"w": ("normal", (k, k, i, o), 0.02)}

    def bn(n):
        return {"scale": ("ones", (n,), None), "bias": ("zeros", (n,), None)}
    g = {"c1": conv(z, 4 * f, k=s0), "bn1": bn(4 * f),
         "c2": conv(4 * f, 2 * f), "bn2": bn(2 * f),
         "c3": conv(2 * f, f), "bn3": bn(f), "c4": conv(f, c)}
    d = {"c1": conv(c, f), "c2": conv(f, 2 * f), "bn2": bn(2 * f),
         "c3": conv(2 * f, 4 * f), "bn3": bn(4 * f),
         "c4": conv(4 * f, 1, k=s0)}
    return g, d


def _bn(x, p):
    mu = jnp.mean(x, axis=(0, 1, 2), keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=(0, 1, 2), keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + 1e-5) * p["scale"] + p["bias"]


def _conv(x, w, s, pad="SAME"):
    return jax.lax.conv_general_dilated(x, w, (s, s), pad,
                                        dimension_numbers=_DN)


def _convt(x, w, s, pad="SAME"):
    return jax.lax.conv_transpose(x, w, (s, s), pad, dimension_numbers=_DN)


def d_apply(cfg, p, x):
    h = jax.nn.leaky_relu(_conv(x, p["c1"]["w"], 2), 0.2)
    h = jax.nn.leaky_relu(_bn(_conv(h, p["c2"]["w"], 2), p["bn2"]), 0.2)
    h = jax.nn.leaky_relu(_bn(_conv(h, p["c3"]["w"], 2), p["bn3"]), 0.2)
    return _conv(h, p["c4"]["w"], 1, "VALID")[:, 0, 0, 0]


def g_apply(cfg, p, z):
    h = _convt(z[:, None, None, :], p["c1"]["w"], 1, "VALID")
    h = jax.nn.relu(_bn(h, p["bn1"]))
    h = jax.nn.relu(_bn(_convt(h, p["c2"]["w"], 2), p["bn2"]))
    h = jax.nn.relu(_bn(_convt(h, p["c3"]["w"], 2), p["bn3"]))
    return jnp.tanh(_convt(h, p["c4"]["w"], 2))


def sample_shape(cfg):
    return (cfg["image_size"], cfg["image_size"], cfg["channels"])


def _taps(n_in: int, n_out: int, k: int, s: int, pad_lo: int,
          transpose: bool) -> int:
    """(output, tap) pairs along one axis that read a real input element
    (taps that land on padding or on the zeros of a dilated input are not
    work the layer needs)."""
    n = 0
    for o in range(n_out):
        for t in range(k):
            j = o * (1 if transpose else s) + t - pad_lo
            if transpose:
                n += j >= 0 and j % s == 0 and j // s < n_in
            else:
                n += 0 <= j < n_in
    return n


def _conv_flops(n_in, k, s, cin, cout, same: bool):
    n_out = -(-n_in // s) if same else n_in - k + 1
    pad = max((n_out - 1) * s + k - n_in, 0) if same else 0
    return 2 * _taps(n_in, n_out, k, s, pad // 2, False) ** 2 * cin * cout


def _convt_flops(n_in, k, s, cin, cout, same: bool):
    # jax.lax.conv_transpose: a stride-1 conv over the s-dilated input,
    # padded (pad_a, pad_b) as lax._conv_transpose_padding states
    if same:
        pad_len = k + s - 2
        pad_a = k - 1 if s > k - 1 else -(-pad_len // 2)
    else:
        pad_len = k + s - 2 + max(k - s, 0)
        pad_a = k - 1
    n_out = (n_in - 1) * s + 1 + pad_len - k + 1
    return 2 * _taps(n_in, n_out, k, s, pad_a, True) ** 2 * cin * cout


def layer_flops(cfg):
    """Forward FLOPs per sample, layer by layer (2 per multiply-add that
    reads a real input element)."""
    f, c, z, n = cfg["base_filters"], cfg["channels"], cfg["z_dim"], \
        cfg["image_size"]
    s0 = n // 8
    d = [_conv_flops(n, 4, 2, c, f, True),
         _conv_flops(n // 2, 4, 2, f, 2 * f, True),
         _conv_flops(n // 4, 4, 2, 2 * f, 4 * f, True),
         _conv_flops(s0, s0, 1, 4 * f, 1, False)]
    g = [_convt_flops(1, s0, 1, z, 4 * f, False),
         _convt_flops(s0, 4, 2, 4 * f, 2 * f, True),
         _convt_flops(2 * s0, 4, 2, 2 * f, f, True),
         _convt_flops(4 * s0, 4, 2, f, c, True)]
    return {"g": g, "d": d}


def program_pair(cfg):
    from repro.core.gan import ConvGanConfig, make_conv_pair
    return make_conv_pair(ConvGanConfig(
        image_size=cfg["image_size"], channels=cfg["channels"],
        z_dim=cfg["z_dim"], base_filters=cfg["base_filters"]))
