"""MLP-784: the paper's MNIST pair (arXiv:1911.08128, Tables 1-2).

D: x -> Linear -> LeakyReLU(0.2) -> Linear -> LeakyReLU(0.2) -> Linear (logit)
G: z -> Linear -> ReLU -> Linear -> ReLU -> Linear -> tanh

The plain reference of both nets, the system's pair for them, and the
count of the operations one sample's forward pass needs.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def decls(cfg):
    """(G, D) declarations: (init, shape, std); std None = 1/sqrt(fan-in)."""
    def lin(i, o):
        return {"w": ("normal", (i, o), None), "b": ("zeros", (o,), None)}
    x, z, gh, dh = cfg["data_dim"], cfg["z_dim"], cfg["g_hidden"], \
        cfg["d_hidden"]
    g = {"l1": lin(z, gh), "l2": lin(gh, gh), "l3": lin(gh, x)}
    d = {"l1": lin(x, dh), "l2": lin(dh, dh), "l3": lin(dh, 1)}
    return g, d


def d_apply(cfg, p, x):
    h = jax.nn.leaky_relu(x @ p["l1"]["w"] + p["l1"]["b"], 0.2)
    h = jax.nn.leaky_relu(h @ p["l2"]["w"] + p["l2"]["b"], 0.2)
    return (h @ p["l3"]["w"] + p["l3"]["b"])[:, 0]


def g_apply(cfg, p, z):
    h = jax.nn.relu(z @ p["l1"]["w"] + p["l1"]["b"])
    h = jax.nn.relu(h @ p["l2"]["w"] + p["l2"]["b"])
    return jnp.tanh(h @ p["l3"]["w"] + p["l3"]["b"])


def sample_shape(cfg):
    return (cfg["data_dim"],)


def layer_flops(cfg):
    """Forward FLOPs per sample, layer by layer (2 per multiply-add)."""
    x, z, gh, dh = cfg["data_dim"], cfg["z_dim"], cfg["g_hidden"], \
        cfg["d_hidden"]
    return {"g": [2 * z * gh, 2 * gh * gh, 2 * gh * x],
            "d": [2 * x * dh, 2 * dh * dh, 2 * dh]}


def program_pair(cfg):
    from repro.core.gan import MLPGanConfig, make_mlp_pair
    return make_mlp_pair(MLPGanConfig(
        data_dim=cfg["data_dim"], z_dim=cfg["z_dim"],
        g_hidden=cfg["g_hidden"], d_hidden=cfg["d_hidden"]))
