"""Property tests (hypothesis) for the paper's selective-sharing mechanism
and server combination rules."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
st = pytest.importorskip("hypothesis.strategies")
from hypothesis import given, settings
from hypothesis.extra.numpy import arrays

from repro.core.federated import (COMBINERS, combine_max_abs, combine_mean,
                                  combine_masked_mean, select_delta,
                                  threshold_mask, topk_mask, upload_bytes)

hypothesis.settings.register_profile(
    "ci", deadline=None, max_examples=25,
    suppress_health_check=[hypothesis.HealthCheck.too_slow])
hypothesis.settings.load_profile("ci")

# keep away from denormals: XLA flushes them to zero (FTZ), numpy doesn't,
# and the combiner semantics tests compare "!= 0" across the two
floats = st.floats(-10, 10, allow_nan=False, width=32).filter(
    lambda x: x == 0.0 or abs(x) > 1e-20)


@given(arrays(np.float32, st.integers(8, 200), elements=floats),
       st.floats(0.05, 0.95))
def test_topk_mask_keeps_at_least_k_and_all_larger(x, frac):
    x = jnp.asarray(x)
    m = np.asarray(topk_mask(x, frac))
    k = max(int(x.shape[0] * frac), 1)
    assert m.sum() >= k                       # ties can exceed k
    mags = np.abs(np.asarray(x))
    if m.sum() < len(x):
        assert mags[m].min() >= mags[~m].max()  # kept dominate dropped


@given(arrays(np.float32, st.integers(4, 100), elements=floats),
       st.floats(0.0, 5.0))
def test_threshold_mask_semantics(x, tau):
    m = np.asarray(threshold_mask(jnp.asarray(x), tau))
    np.testing.assert_array_equal(m, np.abs(x) > tau)


@given(arrays(np.float32, st.tuples(st.integers(2, 5), st.integers(3, 40)),
              elements=floats))
def test_combine_max_abs_picks_argmax_magnitude(d):
    out = np.asarray(combine_max_abs(jnp.asarray(d)))
    idx = np.argmax(np.abs(d), axis=0)
    want = d[idx, np.arange(d.shape[1])]
    np.testing.assert_allclose(out, want)


@given(arrays(np.float32, st.tuples(st.integers(2, 4), st.integers(3, 30)),
              elements=floats))
def test_combine_masked_mean_ignores_zeros(d):
    # zero out user 0 entirely: masked mean must equal mean over users 1..U
    d[0] = 0.0
    out = np.asarray(combine_masked_mean(jnp.asarray(d)))
    nz = d[1:]
    cnt = np.maximum((nz != 0).sum(axis=0), 1)
    np.testing.assert_allclose(out, nz.sum(axis=0) / cnt, rtol=1e-5,
                               atol=1e-6)


def test_select_delta_tree_roundtrip():
    tree = {"a": jnp.arange(10, dtype=jnp.float32) - 5,
            "b": {"c": jnp.ones((4, 4)) * 0.01}}
    masked, kept = select_delta(tree, "topk", frac=0.25)
    flat_in = np.concatenate([np.ravel(l) for l in jax.tree.leaves(tree)])
    flat_out = np.concatenate([np.ravel(l) for l in jax.tree.leaves(masked)])
    # masked tree only zeroes entries, never changes surviving values
    surviving = flat_out != 0
    np.testing.assert_allclose(flat_out[surviving], flat_in[surviving])
    assert 0 < float(kept) <= 1.0


def test_select_none_is_identity():
    tree = {"a": jnp.arange(5, dtype=jnp.float32)}
    out, kept = select_delta(tree, "none")
    np.testing.assert_array_equal(np.asarray(out["a"]), np.asarray(tree["a"]))
    assert float(kept) == 1.0


def test_random_mask_needs_key():
    tree = {"a": jnp.arange(100, dtype=jnp.float32)}
    out, kept = select_delta(tree, "random", frac=0.3, key=jax.random.key(0))
    assert 0.05 < float(kept) < 0.7


@given(st.floats(0.01, 1.0))
def test_upload_bytes_scales_with_frac(frac):
    tree = {"a": jnp.zeros((1000,)), "b": jnp.zeros((24, 24))}
    dense = upload_bytes(tree, "none", frac)
    sparse = upload_bytes(tree, "topk", frac)
    n = 1000 + 24 * 24
    assert dense == 4 * n
    assert sparse == int(n * frac) * 8


def test_spmd_combine_matches_host_combine():
    """SPMD pmax/psum fold == stacked-host fold, via shard_map on 1 device
    replicated... exercised with 4 logical users on the host simulation."""
    import subprocess, sys, textwrap
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as PS
        from repro.core.federated import combine_max_abs, combine_max_abs_spmd
        from repro.launch.mesh import make_users_mesh
        mesh = make_users_mesh(4)
        d = jax.random.normal(jax.random.key(0), (4, 37))
        def body(x):
            return combine_max_abs_spmd({"w": x[0]}, "users")["w"]
        out = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=PS("users"),
                                    out_specs=PS(), check_vma=False))(d)
        want = combine_max_abs({"w": d})["w"]
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=1e-6)
        print("OK")
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=_env())
    assert "OK" in r.stdout, r.stdout + r.stderr


def _env():
    import os
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    return env
