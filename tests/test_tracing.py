"""The program's own trace points: the ``fed.*`` named scopes in the round
engine, which reach every compiled op's ``op_name`` metadata, and the
``fed.*`` host spans of the session driver, which land in the profiler's
host plane on the device trace's clock.  Also the session's compile clock,
``RunResult.extra["compile_s"]``, which counts only what JAX compiled in
the window."""

import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core.approaches import DistGANConfig, init_state
from repro.core.engine import (init_cohort_state, make_engine,
                               make_fused_store_engine)
from repro.core.gan import MLPGanConfig, make_mlp_pair
from repro.core.session import FederationSession
from repro.core.spec import (BackendSpec, CombineSpec, CompressionSpec,
                             EngineSpec, FederationSpec, ParticipationSpec)
from repro.data.federated import FederatedDataset
from repro.data.mixtures import make_user_domains

PAIR = make_mlp_pair(MLPGanConfig(data_dim=2, z_dim=8, g_hidden=32,
                                  d_hidden=32))
U, C, K, B = 6, 2, 4, 16
ROUND = {"fed.fakes", "fed.d_update", "fed.g_update", "fed.select",
         "fed.fold", "fed.window_mask"}
STORE = {"fed.codec", "fed.store_gather", "fed.store_scatter"}


def _fcfg(codec="none"):
    return DistGANConfig(num_users=U, codec=codec,
                         error_feedback=codec != "none")


def _scopes(compiled_text: str) -> set:
    names = re.findall(r'op_name="([^"]*)"', compiled_text)
    return {s for n in names for s in re.findall(r"fed\.[a-z_]+", n)}


def _args(*shapes):
    return [jax.ShapeDtypeStruct(s, d) for s, d in shapes]


@pytest.mark.parametrize("engine", ["fused_store", "fused"])
def test_every_scope_reaches_the_compiled_module(engine):
    key = jax.random.key(0)
    if engine == "fused_store":
        # the fused-store cell's shape: topk_int8 with error feedback
        fcfg = _fcfg("topk_int8")
        st = jax.eval_shape(lambda: init_cohort_state(PAIR, fcfg, key,
                                                      sync_ds=True))
        eng = make_fused_store_engine(PAIR, fcfg, "approach1")
        lowered = eng.lower(st, *_args(((K, C, B, 2), jnp.float32),
                                       ((K, C), jnp.int32)),
                            valid=jax.ShapeDtypeStruct((K,), jnp.bool_))
        want = ROUND | STORE
    else:
        fcfg = _fcfg()
        st = jax.eval_shape(lambda: init_state(PAIR, fcfg, key,
                                               sync_ds=True))
        eng = make_engine(PAIR, fcfg, "approach1")
        lowered = eng.lower(st, *_args(((K, U, B, 2), jnp.float32),
                                       ((K,), jnp.bool_)))
        want = ROUND
    got = _scopes(lowered.compile().as_text())
    assert want <= got, f"missing scopes: {sorted(want - got)}"
    assert got <= ROUND | STORE, f"unknown scopes: {sorted(got - want)}"


def _session(rpj=K):
    users, union = make_user_domains(U, 2, 1.0)
    ds = FederatedDataset([u.sample for u in users], union.sample,
                          {"shard_sizes": [100] * U})
    spec = FederationSpec(
        approach="approach1", batch_size=B, seed=0, eval_samples=0,
        engine=EngineSpec("fused", rounds_per_jit=rpj,
                          fuse_store_rounds=True),
        participation=ParticipationSpec("round_robin", cohort_size=C),
        backend=BackendSpec("device"),
        combine=CombineSpec(compression=CompressionSpec(
            codec="topk_int8", error_feedback=True)))
    return FederationSession(PAIR, _fcfg(), ds, spec)


def _spans(log_dir) -> list:
    """[(name, start_ns, end_ns, args)] of the ``fed.*`` host spans."""
    path, = glob.glob(f"{log_dir}/plugins/profile/*/*.xplane.pb")
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend((e.name, e.start_ns, e.end_ns, dict(e.stats))
                           for e in line.events
                           if e.name.startswith("fed."))
    return sorted(out, key=lambda s: s[1])


def test_session_spans_nest_inside_each_window(tmp_path):
    sess = _session()
    sess.run(K)                                  # compiles outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        sess.run(K)
        sess.run(2 * K)                          # two chunks in one window
    finally:
        jax.profiler.stop_trace()
    spans = _spans(tmp_path)
    runs = [s for s in spans if s[0] == "fed.run"]
    # consecutive windows, each named by the session round at its start
    assert [(r[3]["window"], r[3]["rounds"]) for r in runs] == [
        (K, K), (2 * K, 2 * K)]
    for run in runs:
        inner = [s for s in spans if s[0] != "fed.run"
                 and run[1] <= s[1] and s[2] <= run[2]]
        assert {s[3]["window"] for s in inner} == {run[3]["window"]}
        names = [s[0] for s in inner]
        # one prestaged window of batches, then a dispatch and a sync a
        # chunk; the store is unpacked only when RunResult.state is read
        chunks = run[3]["rounds"] // K
        assert names == (["fed.sample", "fed.h2d"]
                         + ["fed.dispatch", "fed.sync"] * chunks)
        sample, h2d = inner[0], inner[1]
        assert sample[3]["bytes"] == h2d[3]["bytes"] > 0
        assert sample[2] <= h2d[1]


def test_unpack_span_opens_once_for_each_part_read(tmp_path):
    """The store is unpacked only for the parts of RunResult.state that
    are read, each once, tagged with the window of the run that made the
    state."""
    sess = _session()
    sess.run(K)                                  # compiles outside the trace
    jax.profiler.start_trace(str(tmp_path / "window"))
    try:
        res = sess.run(K)
        jax.block_until_ready(res.state)
        res.state.g
    finally:
        jax.profiler.stop_trace()
    names = [s[0] for s in _spans(tmp_path / "window")]
    assert "fed.run" in names and "fed.unpack" not in names
    jax.profiler.start_trace(str(tmp_path / "reads"))
    try:
        res.state.ds
        res.state.ds                             # cached: no second unpack
        res.state.d_opts
        res.state.to_full()
    finally:
        jax.profiler.stop_trace()
    assert [(s[0], s[3]["window"], s[3]["part"])
            for s in _spans(tmp_path / "reads")] == [
        ("fed.unpack", K, "ds"), ("fed.unpack", K, "d_opts")]


def test_dispatch_span_counts_the_masked_rounds(tmp_path):
    sess = _session()
    sess.run(1)                                  # compiles outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        sess.run(K)
        sess.run(1)                              # K - 1 padded rounds
    finally:
        jax.profiler.stop_trace()
    assert [s[3]["masked"] for s in _spans(tmp_path)
            if s[0] == "fed.dispatch"] == [0, K - 1]


def test_compile_s_counts_only_the_window_s_compiles():
    sess = _session()
    first = sess.run(K)
    assert first.extra["compile_s"] > 0
    warm = sess.run(K)
    assert warm.extra["compile_s"] == 0.0
    # a short window's first run compiles its eager padding ops once
    sess.run(1)
    assert sess.run(1).extra["compile_s"] == 0.0
    assert np.all(np.isfinite(warm.g_losses))
