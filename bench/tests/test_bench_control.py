"""The check fails what it must: at toy size on the CPU, each fault a cell
can have is planted under the timed path and a whole run (the platform
gate steered to the CPU) comes out ``correct: false``; and the control
(the reference in bfloat16, put in the program's place) reads above the
cell's limits."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

import bench_toy
from bench.lib import checks, data, federation, reference, registry

TRAIN = ["mlp784.fused_store_u1024", "convgan64.silo4"]


def plant(monkeypatch, wrap):
    """Wrap approach 1's round body in every engine the session builds."""
    import repro.core.engine as engine
    real = engine.resolve_approach

    def resolve(name):
        appr = real(name)
        make = appr.body_factory
        return dataclasses.replace(
            appr, body_factory=lambda pair, fcfg: wrap(make(pair, fcfg)))
    monkeypatch.setattr(engine, "resolve_approach", resolve)


def state_unchanged(body):
    def broken(state, real, *rest):
        out = body(state, real, *rest)
        return (state,) + tuple(out[1:])
    return broken


def half_batch(body):
    def broken(state, real, *rest):
        return body(state, real[:, :real.shape[1] // 2], *rest)
    return broken


@pytest.mark.parametrize("workload", TRAIN)
@pytest.mark.parametrize("fault", [state_unchanged, half_batch],
                         ids=["state_unchanged", "half_batch"])
def test_a_planted_training_fault_is_not_correct(monkeypatch, workload,
                                                 fault):
    bench_toy.toy(monkeypatch)
    plant(monkeypatch, fault)
    res, err = bench_toy.run_cell(workload, seconds=0.05)
    assert res["correct"] is False, err
    over = [k for k, v in res["check"].items() if v["value"] > v["limit"]]
    assert over, res["check"]


def scatter_next_cohort_too(scatter):
    """Also write the cohort's rows over the next cohort's users."""
    def broken(store, idx, *a, **k):
        out = scatter(store, idx, *a, **k)
        return scatter(out, (idx + idx.shape[0]) % store.d_flat.shape[0],
                       *a, **k)
    return broken


def test_a_planted_store_fault_is_not_correct(monkeypatch):
    import repro.core.engine as engine
    bench_toy.toy(monkeypatch)
    monkeypatch.setattr(engine, "cohort_scatter",
                        scatter_next_cohort_too(engine.cohort_scatter))
    res, err = bench_toy.run_cell("mlp784.fused_store_u1024", seconds=0.05)
    assert res["correct"] is False, err
    assert {"rows_off", "last_round_off"} <= {
        k for k, v in res["check"].items() if v["value"] > v["limit"]}


@pytest.mark.parametrize("workload", TRAIN)
def test_the_bf16_control_reads_over_the_limits(monkeypatch, workload):
    bench_toy.toy(monkeypatch)
    cell = registry.cell(registry.benchmark(), workload)
    cfg, mod = registry.config(cell["config"])
    fed = registry.traffic(cell["traffic"])
    shards = data.make_shards(fed["data"], mod.sample_shape(cfg),
                              fed["users"], 5)
    ref = federation.run_reference(mod, cfg, fed, 5, shards)
    ctl = federation.run_reference(mod, cfg, fed, 5, shards,
                                   dtype=jnp.bfloat16)
    numbers = checks.train_numbers(ctl, ref)
    # the control has no store of its own: its numbers are the gaps
    limits = {k: v for k, v in registry.limits(workload)["limits"].items()
              if k in numbers}
    ok, shown = checks.judge(numbers, limits)
    assert not ok, shown
    assert set(limits) == {"loss_gap", "grad_gap", "change_gap"}
    assert set(checks.train_numbers(ref, ref).values()) == {0.0}


def test_quiet_leaves_are_left_out_of_the_change():
    # a leaf whose reference gradient is ~0 (under 1e-3 of the median)
    loud = {"a": np.ones(4), "b": np.ones(4), "q": np.full(4, 1e-9)}
    assert checks.loud([loud]) == [True, True, False]
    ref = {"a": np.ones(4), "b": np.ones(4), "q": np.full(4, 1e-7)}
    prog = dict(ref, q=np.full(4, 3e-7))        # round-off moves it
    assert checks.leaf_gap(prog, ref, checks.loud([loud])) == 0.0
    assert checks.leaf_gap(prog, ref) > 0.0


def test_schedules_of_the_reference():
    assert reference.schedule("round_robin", 10, 4, 3).tolist() == [
        [0, 1, 2, 3], [4, 5, 6, 7], [8, 9, 0, 1]]
    assert reference.schedule("full", 3, None, 2).tolist() == [
        [0, 1, 2], [0, 1, 2]]


def test_replayed_batches_are_the_ones_the_dataset_hands_out():
    shards = data.make_shards({"image_size": 8, "per_class": 6,
                               "partition": "dirichlet", "alpha": 1.0},
                              (64,), 5, seed=3)
    ds = data.dataset(shards)
    sched = reference.schedule("round_robin", 5, 2, 3)
    rng = np.random.default_rng(11)
    fed = np.stack([np.stack([ds.user_batch(int(u), rng, 4) for u in row])
                    for row in sched])
    np.testing.assert_array_equal(
        fed, data.replay_batches(shards, 11, sched, 4))
