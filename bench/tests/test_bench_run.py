"""bench/run.py end to end on the CPU: its refusal without a TPU, and one
toy-size run of each kind of cell through the whole run path, with the
last line checked against the result schema."""

import json
import os
import subprocess
import sys

import pytest

import bench_toy

RUN = os.path.join(bench_toy.ROOT, "bench", "run.py")


def check_schema(res: dict, want_metrics: set, trace: bool) -> None:
    assert list(res)[-1] == "check"
    for k in ("correct", "attempted", "failed", "metrics", "device"):
        assert k in res
    assert isinstance(res["correct"], bool)
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == want_metrics
    for m in res["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] is not None
    dev = res["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    if trace:
        assert {"busy_s", "window_s"} <= set(dev)
        assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    for v in res["check"].values():
        assert set(v) == {"value", "limit"}


def test_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, RUN, "--workload", "mlp784.fused_store_u1024",
         "--seed", "0", "--seconds", "10", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300,
        cwd=bench_toy.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_refuses_in_a_directory_with_only_the_benchmark(tmp_path):
    import shutil
    shutil.copytree(os.path.join(bench_toy.ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".out", "__pycache__"))
    shutil.copy(os.path.join(bench_toy.ROOT, "BENCHMARK.json"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "convgan64.silo4",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300, cwd=tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_federation_cell_runs_at_toy_size(monkeypatch):
    bench_toy.toy(monkeypatch)
    res, err = bench_toy.run_cell("mlp784.fused_store_u1024")
    check_schema(res, {"setup_s", "rounds_per_s"}, trace=False)
    assert res["correct"], err
    assert set(res["check"]) == {"loss_gap", "grad_gap", "change_gap",
                                 "rows_off", "last_round_off"}
    assert res["check"]["rows_off"]["value"] == 0
    assert res["check"]["last_round_off"]["value"] == 0
    tail = err.strip().splitlines()[-3:]
    assert all(line.startswith("check ") and "limit=" in line
               for line in tail)


def test_conv_federation_cell_runs_traced_at_toy_size(monkeypatch):
    bench_toy.toy(monkeypatch)
    res, err = bench_toy.run_cell("convgan64.silo4", trace=1)
    # no accelerator plane on the CPU: the device metrics read nothing
    check_schema(res, set(), trace=True)
    assert res["correct"], err


def test_result_line_is_one_json_object(monkeypatch):
    bench_toy.toy(monkeypatch)
    res, _ = bench_toy.run_cell("convgan64.silo4", seconds=0.05)
    json.dumps(res)  # every value serializable, no NaN/inf
    assert "NaN" not in json.dumps(res) and "Infinity" not in json.dumps(res)
