"""Toy sizes for the harness's CPU tests, steered from the test side: the
platform gate is pointed at the CPU and the registry hands out shrunken
copies of each cell's configuration and traffic.  Nothing here is an
option of the benchmark."""

from __future__ import annotations

import contextlib
import io
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench.lib import device, registry  # noqa: E402

TOY_CONFIG = {
    "mlp784": {"data_dim": 64, "z_dim": 16, "g_hidden": 32, "d_hidden": 32,
               "d_params": 3137},
    "convgan64": {"image_size": 16, "z_dim": 8, "base_filters": 4},
}
TOY_TRAFFIC = {
    "fused_store_u1024": {
        "users": 16, "cohort": 4, "rounds_per_jit": 2, "batch": 8,
        "data": {"image_size": 8, "per_class": 8, "partition": "dirichlet",
                 "alpha": 1.0}},
    "silo4": {"rounds_per_jit": 2, "batch": 8,
              "data": {"image_size": 16, "per_class": 4,
                       "partition": "class_split"}},
}
TOY_PEAKS = {"bf16_flops": 1e12, "int8_ops": 2e12,
             "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e9}


def toy(monkeypatch) -> None:
    """Point the harness at the CPU and at toy sizes."""
    real_config, real_traffic = registry.config, registry.traffic

    def config(name, *a):
        cfg, mod = real_config(name, *a)
        return dict(cfg, **TOY_CONFIG[name]), mod

    def traffic(name, *a):
        return dict(real_traffic(name, *a), **TOY_TRAFFIC[name])

    # run.py sets this for its own process; restore it after the test
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       os.path.join(ROOT, ".jax_cache"))
    monkeypatch.setattr(device, "PLATFORM", "cpu")
    monkeypatch.setattr(device, "peaks", lambda kind: TOY_PEAKS)
    monkeypatch.setattr(registry, "config", config)
    monkeypatch.setattr(registry, "traffic", traffic)


def run_cell(workload: str, *, seed: int = 2 ** 31 + 11,
             seconds: float = 0.2, trace: int = 0) -> tuple:
    """``bench/run.py``'s main in this process; returns (result line as a
    dict, standard error)."""
    from bench import run as bench_run
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = bench_run.main(["--workload", workload, "--seed", str(seed),
                             "--seconds", str(seconds), "--trace",
                             str(trace)])
    assert rc == 0, err.getvalue()
    return json.loads(out.getvalue().strip().splitlines()[-1]), err.getvalue()
