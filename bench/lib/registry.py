"""Discovery by name: every cell, configuration, traffic mix, limit file and
per-layer metric is found from its name in ``BENCHMARK.json``, so a later
change adds one by adding files and entries, never by editing these.

* configuration ``<c>``: ``bench/configs/<c>.json`` (the sizes as run) and
  ``bench/configs/<c>.py`` (its plain reference and the system's pair);
* traffic ``<t>``: ``bench/traffic/<t>.json``, whose ``kind`` names the
  general runner ``bench/lib/<kind>.py``;
* cell ``<w>``: ``bench/cells/<w>.json``, the limits of its check;
* per-layer metric ``<m>``: ``bench/metrics/<m>.py``, whose ``read(ctx)``
  returns the number or None.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark(root: str = ROOT) -> dict:
    return _json(os.path.join(root, "BENCHMARK.json"))


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json (known: "
                   f"{[w['name'] for w in bench['workloads']]})")


def config(name: str, bench_dir: str = BENCH):
    """(sizes dict, reference module) of configuration ``name``."""
    base = os.path.join(bench_dir, "configs", name)
    return _json(base + ".json"), _module(base + ".py",
                                          f"bench_config_{name}")


def traffic(name: str, bench_dir: str = BENCH) -> dict:
    return _json(os.path.join(bench_dir, "traffic", name + ".json"))


def runner(kind: str):
    return importlib.import_module(f"bench.lib.{kind}")


def limits(workload: str, bench_dir: str = BENCH) -> dict:
    return _json(os.path.join(bench_dir, "cells", workload + ".json"))


def metric_reader(name: str, bench_dir: str = BENCH):
    return _module(os.path.join(bench_dir, "metrics", name + ".py"),
                   "bench_metric_" + name.replace(".", "_")).read


def cell_metrics(bench: dict, workload: str) -> tuple:
    """(end-to-end entries, per-layer entries) that ``workload`` reports."""
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or workload in m["workloads"]]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (workload in m["workloads"] if "workloads" in m
                 else m["moves"] in names)]
    return e2e, layer


def kernels(bench_dir: str = BENCH) -> dict:
    """Device-trace event names of each Pallas kernel family."""
    return _json(os.path.join(bench_dir, "kernels.json"))
