"""BENCHMARK.json against the benchmark's contract, and discovery by name:
every entry resolves to its files, and a cell added as new files in a
fresh directory is found without an existing file being edited."""

import json
import math
import os
import re
import shutil

import pytest

import bench_toy  # noqa: F401  (puts the checkout on sys.path)
from bench.lib import registry

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = registry.benchmark()


def test_top_level_keys_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert BENCH["paths"] == ["bench"]
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51


def test_check_budget_fits_with_24_cells():
    s = BENCH["run_seconds"]
    assert (2 + 14 * 24) * (s + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_names_units_and_entry_keys():
    names = []
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and all(NAME.match(k)
                                             for k in c["reduced"])
        names.append(c["name"])
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        for k in ("name", "config", "traffic"):
            assert NAME.match(w[k]), w[k]
        names.append(w["name"])
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        names.append(m["name"])
    assert len(names) == len(set(names))
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25


def test_at_most_half_the_cells_take_four_chips():
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)


@pytest.mark.parametrize("w", [w["name"] for w in BENCH["workloads"]])
def test_each_cell_resolves_to_its_files(w):
    cell = registry.cell(BENCH, w)
    cfg, mod = registry.config(cell["config"])
    assert cfg["name"] == cell["config"]
    for fn in ("decls", "g_apply", "d_apply", "sample_shape", "layer_flops",
               "program_pair"):
        assert callable(getattr(mod, fn))
    tr = registry.traffic(cell["traffic"])
    registry.runner(tr["kind"])
    lim = registry.limits(w)["limits"]
    assert lim and all(isinstance(v, (int, float)) for v in lim.values())
    e2e, layer = registry.cell_metrics(BENCH, w)
    names = {m["name"] for m in e2e}
    assert "setup_s" in names and len(names) >= 2
    assert layer
    for m in layer:
        assert m["moves"] in names
        assert callable(registry.metric_reader(m["name"]))


def test_every_config_is_used_and_its_file_is_under_paths():
    used = {w["config"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert c["name"] in used
        assert c["file"] == f"bench/configs/{c['name']}.json"
        assert os.path.exists(os.path.join(registry.ROOT, c["file"]))


def test_metric_workloads_name_existing_cells():
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells


def test_a_cell_added_as_new_files_is_found(tmp_path):
    bench_dir = tmp_path / "bench"
    for sub in ("configs", "traffic", "cells", "metrics"):
        (bench_dir / sub).mkdir(parents=True)
    shutil.copy(os.path.join(registry.BENCH, "configs", "mlp784.py"),
                bench_dir / "configs" / "toy.py")
    (bench_dir / "configs" / "toy.json").write_text(json.dumps(
        {"name": "toy", "data_dim": 4, "z_dim": 2, "g_hidden": 3,
         "d_hidden": 3}))
    (bench_dir / "traffic" / "burst.json").write_text(json.dumps(
        {"kind": "federation", "rate_rps": 5}))
    (bench_dir / "cells" / "toy.burst.json").write_text(json.dumps(
        {"limits": {"loss_gap": 0.5}}))
    (bench_dir / "metrics" / "toy_fill.py").write_text(
        "def read(r):\n    return 42.0\n")
    cfg, mod = registry.config("toy", str(bench_dir))
    assert mod.layer_flops(cfg)["d"] == [2 * 4 * 3, 2 * 3 * 3, 2 * 3]
    assert registry.traffic("burst", str(bench_dir))["rate_rps"] == 5
    assert registry.limits("toy.burst", str(bench_dir)) == {
        "limits": {"loss_gap": 0.5}}
    assert registry.metric_reader("toy_fill", str(bench_dir))(None) == 42.0
    bench = {"end_to_end": [{"name": "setup_s"}, {"name": "x",
                                                  "workloads": ["toy.burst"]}],
             "per_layer": [{"name": "toy_fill", "moves": "x",
                            "workloads": ["toy.burst"]},
                           {"name": "all", "moves": "setup_s"}]}
    e2e, layer = registry.cell_metrics(bench, "toy.burst")
    assert [m["name"] for m in e2e] == ["setup_s", "x"]
    assert [m["name"] for m in layer] == ["toy_fill", "all"]


def test_peaks_table_is_keyed_by_device_kind():
    from bench.lib import device
    p = device.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert math.isclose(p["int8_ops"], 393e12) and p["hbm_bytes"] == 16e9
    with pytest.raises(KeyError, match="no peaks"):
        device.peaks("TPU v9 imaginary")
