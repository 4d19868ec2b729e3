"""A scratch cell and a scratch per-layer metric are added and removed by
files alone: no file of the benchmark is edited."""

import json
import os
import shutil

import pytest

from benchlib import discover


@pytest.fixture()
def checkout(tmp_path):
    """A copy of BENCHMARK.json and the benchmark's data and readers."""
    root = tmp_path / "checkout"
    home = root / "benchmark"
    for d in ("configs", "traffic", "cells", "metrics", "reference"):
        shutil.copytree(os.path.join(discover.HERE, d), home / d)
    shutil.copy(os.path.join(discover.ROOT, "BENCHMARK.json"), root)
    return root


def add_entries(root, cells=(), metrics=()):
    path = root / "BENCHMARK.json"
    spec = json.loads(path.read_text())
    spec["workloads"] += list(cells)
    spec["per_layer"] += list(metrics)
    path.write_text(json.dumps(spec))


def test_scratch_cell_and_metric_by_files_alone(checkout):
    home = checkout / "benchmark"
    (home / "traffic" / "scratch_b4.json").write_text(json.dumps(
        {"train": {"compressor": "oktopk", "num_buckets": 4},
         "algo": {"warmup_steps": 3, "threshold_method": "sort"}}))
    (home / "cells" / "scratch_cell.json").write_text(json.dumps(
        {"limits": {"loss_gap": 1e-3}}))
    (home / "metrics" / "scratch_metric.py").write_text(
        "def read(ctx):\n    return 42.0 if ctx.cell['name'] == "
        "'scratch_cell' else None\n")
    add_entries(
        checkout,
        cells=[{"name": "scratch_cell", "config": "vgg16_cifar10",
                "traffic": "scratch_b4", "chips": 1, "why": "scratch"}],
        metrics=[{"name": "scratch_metric", "unit": "ms", "better": "lower",
                  "source": "device_trace", "layer": "scratch",
                  "moves": "step_ms_p50", "workloads": ["scratch_cell"]}])

    bench = discover.Bench(str(checkout / "BENCHMARK.json"))
    cell = bench.cell("scratch_cell")
    assert bench.traffic(cell["traffic"])["train"]["num_buckets"] == 4
    assert bench.limits("scratch_cell") == {"loss_gap": 1e-3}
    assert bench.config(cell["config"])["train"]["dnn"] == "vgg16"
    readers = {m["name"]: m["read"]
               for m in bench.metrics("per_layer", "scratch_cell")}
    assert readers["scratch_metric"](discover.Context(cell=cell)) == 42.0
    # other cells do not report it
    others = [m["name"] for m in bench.metrics("per_layer", "vgg16_dense_x1")]
    assert "scratch_metric" not in others

    # and removed again by files alone
    shutil.copy(os.path.join(discover.ROOT, "BENCHMARK.json"), checkout)
    for p in ("traffic/scratch_b4.json", "cells/scratch_cell.json",
              "metrics/scratch_metric.py"):
        os.remove(home / p)
    bench = discover.Bench(str(checkout / "BENCHMARK.json"))
    with pytest.raises(KeyError):
        bench.cell("scratch_cell")
    names = [m["name"] for m in bench.metrics("per_layer", "lstm_ptb_oktopk_x1")]
    assert "scratch_metric" not in names and "device_idle_pct" in names


def test_keys_of_a_traffic_file_reach_the_program_unchanged(checkout):
    """A field of TrainConfig or OkTopkConfig given in a data file reaches
    the program's configuration; a key that is no field is an error."""
    from benchlib.harness import _dataclass_kwargs
    from oktopk_tpu.config import OkTopkConfig, TrainConfig
    kw = _dataclass_kwargs(TrainConfig, {"dnn": "vgg16", "num_buckets": 1},
                           {"num_buckets": 4, "autotune_candidates":
                            ["dense", "oktopk"]})
    cfg = TrainConfig(**kw)
    assert cfg.num_buckets == 4 and cfg.autotune_candidates == (
        "dense", "oktopk")
    assert OkTopkConfig(**_dataclass_kwargs(
        OkTopkConfig, {"threshold_method": "sort"})).threshold_method == "sort"
    with pytest.raises(KeyError):
        _dataclass_kwargs(TrainConfig, {"num_bukets": 4})


def test_every_name_in_benchmark_json_has_its_files():
    bench = discover.Bench()
    for cell in bench.spec["workloads"]:
        assert bench.config(cell["config"])["train"]
        assert bench.traffic(cell["traffic"])["train"]
        assert bench.limits(cell["name"])
        for group in ("end_to_end", "per_layer"):
            assert bench.metrics(group, cell["name"])
