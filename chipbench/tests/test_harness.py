"""The harness finds a cell's files by name, and refuses to run without a
TPU."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

import smoke  # noqa: F401  (puts the checkout on sys.path)

from chipbench import harness  # noqa: E402

ROOT = smoke.ROOT


def test_peaks_reject_an_unknown_device_kind():
    assert harness.peak("TPU v5 lite", "bf16_flops") == 197e12
    with pytest.raises(harness.BenchError, match="not in peaks.json"):
        harness.peak("TPU v9 imaginary", "bf16_flops")


def test_every_cell_finds_its_files():
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    for w in bench["workloads"]:
        c = harness.cell(w["name"])
        assert c.chips == w["chips"]
        assert c.limits and set(c.limits) <= set(c.kind.CHECKS)
        assert harness.reference(c.config).layout(c.config["model"])
        for m in c.per_layer:
            assert callable(harness.metric_reader(m["name"]))


def test_new_files_and_entries_are_found_with_no_other_edit(tmp_path):
    """A configuration, a traffic mix, a cell's limits and a per-layer
    metric added as files, plus their entries in BENCHMARK.json."""
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cb = tmp_path / "chipbench"
    config = json.loads((cb / "configs" / "mamba2-370m.json").read_text())
    config["name"] = "mamba2-new"
    (cb / "configs" / "mamba2-new.json").write_text(json.dumps(config))
    (cb / "traffic" / "train-new.json").write_text(json.dumps(
        dict(smoke.TRAFFIC, seq_len=4096)))
    (cb / "limits" / "mamba2-new.train.json").write_text(json.dumps(
        smoke.LIMITS))
    (cb / "metrics" / "new_metric.train.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    bench["configs"].append({"name": "mamba2-new", "source": "x",
                             "file": "chipbench/configs/mamba2-new.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "mamba2-new.train",
                               "config": "mamba2-new",
                               "traffic": "train-new", "chips": 1,
                               "why": "x"})
    bench["per_layer"].append({"name": "new_metric.train", "unit": "%",
                               "better": "higher", "source": "device_trace",
                               "layer": "device",
                               "moves": "train_tokens_per_s",
                               "workloads": ["mamba2-new.train"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    c = harness.cell("mamba2-new.train", root=str(tmp_path))
    assert c.config["name"] == "mamba2-new"
    assert c.traffic["seq_len"] == 4096
    assert c.limits == smoke.LIMITS
    assert [m["name"] for m in c.per_layer] == ["new_metric.train"]
    read = harness.metric_reader("new_metric.train", root=str(tmp_path))
    assert read({}) == 42.0
    # the cells that were there do not see the new metric
    old = harness.cell(bench["workloads"][0]["name"], root=str(tmp_path))
    assert "new_metric.train" not in [m["name"] for m in old.per_layer]


TOY_KIND = """
CHECKS = ("gap",)
END_TO_END = ("toy_ops_per_s",)


def run(cell, seed, seconds, trace, devices):
    return {"t_window": 0.0, "attempted": 3, "failed": 0,
            "end_to_end": {"toy_ops_per_s": cell.traffic["ops"] / 2.0},
            "checks": {"gap": (0.5, cell.limits["gap"])},
            "device": {"platform": "cpu", "kind": "cpu", "count": 1},
            "summary": None}
"""


def test_a_new_kind_and_end_to_end_metric_need_only_files(tmp_path):
    """A traffic kind with its own generator and end-to-end metric, added
    as files and entries, is run by ``run.py``'s own path."""
    from chipbench import run

    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cb = tmp_path / "chipbench"
    (cb / "kinds" / "toy.py").write_text(TOY_KIND)
    (cb / "traffic" / "toy-mix.json").write_text(json.dumps(
        {"kind": "toy", "ops": 10}))
    (cb / "limits" / "mamba2-370m.toy.json").write_text(json.dumps(
        {"gap": 1.0}))
    bench["workloads"].append({"name": "mamba2-370m.toy",
                               "config": "mamba2-370m",
                               "traffic": "toy-mix", "chips": 1,
                               "why": "x"})
    bench["end_to_end"].append({"name": "toy_ops_per_s", "unit": "ops/s",
                                "better": "higher", "bound": 0.05,
                                "source": "host_clock",
                                "workloads": ["mamba2-370m.toy"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    c = harness.cell("mamba2-370m.toy", root=str(tmp_path))
    line, _ = run.run_cell(c, 5, 1.0, False, [], t0=-12.5)
    assert line["correct"] is True
    assert line["metrics"] == {"toy_ops_per_s": {"value": 5.0,
                                                 "unit": "ops/s"},
                               "setup_s": {"value": 12.5, "unit": "s"}}
    assert list(line)[-1] == "checks"
    assert line["checks"] == {"gap": {"value": 0.5, "limit": 1.0}}

    # an end-to-end metric that the cell's kind does not measure, and a
    # limit on a number its check does not compute, are refused
    bench["end_to_end"][-1]["workloads"].append("mamba2-370m.train")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    with pytest.raises(harness.BenchError, match="measures none"):
        harness.cell("mamba2-370m.train", root=str(tmp_path))
    (cb / "limits" / "mamba2-370m.toy.json").write_text(json.dumps(
        {"gap": 1.0, "grad_gap": 0.1}))
    with pytest.raises(harness.BenchError, match="no check computes"):
        harness.cell("mamba2-370m.toy", root=str(tmp_path))


def test_program_config_compares_each_key_the_file_states():
    train = harness.kind("train")
    config = harness.load_json(os.path.join(
        ROOT, "chipbench", "configs", "mamba2-370m.json"))
    assert train.program_config(config).d_model == 1024
    model = dict(config["model"], sliding_window=0)
    assert train.program_config(dict(config, model=model))
    wrong = dict(model, d_model=512, ssm=dict(model["ssm"], d_state=64),
                 num_experts_per_token=2)
    with pytest.raises(harness.BenchError) as e:
        train.program_config(dict(config, model=wrong))
    for key in ("d_model", "d_state", "num_experts_per_token': (2, 'absent"):
        assert key in str(e.value)


def _run(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "mamba2-370m.train", "--seed", "5", "--seconds", "1",
         "--trace", "0", *extra], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def test_run_without_a_tpu_fails_and_prints_nothing():
    p = _run(ROOT)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "no TPU" in p.stderr


def test_run_from_the_benchmark_files_alone_fails_and_prints_nothing(
        tmp_path):
    """A directory with only BENCHMARK.json and the files under paths."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout == ""


def test_peak_bytes_count_what_the_runtime_reserves():
    # memory_stats() of a v5e after the mamba2-370m step at 16 rows
    stats = {"peak_bytes_in_use": 3713498624,
             "peak_bytes_reserved": 9988784128}
    assert harness.peak_bytes(stats) == 13702282752
    assert harness.peak_bytes({"peak_bytes_in_use": 5}) == 5
    assert harness.peak_bytes({}) is None
