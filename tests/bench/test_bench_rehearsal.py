"""Every cell end to end on the CPU at smoke size, the refusals, and
the harness finding a new cell, configuration, mix and metric by name."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench_cpu import ROOT, cells, last_result, run_cell

KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


@pytest.mark.parametrize("workload", cells())
def test_cell_runs_on_cpu(workload, cpu_bench, capsys):
    run_cell(workload, seed=2 ** 33 + 5)
    res = last_result(capsys)
    assert list(res)[:5] == KEYS[:5] and list(res)[-1] == "checks"
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    bench = cpu_bench.load_benchmark()
    want = {m["name"] for m in bench["end_to_end"]
            if workload in m.get("workloads", [workload])}
    assert set(res["metrics"]) == want
    assert res["device"] == {"platform": "cpu", "kind": "cpu", "count": 1,
                             "memory_peak_bytes": 0}
    for c in res["checks"].values():
        assert set(c) == {"value", "limit"}


def _run(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", cells()[0],
         "--seed", "1", "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_without_a_tpu():
    r = _run(ROOT)
    assert r.returncode != 0
    assert '"correct"' not in r.stdout
    assert "needs 'tpu'" in r.stderr, r.stderr[-2000:]


def test_refuses_in_a_checkout_without_the_program(tmp_path):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        paths = json.load(f)["paths"]
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in paths:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    # steered to the CPU as the other rehearsals are, so that what stops
    # the run is the missing program and not the missing chip
    code = ("import sys; sys.path.insert(0, '.'); from bench import common; "
            "common.PLATFORM = 'cpu'; import bench.run as run; "
            f"run.main(['--workload', {cells()[0]!r}, '--seed', '1', "
            "'--seconds', '1', '--trace', '0'])")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    r = subprocess.run([sys.executable, "-c", code], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"correct"' not in r.stdout
    assert "No module named 'repro'" in r.stderr, r.stderr[-2000:]


NEW_METRIC = '''"""Prefill calls in the traced window (added as a file of its own)."""


def read(run):
    rec = run.get("record") or {}
    return rec.get("prefill_rows") and float(rec["prefill_rows"])
'''


def test_new_cell_config_mix_and_metric_are_found(cpu_bench, capsys,
                                                  monkeypatch, tmp_path):
    """A later PR adds files and entries and edits no file: a copy of
    the benchmark gains a configuration, a traffic mix judged on
    throughput above the knee, a cell, an end-to-end metric and a
    per-layer metric, and the harness runs the new cell."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    conf = json.loads((root / "bench/configs/deepseek-7b-2L.json")
                      .read_text())
    (root / "bench/configs/deepseek-7b-1L.json").write_text(json.dumps(
        dict(conf, num_hidden_layers=1)))
    mix = json.loads((root / "bench/traffic/chat.json").read_text())
    (root / "bench/traffic/chat-flood.json").write_text(json.dumps(
        dict(mix, judge="throughput", rate_per_s=mix["rate_per_s"] * 4)))
    (root / "bench/metrics/prefill.rows.py").write_text(NEW_METRIC)
    bench["configs"].append({"name": "deepseek-7b-1L",
                             "source": conf["source"],
                             "file": "bench/configs/deepseek-7b-1L.json",
                             "reduced": ["num_hidden_layers"],
                             "why": "one layer"})
    bench["workloads"].append({"name": "serve-ds7b1-flood",
                               "config": "deepseek-7b-1L",
                               "traffic": "chat-flood", "chips": 1,
                               "why": "above the knee"})
    bench["end_to_end"].append({"name": "serve_tokens_per_s",
                                "unit": "tokens/s", "better": "higher",
                                "bound": 0.05, "source": "host_clock",
                                "workloads": ["serve-ds7b1-flood"]})
    bench["per_layer"].append({"name": "prefill.rows", "unit": "tokens",
                               "better": "lower", "source": "program_span",
                               "layer": "scheduler",
                               "moves": "serve_tokens_per_s",
                               "workloads": ["serve-ds7b1-flood"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    monkeypatch.setattr(cpu_bench, "ROOT", str(root))
    monkeypatch.setattr(cpu_bench, "BENCH_DIR", str(root / "bench"))
    names = [m["name"] for m in cpu_bench.per_layer_for(
        cpu_bench.load_benchmark(), "serve-ds7b1-flood")]
    assert names == ["prefill.rows"]
    reader = cpu_bench.reader("prefill.rows")
    assert reader.read({"record": {"prefill_rows": 96}}) == 96.0
    run_cell("serve-ds7b1-flood")
    res = last_result(capsys)
    assert res["correct"] is True
    assert set(res["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert res["metrics"]["serve_tokens_per_s"]["value"] > 0
