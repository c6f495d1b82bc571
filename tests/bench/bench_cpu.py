"""Helpers of the CPU rehearsals: the cut that makes every cell small
enough for the CPU, the cells of BENCHMARK.json, and one run's result."""
import json
import os

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))


def shrink(config, mix):
    """The configuration cut by its architecture's module, the traffic by
    its mix's driver: each brings its own CPU cut (``smoke``)."""
    from bench import common
    return (common.arch(config).smoke(config),
            common.driver(mix["driver"]).smoke(mix))


def cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


def last_result(capsys):
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


def run_cell(workload, seed=7, seconds=1, trace=0):
    import bench.run as run
    run.main(["--workload", workload, "--seed", str(seed), "--seconds",
              str(seconds), "--trace", str(trace)])
