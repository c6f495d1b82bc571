"""Helpers of the CPU rehearsals: the cut that makes every cell small
enough for the CPU, the cells of BENCHMARK.json, and one run's result."""
import json
import os

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))


def shrink(config, mix):
    """Every width cut, grouped-query attention kept grouped; traffic cut
    to a few slots and short requests, its rates scaled to the CPU."""
    kv = 2 if config["num_key_value_heads"] < \
        config["num_attention_heads"] else 4
    config = dict(config, hidden_size=128, intermediate_size=256,
                  num_attention_heads=4, num_key_value_heads=kv,
                  vocab_size=512, program=dict(config["program"],
                                               smoke=True))
    mix = dict(mix)
    # limits read at this size on the CPU (program against fp32
    # reference, and the fp8 control): bf16 rounding weighs more at these
    # widths than at the cell's, so the cell's own limits would not fit
    if mix["driver"] == "train":
        mix.update(batch=4, seq=64, reference_rows=2,
                   limits=dict(mix["limits"], change_gap=2e-3))
    else:
        mix.update(slots=4, ctx_max=128, rate_per_s=20.0, lead_s=0.5,
                   follow_s=30.0, check_tokens=20,
                   prompt={"median": 24, "sigma": 0.5, "min": 8, "max": 64},
                   output={"median": 8, "sigma": 0.5, "min": 4, "max": 32},
                   limits={"logit_gap": 0.02})
    return config, mix


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
