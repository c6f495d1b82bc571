"""DeepSeek-V2-Lite on one chip's share of its experts
(``bench/archs/DeepseekV2ForCausalLM.py``, ``deepseek-v2-lite-5L``): the
counts its costs rest on, the configurations its module refuses, the
MoE layer's two readers, and its cell's CPU rehearsal."""
import json
import os
from types import SimpleNamespace as NS

import numpy as np
import pytest

from bench import common, peaks, weights
from bench_cpu import ROOT, cells

CONFIG = os.path.join(ROOT, "bench", "configs", "deepseek-v2-lite-5L.json")
CELL = "train-dsv2lite-8k"


def _config(**change):
    with open(CONFIG) as f:
        return dict(json.load(f), **change)


def test_counts_of_the_configuration():
    """535,060,992 parameters: embedding and unembedding 52,428,800, the
    dense layer 81,007,104, each MoE layer 100,405,760 (held routed
    experts 69,206,016, shared 17,301,504, attention 13,763,072, router
    131,072, norms 4,096), the final norm 2,048; and the train FLOPs a
    token at 8192 with the routed weights counted at k * held / E."""
    config = _config()
    arch = common.arch(config)
    m = arch.dims(config)
    assert weights.n_params(config) == 535_060_992
    sp = arch.spec(config)
    moe = sum(int(np.prod(s[1:])) for n, (s, _) in sp.items()
              if n.startswith("blocks/L0/"))
    assert moe == 100_405_760
    assert peaks.train_flops_per_token(m, 8192) == 2_806_139_904
    # attention's term: 6 L H (192 + 128) S
    assert 6 * 5 * 16 * 320 * 8192 == 1_258_291_200
    # the held experts' products, per token, every MoE layer
    assert arch.moe_experts_flops_per_token(m) == pytest.approx(
        6 * 3 * 2048 * 1408 * 6 * 8 / 64 * 4)
    # latent attention's cache: 512 latent + 64 rope values a position
    assert arch.decode_cache_bytes(m, 1, 2) - arch.decode_cache_bytes(
        m, 0, 2) == 5 * 576 * 2


def test_the_program_holds_what_the_file_says():
    config = _config()
    cfg = common.arch(config).program_config(config)
    assert (cfg.moe.n_experts, cfg.moe.held, cfg.moe.first_held,
            cfg.moe.top_k, cfg.moe.n_shared, cfg.moe.n_dense_prefix) == (
        64, 8, 0, 6, 2, 1)
    assert (cfg.n_layers, cfg.vocab_size) == (5, 12800)
    assert cfg.rope_scaling.factor == 40 and cfg.moe.seq_aux


@pytest.mark.parametrize("change, message", [
    ({"reduced": {"num_hidden_layers": [27, 5], "n_routed_experts": [32, 8],
                  "vocab_size": [102400, 12800]}}, "'router': \\(64, 32\\)"),
    ({"n_routed_experts": 16}, "'n_routed_experts': \\(16, 8\\)"),
    ({"num_experts_per_tok": 4}, "'num_experts_per_tok': \\(6, 4\\)"),
    ({"reduced": {"num_hidden_layers": [27, 5],
                  "vocab_size": [102400, 12800]}},
     "'n_routed_experts': \\(64, 8\\)"),
    ({"reduced": {"num_hidden_layers": [27, 5], "n_routed_experts": [64, 8],
                  "vocab_size": [102400, 12800],
                  "moe_intermediate_size": [1408, 704]}},
     r"cannot cut \['moe_intermediate_size'\]"),
    ({"rope_scaling": None}, "'rope_scaling'"),
    ({"norm_topk_prob": True}, "'norm_topk_prob'"),
    ({"topk_method": "group_limited_greedy"}, "'topk_method'"),
], ids=["router_width", "held_count", "top_k", "cut_not_listed",
        "unknown_cut", "no_rope_scaling", "renormalized", "grouped_topk"])
def test_a_configuration_the_program_does_not_run_is_refused(change,
                                                             message):
    config = _config(**change)
    with pytest.raises(SystemExit, match=message):
        common.arch(config).program_config(config)


def _ops_trace():
    """Window 0..1000 ns; two train-step runs; ops under each scope."""
    def ev(name, start, dur):
        return NS(name=name, start_ns=float(start), duration_ns=float(dur))

    def plane(name, **lines):
        return NS(name=name, lines=[NS(name=k.replace("_", " "), events=v)
                                    for k, v in lines.items()])
    host = plane("/host:CPU", python3=[ev("bench.window", 0, 1000)])
    dev = plane("/device:TPU:0",
                XLA_Modules=[ev("jit_train_step(7)", 0, 500),
                             ev("jit_train_step(7)", 500, 500)],
                XLA_Ops=[ev("%while.1 = ()", 0, 500),
                         ev("%gmm.1 = ()", 10, 100),
                         ev("%tgmm.2 = ()", 120, 60),
                         ev("%sort.3 = ()", 200, 40),
                         ev("%fusion.4 = ()", 300, 50),
                         ev("%gmm.1 = ()", 510, 100),
                         ev("%fusion.9 = ()", 700, 20)])
    paths = {
        ("7", "gmm.1"): "jit(train_step)/while/body/fwd_bwd/mlp/"
                        "jvp(moe_experts)/pallas_call",
        ("7", "tgmm.2"): "jit(train_step)/while/body/fwd_bwd/"
                         "transpose(jvp(mlp))/transpose(jvp(moe_experts))/"
                         "pallas_call",
        ("7", "sort.3"): "jit(train_step)/while/body/fwd_bwd/mlp/"
                         "jvp(moe_route)/sort",
        ("7", "fusion.4"): "jit(train_step)/while/body/fwd_bwd/mlp/"
                           "transpose(jvp(moe_route))/scatter-add",
        ("7", "fusion.9"): "jit(train_step)/while/body/fwd_bwd/mlp/mul",
    }
    return NS(planes=[host, dev]), paths


def test_the_moe_readers_on_a_constructed_trace(monkeypatch, tmp_path):
    from bench import scope_time
    pd, paths = _ops_trace()
    assert scope_time.by_scope(pd, paths, 0, 1000, "moe_experts") == {
        "jit_train_step": pytest.approx(260e-9)}
    assert scope_time.by_scope(pd, paths, 0, 1000, "moe_route") == {
        "jit_train_step": pytest.approx(90e-9)}
    assert scope_time.by_scope(pd, paths, 0, 1000, "attention") == {}

    # the readers, through the newest trace under the benchmark's output
    tdir = tmp_path / "traces" / "cell-1"
    tdir.mkdir(parents=True)
    (tdir / "t.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(common, "OUT_DIR", str(tmp_path))
    monkeypatch.setattr(scope_time, "_read",
                        lambda path, mtime: (pd, (0, 1000), paths))
    config = _config()
    with open(os.path.join(ROOT, "bench", "traffic",
                           "sngm-dsv2-8k.json")) as f:
        mix = json.load(f)
    run = {"dims": common.arch(config).dims(config), "mix": mix,
           "kind": "TPU v5 lite", "chips": 1,
           "trace": {"window_s": 1000e-9,
                     "modules": {"jit_train_step": 2.0}}}
    route = common.reader("moe_route.ms_per_step").read(run)
    assert route == pytest.approx(1e3 * 90e-9 / 2)
    mfu = common.reader("moe_experts.mfu").read(run)
    flops = 6 * 3 * 2048 * 1408 * 32768 * 6 * 8 / 64 * 4
    assert mfu == pytest.approx(100 * flops / (260e-9 / 2 * 197e12))
    # another window: not this run's trace, no reading
    other = dict(run, trace=dict(run["trace"], window_s=2e-6))
    assert common.reader("moe_route.ms_per_step").read(other) is None
    assert common.reader("moe_experts.mfu").read(other) is None
    # a llama run has no expert products to count
    llama = dict(run, dims={"arch": "LlamaForCausalLM"})
    assert common.reader("moe_experts.mfu").read(llama) is None


def test_the_cell_is_rehearsed_on_the_cpu_by_its_entry_alone():
    """``test_bench_rehearsal`` runs every cell of BENCHMARK.json; this
    one needs no test of its own."""
    assert CELL in cells()


def test_fp8_control_fails_where_the_program_passes(cpu_bench):
    from bench.drivers import train
    bench = cpu_bench.load_benchmark()
    _, config, mix = cpu_bench.cell_files(bench, CELL)
    seed = 2 ** 33 + 11
    ref = train.reference_steps(config, mix, seed)
    job = train.Job(config, mix, seed)
    got = train.compare(job.first_steps(), ref)
    job.free()
    ctl = train.compare(train.reference_steps(config, mix, seed, "fp8"),
                        ref)
    limits = mix["limits"]
    assert all(got[k] <= limits[k] for k in limits), got
    assert any(ctl[k] > limits[k] for k in limits), ctl
