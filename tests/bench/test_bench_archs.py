"""An architecture is a module of its own under ``bench/archs/``, found
by the configuration file's ``architectures[0]``: a second architecture
comes as new files only, and a name or a cut that no module knows stops
the run with a message that says what to add."""
import hashlib
import json
import os
import shutil

import pytest

from bench_cpu import ROOT, last_result, run_cell

# the llama decoder with RMSNorm on each head's q and k before the rotary
# positions (the program's ``qk_norm``), built on the llama module
QK_NORM_ARCH = '''"""Llama with RMSNorm on each head's q and k (qk-norm)."""
import jax
import jax.numpy as jnp

from bench import common
from bench.reference import attention, mm, rmsnorm, rope

llama = common.arch_named("LlamaForCausalLM")
dims, program_config, smoke = llama.dims, llama.program_config, llama.smoke
train_flops_per_token = llama.train_flops_per_token
decode_flops, decode_cache_bytes = llama.decode_flops, llama.decode_cache_bytes


def spec(config):
    m = dims(config)
    return dict(llama.spec(config), **{
        "blocks/L0/attn/qn": ((m["layers"], m["hd"]), "ones"),
        "blocks/L0/attn/kn": ((m["layers"], m["hd"]), "ones")})


def hidden(w, tokens, m, prec="fp32"):
    pos = jnp.arange(tokens.shape[1])
    h = w["embed"][tokens]
    for l in range(m["layers"]):
        def W(name):
            return w["blocks/L0/" + name][l]
        x = rmsnorm(h, W("attn_norm/scale"), m["eps"])
        q = rmsnorm(mm("bsd,dnh->bsnh", x, W("attn/wq"), prec),
                    W("attn/qn"), m["eps"])
        k = rmsnorm(mm("bsd,dnh->bsnh", x, W("attn/wk"), prec),
                    W("attn/kn"), m["eps"])
        v = mm("bsd,dnh->bsnh", x, W("attn/wv"), prec)
        q, k = rope(q, pos, m["theta"]), rope(k, pos, m["theta"])
        h = h + mm("bsnh,nhd->bsd", attention(q, k, v, prec),
                   W("attn/wo"), prec)
        x = rmsnorm(h, W("ffn_norm/scale"), m["eps"])
        a = jax.nn.silu(mm("bsd,df->bsf", x, W("ffn/wg"), prec))
        a = a * mm("bsd,df->bsf", x, W("ffn/wu"), prec)
        h = h + mm("bsf,fd->bsd", a, W("ffn/wd"), prec)
    return rmsnorm(h, w["final_norm/scale"], m["eps"]), 0.0
'''

QK_NORM_CONFIG = {
    "source": "arXiv:2405.09818",
    "architectures": ["ChameleonForConditionalGeneration"],
    "hidden_size": 8192, "intermediate_size": 22016,
    "num_attention_heads": 64, "num_key_value_heads": 8,
    "num_hidden_layers": 1, "vocab_size": 65536, "rms_norm_eps": 1e-06,
    "rope_theta": 10000.0, "tie_word_embeddings": False,
    "reduced": {"num_hidden_layers": [48, 1]},
    "program": {"arch": "chameleon-34b", "param_dtype": "float32",
                "compute_dtype": "bfloat16", "remat": False},
}


def _digests(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


@pytest.fixture
def checkout(cpu_bench, monkeypatch, tmp_path):
    """A copy of the benchmark that the harness reads in place of the
    repository's, and the digests of its files as copied."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    monkeypatch.setattr(cpu_bench, "ROOT", str(root))
    monkeypatch.setattr(cpu_bench, "BENCH_DIR", str(root / "bench"))
    return root, _digests(root)


def _add_cell(root, name, config, mix):
    """Files for a configuration, a training mix and a cell, and their
    entries in BENCHMARK.json."""
    (root / f"bench/configs/{name}.json").write_text(json.dumps(config))
    (root / f"bench/traffic/{name}.json").write_text(json.dumps(mix))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": name, "source": config["source"],
                             "file": f"bench/configs/{name}.json",
                             "reduced": list(config["reduced"]),
                             "why": "a test"})
    bench["workloads"].append({"name": f"train-{name}", "config": name,
                               "traffic": name, "chips": 1,
                               "why": "a test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return f"train-{name}"


def _mix():
    with open(os.path.join(ROOT, "bench/traffic/sngm-8k.json")) as f:
        return json.load(f)


def test_a_second_architecture_is_new_files_only(checkout, capsys):
    """A qk-norm decoder (the program's chameleon-34b at smoke widths):
    its module, a configuration that names it, a training mix and a cell
    are added; the cell runs correct on the CPU, and no file that was
    there before changed but BENCHMARK.json, which only gained entries."""
    root, before = checkout
    with open(root / "BENCHMARK.json") as f:
        old = json.load(f)
    (root / "bench/archs/ChameleonForConditionalGeneration.py").write_text(
        QK_NORM_ARCH)
    cell = _add_cell(root, "qknorm-1L", QK_NORM_CONFIG, _mix())
    run_cell(cell, seed=2 ** 33 + 5)
    res = last_result(capsys)
    assert res["correct"] is True, res["checks"]
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}
    after = _digests(root)
    assert {p: after[p] for p in before if p != "BENCHMARK.json"} == {
        p: d for p, d in before.items() if p != "BENCHMARK.json"}
    with open(root / "BENCHMARK.json") as f:
        new = json.load(f)
    assert {k: v[:len(old[k])] if isinstance(v, list) else v
            for k, v in new.items()} == old


def _llama_config(**change):
    with open(os.path.join(ROOT, "bench/configs/yi-9b-1L.json")) as f:
        return dict(json.load(f), **change)


@pytest.mark.parametrize("config, message", [
    (_llama_config(architectures=["NoSuchForCausalLM"]),
     "add bench/archs/NoSuchForCausalLM.py"),
    (_llama_config(reduced={"num_hidden_layers": [48, 1],
                            "vocab_size": [64000, 8000]}),
     r"cannot cut \['vocab_size'\]"),
    (_llama_config(num_hidden_layers=2, reduced={}),
     "differs from the configuration file"),
], ids=["unknown_architecture", "unknown_cut", "cut_not_listed"])
def test_a_configuration_no_module_can_run_is_refused(checkout, config,
                                                      message):
    root, _ = checkout
    cell = _add_cell(root, "refused", config, _mix())
    with pytest.raises(SystemExit, match=message):
        run_cell(cell)
