"""The benchmark's yardstick: peaks by device kind, and the operations
and bytes that its shares of a peak are computed from."""
import json
import os

import pytest

from bench import common, peaks, weights

ROOT = os.path.join(os.path.dirname(__file__), "..", "..")


def _config(name):
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
        return json.load(f)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks known"):
        peaks.peaks("TPU v99")
    assert peaks.peaks("TPU v5 lite")["bf16_flops"] == 197e12


def _dims(name):
    config = _config(name)
    return common.arch(config), common.arch(config).dims(config)


def test_train_step_flops_of_the_yi9b_cell():
    arch, m = _dims("yi-9b-1L")
    assert arch.params_without_input_embedding(m) == 435_171_328
    per_step = peaks.train_flops_per_token(m, 1024) * 8 * 1024
    assert per_step == pytest.approx(21.8e12, rel=2e-3)


def test_sngm_update_bytes_of_the_yi9b_cell():
    n = weights.n_params(_config("yi-9b-1L"))
    assert n == 697_315_328
    assert peaks.sngm_min_bytes(n) == 20 * 697_315_328


def test_paged_decode_bytes_for_a_small_block_table():
    # two layers, 32 kv heads of 128: a sequence at position 32 attends
    # 33 keys (two 16-token blocks, the second one entry deep), bf16
    m = {"arch": "LlamaForCausalLM", "layers": 2, "k": 32, "h": 32,
         "hd": 128}
    kv = 2 * 33 * 32 * 128 * 2
    qo = 2 * 32 * 128 * 2
    assert peaks.paged_decode_bytes(m, 33) == 2 * (kv + qo)
    # grouped heads read their shared K/V once
    gqa = dict(m, k=4)
    assert peaks.paged_decode_bytes(gqa, 33) == 2 * (
        2 * 33 * 4 * 128 * 2 + qo)


def test_decode_flops_grow_with_context():
    arch, m = _dims("deepseek-7b-2L")
    base = 2.0 * arch.params_without_input_embedding(m)
    assert peaks.decode_flops(m, 0) == base
    assert peaks.decode_flops(m, 100) - base == 4.0 * 2 * 4096 * 100


# (parameters, train FLOPs a token at 1024, decode FLOPs at 512 keys,
# paged decode bytes at 512 keys) of each configuration file, as the
# benchmark counted them before its architectures had modules of their own
COUNTS = {
    "yi-9b-1L": (697_315_328, 2661359616.0, 878731264.0, 1064960.0),
    "deepseek-7b-2L": (1_243_631_616, 5045870592.0, 1665179648.0,
                       16809984.0),
}


@pytest.mark.parametrize("name", sorted(COUNTS), ids=lambda n: n.replace(
    "-", ""))
def test_counts_of_each_configuration_hold(name):
    _, m = _dims(name)
    assert (weights.n_params(_config(name)),
            peaks.train_flops_per_token(m, 1024), peaks.decode_flops(m, 512),
            peaks.paged_decode_bytes(m, 512)) == COUNTS[name]


def test_serving_readers_on_a_constructed_window():
    """The paged kernel's roofline and the decode step's share of the
    peak, from the keys each decoded token attended in a traced window."""
    _, m = _dims("deepseek-7b-2L")
    keys = [300 + i for i in range(64)]
    run = {"dims": m, "kind": "TPU v5 lite", "chips": 1,
           "record": {"keys": keys, "prefill_real": 300,
                      "prefill_rows": 32 * 512},
           "trace": {"window_s": 1.0,
                     "module_s": {"jit_chunk": 0.25, "jit_prefill": 0.5},
                     "ops_s": {"paged_decode_attention": 0.05}}}
    moved = sum(peaks.paged_decode_bytes(m, n) for n in keys)
    roof = common.reader("paged_decode_roofline").read(run)
    assert roof == pytest.approx(100 * moved / 819e9 / 0.05)
    assert 0 < roof < 100
    # over the decode programs' device seconds, not the whole window
    mfu = common.reader("decode_step.mfu").read(run)
    flops = sum(peaks.decode_flops(m, n) for n in keys)
    assert mfu == pytest.approx(100 * flops / (0.25 * 197e12))
    assert common.reader("decode_step.mfu").read(
        dict(run, trace=dict(run["trace"], module_s={}))) is None
    frac = common.reader("prefill.useful_frac").read(run)
    assert frac == pytest.approx(100 * 300 / (32 * 512))
    assert common.reader("paged_decode_roofline").read(
        dict(run, record={"keys": []})) is None
