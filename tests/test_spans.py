"""The program's spans and scopes: the train step's phases and the
model's scopes in the compiled programs' op metadata, the paged
scheduler's host spans and counters, and the launchers' --profile-dir
traces."""
import dataclasses
import glob
import os
import re

import jax
import numpy as np
import pytest

from repro.configs import ARCHS, smoke_variant
from repro.core import sngm
from repro.core.schedules import poly_power
from repro.models import CPU_RUNTIME, model_defs
from repro.models.param import materialize

STEP_PHASES = ("params_view", "fwd_bwd", "grad_pack", "grad_accum",
               "sngm_update")
PHASES = {"serve.admit", "serve.prefill", "serve.grow_blocks",
          "serve.chunk", "serve.sync", "serve.emit"}


@pytest.fixture(scope="module")
def setup():
    cfg = dataclasses.replace(smoke_variant(ARCHS["deepseek-7b"]),
                              vocab_size=64)
    params = materialize(model_defs(cfg), jax.random.PRNGKey(0))
    return cfg, params


def scopes(compiled):
    """Every name on the op_name paths of a compiled program's ops,
    transformation wrappers such as 'transpose(jvp(mlp))' opened up."""
    out = set()
    for path in re.findall(r'op_name="([^"]*)"', compiled.as_text()):
        out.update(p for p in re.split(r"[/()]", path) if p)
    return out


def test_train_step_carries_every_phase_scope(setup):
    from repro.training import make_train_step
    cfg, params = setup
    opt = sngm(poly_power(1.0, 10, 1.1), beta=0.9, fused="multi_tensor")
    state = opt.init_state(params)
    step = jax.jit(make_train_step(cfg, CPU_RUNTIME, opt, n_micro=2))
    batch = {"tokens": np.zeros((4, 16), np.int32),
             "loss_mask": np.ones((4, 16), np.float32)}
    got = scopes(step.lower(state, batch).compile())
    for name in STEP_PHASES + ("embed", "attention", "mlp", "loss"):
        assert name in got, name


def test_prefill_and_decode_chunk_carry_the_model_scopes(setup):
    from repro.serving.scheduler import PagedScheduler
    cfg, params = setup
    s = PagedScheduler(cfg, params, CPU_RUNTIME, n_slots=2, block_size=4,
                       n_blocks=9, ctx_max=16, decode_chunk=2)
    prefill = s._prefill.lower(params, np.zeros((2, 8), np.int32),
                               last_pos=np.zeros((2,), np.int32)).compile()
    rngs = jax.random.split(jax.random.PRNGKey(0), 2)
    chunk = s._chunk.lower(params, s.paged, s.tok, s.pos,
                           np.ones((2, 2), bool), rngs).compile()
    for compiled in (prefill, chunk):
        got = scopes(compiled)
        for name in ("embed", "attention", "mlp", "unembed"):
            assert name in got, name


def host_spans(log_dir, prefix):
    """[(name, start, end, args, parent index)] of the host events whose
    names start with ``prefix``, nested per thread."""
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                     recursive=True)
    assert path, os.listdir(log_dir)
    out = []
    for plane in ProfileData.from_file(path[0]).planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            evs = sorted(((e.name, e.start_ns, e.start_ns + e.duration_ns,
                           dict(e.stats)) for e in line.events
                          if e.name.startswith(prefix)),
                         key=lambda x: (x[1], x[1] - x[2]))
            stack = []
            for name, s, e, args in evs:
                while stack and out[stack[-1]][2] <= s:
                    stack.pop()
                out.append((name, s, e, args, stack[-1] if stack else None))
                stack.append(len(out) - 1)
    return out


def traced(log_dir):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return jax.profiler.trace(log_dir, profiler_options=opts)


def test_scheduler_spans_nest_and_count(setup, tmp_path, monkeypatch):
    """Each round's phases are its direct children; every device-to-host
    read is a serve.sync (one per active slot, one per prefill group, one
    for the chunk's tokens); the counters agree with the spans and with
    the requests."""
    from repro.serving import scheduler as sch
    cfg, params = setup
    writes = []
    real_set = sch.set_block_table
    monkeypatch.setattr(sch, "set_block_table",
                        lambda *a: writes.append(1) or real_set(*a))
    s = sch.PagedScheduler(cfg, params, CPU_RUNTIME, n_slots=3,
                           block_size=4, n_blocks=40, ctx_max=32,
                           decode_chunk=3, buckets=[8, 16])
    rng = np.random.RandomState(1)
    reqs = [sch.ServeRequest(rid=i, max_new=int(rng.randint(2, 9)),
                             prompt=rng.randint(0, 64, int(rng.randint(
                                 3, 14))).astype(np.int32))
            for i in range(7)]
    for r in reqs:
        s.submit(r)
    # what each round should read back, counted outside the scheduler
    want, groups = [], [0]
    prefill_group, decode = s._prefill_group, s.decode

    def count_groups(bucket, group):
        groups[0] += 1
        return prefill_group(bucket, group)

    def count_reads():
        active = sum(r is not None for r in s.slots)
        want.append(groups[0] + active + (active > 0))
        groups[0] = 0
        return decode()
    s._prefill_group, s.decode = count_groups, count_reads
    with traced(str(tmp_path)):
        s.run()
    spans = host_spans(str(tmp_path), "serve.")
    rounds = [i for i, sp in enumerate(spans) if sp[0] == "serve.round"]
    assert len(rounds) == s.stats["rounds"] == len(want) > 1
    per_round = []
    for r in rounds:
        kids = [sp for sp in spans if sp[4] == r]
        assert {k[0] for k in kids} <= PHASES
        inside = [sp for sp in spans if sp[0] == "serve.sync"
                  and spans[r][1] <= sp[1] < spans[r][2]]
        per_round.append(len(inside))
    assert per_round == want
    for name, s_, e, args, parent in spans:
        if name == "serve.splice":
            assert spans[parent][0] == "serve.prefill"
        if name == "serve.sync":
            assert args["what"] in ("pos", "first", "toks")
            assert spans[parent][0] in ("serve.grow_blocks",
                                        "serve.prefill", "serve.round")
    assert s.stats["host_syncs"] == sum(sp[0] == "serve.sync"
                                        for sp in spans)
    pre = [sp[3] for sp in spans if sp[0] == "serve.prefill"]
    assert sum(a["real_tokens"] for a in pre) == \
        s.stats["prefill_real_tokens"] == sum(len(r.prompt) for r in reqs)
    assert s.stats["prefill_slot_tokens"] == sum(
        3 * a["bucket"] for a in pre)
    assert sum(a["rows"] for a in pre) == len(reqs)
    # the first token of each request comes from its prefill
    assert s.stats["decode_tokens"] == sum(r.max_new - 1 for r in reqs)
    assert s.stats["table_writes"] == len(writes)
    assert all(r.t_submit <= r.t_admit <= r.t_first for r in reqs)
    assert s.stats["queue_wait_s"] == pytest.approx(
        sum(r.t_admit - r.t_submit for r in reqs))


def test_train_launcher_profiles_steps_3_to_5(tmp_path):
    from repro.launch.train import main
    main(["--arch", "deepseek-7b", "--reduced", "--steps", "7", "--batch",
          "2", "--seq", "16", "--n-micro", "1", "--log-every", "7",
          "--profile-dir", str(tmp_path)])
    steps = [sp[3]["step_num"] for sp in host_spans(str(tmp_path), "train")
             if sp[0] == "train"]
    assert sorted(steps) == [3, 4, 5]


def test_serve_launcher_profiles_the_scheduler(tmp_path):
    from repro.launch.serve import main
    main(["--arch", "deepseek-7b", "--reduced", "--requests", "4",
          "--slots", "2", "--prompt-len", "6", "--max-new", "5",
          "--profile-dir", str(tmp_path)])
    names = {sp[0] for sp in host_spans(str(tmp_path), "serve.")}
    assert {"serve.round", "serve.admit", "serve.prefill", "serve.splice",
            "serve.grow_blocks", "serve.chunk", "serve.sync",
            "serve.emit"} <= names
