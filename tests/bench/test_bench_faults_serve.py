"""A serving run whose decode step alters the tokens it makes comes out
not correct; the fp8 control separates from the served tokens."""
from bench_cpu import last_result, run_cell


def test_token_altered_where_it_is_made(cpu_bench, capsys, monkeypatch):
    import repro.serving.scheduler as scheduler
    real = scheduler.make_serve_step

    def make(cfg, rt, **kw):
        step = real(cfg, rt, **kw)

        def altered(params, cache, tokens, pos, rng=None):
            nxt, last, cache = step(params, cache, tokens, pos, rng)
            return (nxt + 1) % cfg.vocab_size, last, cache
        return altered
    monkeypatch.setattr(scheduler, "make_serve_step", make)
    run_cell("serve-ds7b-chat")
    res = last_result(capsys)
    assert res["correct"] is False
    c = res["checks"]["logit_gap"]
    assert c["value"] > c["limit"]


def test_fp8_control_fails_where_the_program_passes(cpu_bench):
    """The served tokens of a short open loop, read against the fp32
    reference, stay within the limit; the tokens the fp8 reference puts
    first, at the same positions, do not."""
    import numpy as np

    from bench import common, reference, weights
    from bench.drivers import serve_open_loop as drv
    bench = cpu_bench.load_benchmark()
    _, config, mix = cpu_bench.cell_files(bench, "serve-ds7b-chat")
    limit = mix["limits"]["logit_gap"]
    m = common.arch(config).dims(config)
    worst = {"fp32": 0.0, "fp8": 0.0}
    for seed in (3, 4):
        server = drv.Server(config, mix, seed)
        server.warm(seed)
        out = drv.serve(server, mix, seed, 1.0)
        picked = drv.sample(list(out["reqs"].values()), seed,
                            mix["check_tokens"])
        server.free()
        w = weights.make_all(config, common.seed_key(seed, 1))
        for r in picked:
            for prec in worst:
                g = reference.token_gaps(
                    w, np.asarray(r.prompt), np.asarray(r.out), m,
                    mix["ctx_max"], mix["output"]["max"], prec)
                worst[prec] = max(worst[prec], float(np.max(g)))
    assert worst["fp32"] <= limit < worst["fp8"], worst
