"""Serving traffic: an open loop of requests into ``PagedScheduler``
(bucketed prefill, chunked decode through the Pallas paged kernel),
driven round by round with ``step()``.

Requests arrive on a schedule fixed before the run (Poisson gaps at the
mix's rate) whether or not the server keeps up; each is timed from the
moment it was due, so a stall counts against every request behind it.
Every seed gets the same prompt lengths, output lengths and gaps, in the
same order, drawn once from the mix's own ``shape_seed``; the seed draws
only the tokens (and the weights), so the work of a run is fixed.

Two kinds of window, named by the mix's ``judge``:
  * "tails": the window opens ``lead_s`` after the first arrival (set to
    at least a request's lifetime, so the server is as full as it stays);
    a traced run opens its shorter window at the same point; every
    request due inside it is followed to completion (at most
    ``follow_s`` after the window; one not done by then has failed); the
    tails (at the mix's ``tail`` quantile, by nearest rank) of time to
    first token and time per output token are over all of them.
  * "throughput": the window opens once every slot is busy and closes
    after the window's seconds; output tokens made inside it over its
    length.  Requests still queued or running at the close are neither
    done nor failed.

``correct``: once the window has closed and the server is freed, a
sample of finished requests drawn from the seed, with the longest among
them, is run through the plain reference over prompt and served tokens;
the widest gap by which a served token's reference logit lies below the
reference's best must stay within the mix's limit (greedy decoding)."""
from __future__ import annotations

import gc
import os
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from bench import common, reference, trace, weights

TRACE_SECONDS = 4.0
# threads that compile the pool splices at set-up; each holds a copy of
# the pool while it runs
WARM_THREADS = 4


def smoke(mix):
    """The CPU rehearsal's cut of the mix: a few slots and short
    requests, the rates scaled to the CPU.  ``logit_gap`` was read at
    this size on the CPU (program against the fp32 reference, and the
    fp8 control): bf16 rounding weighs more at smoke widths than at the
    cell's, so the cell's own limit would not fit."""
    return dict(mix, slots=4, ctx_max=128, rate_per_s=20.0, lead_s=0.5,
                follow_s=30.0, check_tokens=20,
                prompt={"median": 24, "sigma": 0.5, "min": 8, "max": 64},
                output={"median": 8, "sigma": 0.5, "min": 4, "max": 32},
                limits={"logit_gap": 0.02})


def lengths(rng, n, spec):
    """Lognormal lengths with the given median, clipped to [lo, hi]."""
    x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.round(x), spec["min"], spec["max"]).astype(int)


def schedule(mix, seed, vocab, seconds):
    """[(due offset s, prompt, max_new)], in order of arrival."""
    horizon = mix["lead_s"] + seconds + mix["follow_s"]
    n = int(np.ceil(mix["rate_per_s"] * horizon * 1.2)) + 16
    fixed = np.random.default_rng(mix["shape_seed"])
    S = lengths(fixed, n, mix["prompt"])
    O = lengths(fixed, n, mix["output"])
    due = np.cumsum(fixed.exponential(1.0 / mix["rate_per_s"], n))
    rng = np.random.default_rng(seed)
    return [(float(due[i]), rng.integers(0, vocab, int(S[i]),
                                         dtype=np.int32), int(O[i]))
            for i in range(n)]


class Server:
    """The program under test: one scheduler with its weights, warmed for
    every shape the traffic can reach."""

    def __init__(self, config, mix, seed):
        import jax
        from repro.models import model_defs
        from repro.models.param import abstract
        from repro.models.runtime import Runtime
        from repro.serving.paged_cache import n_blocks_for
        from repro.serving.scheduler import PagedScheduler
        arch = common.arch(config)
        self.mix, self.m = mix, arch.dims(config)
        self.cfg = arch.program_config(config)
        flat = weights.make_all(config, common.seed_key(seed, 1))
        params = weights.to_program_tree(flat, abstract(model_defs(
            self.cfg)))
        bs, ctx = mix["block_size"], mix["ctx_max"]
        # the serve launcher's runtime and pool: enough blocks for every
        # slot at ctx_max, so no request is ever preempted
        self.sched = PagedScheduler(
            self.cfg, params, Runtime(mesh=None, remat=False),
            n_slots=mix["slots"], block_size=bs,
            n_blocks=1 + mix["slots"] * n_blocks_for(ctx, bs), ctx_max=ctx,
            decode_chunk=mix["decode_chunk"])
        del params
        self.record = None          # counters of a traced window
        self._wrap()

    def _wrap(self):
        """Spans round the scheduler's calls, and the counters the
        per-layer readers need, taken in the benchmark's own code."""
        import jax
        s = self.sched
        prefill_group, decode, admit = s._prefill_group, s.decode, s.admit

        def _prefill_group(bucket, group):
            if self.record is not None:
                self.record["prefill_real"] += sum(len(g[1].prompt)
                                                   for g in group)
                self.record["prefill_rows"] += s.n_slots * bucket
            with jax.profiler.TraceAnnotation("bench.prefill"):
                return prefill_group(bucket, group)

        def _decode():
            if self.record is not None:
                for r in s.slots:
                    if r is None:
                        continue
                    pos = len(r.prompt) + r.n_generated - r.n_folded - 1
                    take = min(s.decode_chunk, r.max_new - r.n_generated)
                    self.record["keys"] += [pos + 1 + i for i in range(take)]
            with jax.profiler.TraceAnnotation("bench.decode"):
                return decode()

        def _admit():
            with jax.profiler.TraceAnnotation("bench.admit"):
                return admit()
        s._prefill_group, s.decode, s.admit = _prefill_group, _decode, _admit

    def warm(self, seed):
        """Compile every program the traffic can reach: a prefill per
        bucket, the decode chunk, the host-side updates, and the splice
        of each prompt length's blocks into the pool."""
        import jax
        import jax.numpy as jnp
        from repro.serving.engine import cache_abstract
        from repro.serving.paged_cache import n_blocks_for, splice_prefill
        from repro.serving.scheduler import ServeRequest
        s, mix = self.sched, self.mix
        lens = range(mix["prompt"]["min"], mix["prompt"]["max"] + 1)
        buckets = sorted({s._bucket(n) for n in lens})
        rng = np.random.default_rng(seed)
        for i, b in enumerate(buckets):
            n = min(b, max(lens))
            s.submit(ServeRequest(rid=-1 - i, max_new=2 * s.decode_chunk,
                                  prompt=rng.integers(0, self.m["vocab"], n,
                                                      dtype=np.int32)))
            s.run()
        pairs = {(s._bucket(n), n_blocks_for(n, s.block_size)) for n in lens}
        for b in buckets:
            dense = jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype),
                                 cache_abstract(self.cfg, s.n_slots, b))

            def splice(nb):
                # into scratch block 0, and the result dropped: the pool
                # is untouched; only the programs are compiled
                jax.block_until_ready(splice_prefill(s.paged, dense, 0, 0,
                                                     [0] * nb))
            # the small programs of one bucket compile side by side
            with ThreadPoolExecutor(WARM_THREADS) as ex:
                list(ex.map(splice, sorted(nb for bb, nb in pairs
                                           if bb == b)))
            del dense
        s.finished.clear()
        jax.block_until_ready(s.paged)

    def free(self):
        import jax
        for x in jax.tree.leaves((self.sched.params, self.sched.paged)):
            x.delete()
        self.sched = None


def serve(server, mix, seed, seconds, traced=None):
    """Run the open loop; returns what the window saw.  ``traced`` is a
    context entered for the window (a profiler trace)."""
    from repro.serving.scheduler import ServeRequest
    s = server.sched
    plan = schedule(mix, seed, server.m["vocab"], seconds)
    t0 = common.now()
    queue = deque((t0 + d, p, o, i) for i, (d, p, o) in enumerate(plan))
    reqs, late = {}, 0.0
    tails = mix["judge"] == "tails"
    win = None
    closed = None            # what the window saw, once it has closed
    # host stalls while the window is open, for the log: the longest
    # scheduler round, and the collector's pauses
    stall = {"open": False, "round": 0.0, "gc": [], "gc_t": None}

    def on_gc(phase, info):
        if not stall["open"]:
            return
        if phase == "start":
            stall["gc_t"] = common.now()
        elif stall["gc_t"] is not None:
            stall["gc"].append((info["generation"],
                                common.now() - stall["gc_t"]))
    gc.callbacks.append(on_gc)
    try:
        while True:
            t = common.now()
            while queue and queue[0][0] <= t:
                due, p, o, i = queue.popleft()
                late = max(late, t - due)
                reqs[i] = ServeRequest(rid=i, prompt=p, max_new=o,
                                       t_submit=due)
                s.submit(reqs[i])
            if win is None and (t >= t0 + mix["lead_s"] or (
                    not tails and all(r is not None for r in s.slots))):
                win = (t, t + seconds)
                stall["open"] = True
                if traced is not None:
                    traced.__enter__()
                    server.record = {"prefill_real": 0, "prefill_rows": 0,
                                     "keys": []}
                at_open = sum(len(r.out) for r in reqs.values())
            if win is not None and closed is None and (
                    t >= win[1] or (s.idle and not queue)):
                if traced is not None:
                    traced.__exit__(None, None, None)
                stall["open"] = False
                closed = {"window": (win[0], t), "record": server.record,
                          "tokens": sum(len(r.out) for r in reqs.values())
                          - at_open,
                          "due": [i for i, r in reqs.items()
                                  if win[0] <= r.t_submit < win[1]]}
                server.record = None
            if closed is not None:
                # follow the window's requests (tails), or wait for a few
                # to finish to have served tokens to check (throughput)
                waiting = [i for i in closed["due"] if not reqs[i].done] \
                    if tails else \
                    max(0, mix["check_requests"] - sum(r.done for r in
                                                       reqs.values()))
                if not waiting or t > closed["window"][1] + mix["follow_s"]:
                    break
            if s.idle:
                if queue:
                    time.sleep(max(0.0, min(queue[0][0] - common.now(),
                                            0.01)))
                continue
            s.step()
            if stall["open"]:
                stall["round"] = max(stall["round"], common.now() - t)
    finally:
        gc.callbacks.remove(on_gc)
    closed.update(reqs=reqs, late_s=late, stall=stall)
    return closed


def sample(reqs, seed, target_tokens):
    """Finished requests drawn from the seed, the longest first, until
    they hold ``target_tokens`` served tokens."""
    done = [r for r in reqs if r.done]
    if not done:
        return []
    done.sort(key=lambda r: (len(r.prompt) + len(r.out), r.rid))
    out = [done.pop()]
    rng = np.random.default_rng(seed)
    rng.shuffle(done)
    while done and sum(len(r.out) for r in out) < target_tokens:
        out.append(done.pop())
    return out


def gaps(config, mix, seed, picked):
    """Widest gap over the picked requests' served tokens."""
    w = weights.make_all(config, common.seed_key(seed, 1))
    m = common.arch(config).dims(config)
    n_max = mix["output"]["max"]
    worst = 0.0
    for prompt, out in picked:
        g = reference.token_gaps(w, np.asarray(prompt), np.asarray(out), m,
                                 mix["ctx_max"], n_max)
        worst = max(worst, float(np.max(g)))
    del w
    return worst


def run(ctx):
    import jax
    args, config, mix = ctx["args"], ctx["config"], ctx["mix"]
    devs = ctx["devs"]
    server = Server(config, mix, args.seed)
    t_built = common.now()
    server.warm(args.seed)
    n_c, secs_c, hits = ctx["clock"].snapshot()
    common.log(f"[setup] server built at {t_built - ctx['t_start']:.1f}s, "
               f"warm at {common.now() - ctx['t_start']:.1f}s: {n_c} "
               f"compiles ({hits} from the cache) took {secs_c:.1f}s")
    compiles0 = ctx["clock"].snapshot()[0]
    metrics, breakdown, device = {}, None, common.device_info(devs)
    seconds = min(args.seconds, TRACE_SECONDS) if args.trace else \
        args.seconds
    tdir = os.path.join(common.OUT_DIR, "traces",
                        f"{args.workload}-{args.seed}")
    out = serve(server, mix, args.seed, seconds,
                trace.traced(tdir) if args.trace else None)
    t_open, t_close = out["window"]
    reqs = out["reqs"]
    setup_s = t_open - ctx["t_start"]
    inside = ctx["clock"].snapshot()[0] - compiles0
    if mix["judge"] == "tails":
        followed = [reqs[i] for i in out["due"]]
        done = [r for r in followed if r.done]
        attempted, failed = len(followed), len(followed) - len(done)
        ttft = [r.t_first - r.t_submit for r in done]
        tpot = [(r.t_done - r.t_first) / (len(r.out) - 1) for r in done]
        q = mix["tail"]
        if done:
            top = sorted(ttft)[-12:]
            common.log(
                f"ttft ms p50 {1e3 * common.nearest_rank(ttft, 0.5):.1f} "
                f"max {1e3 * top[-1]:.1f}, the 12 longest "
                f"{[round(1e3 * x) for x in top]}; tpot ms p50 "
                f"{1e3 * common.nearest_rank(tpot, 0.5):.2f} max "
                f"{1e3 * max(tpot):.2f}")
        st = out["stall"]
        common.log(f"host: longest round {1e3 * st['round']:.1f} ms; "
                   f"{len(st['gc'])} collections in the window, longest "
                   f"{1e3 * max([0.0] + [d for _, d in st['gc']]):.1f} ms, "
                   f"{sum(g == 2 for g, _ in st['gc'])} of generation 2")
        if not args.trace and done:
            metrics[f"ttft_p{round(100 * q)}_ms"] = common.metric(
                1e3 * common.nearest_rank(ttft, q), "ms")
            metrics[f"tpot_p{round(100 * q)}_ms"] = common.metric(
                1e3 * common.nearest_rank(tpot, q), "ms")
        pool = done
    else:
        attempted = sum(r.done and t_open <= r.t_done <= t_close
                        for r in reqs.values())
        failed = 0
        if not args.trace:
            metrics["serve_tokens_per_s"] = common.metric(
                out["tokens"] / (t_close - t_open), "tokens/s")
        pool = [r for r in reqs.values() if r.done]
    if not args.trace:
        metrics["setup_s"] = common.metric(setup_s, "s")
    common.log(f"window {t_close - t_open:.3f}s: {out['tokens']} tokens, "
               f"{attempted} requests, {failed} failed, generator late by "
               f"at most {out['late_s'] * 1e3:.1f} ms, {inside} compiles "
               f"inside")
    if args.trace:
        red = trace.reduce(trace.load(tdir), len(devs))
        run_ = {"trace": red, "dims": server.m, "mix": mix,
                "config": config, "kind": devs[0].device_kind,
                "chips": len(devs), "record": out["record"]}
        for m in ctx["per_layer"]:
            v = common.reader(m["name"]).read(run_)
            if v is not None:
                metrics[m["name"]] = common.metric(v, m["unit"])
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        breakdown = trace.breakdown(red)
    device["memory_peak_bytes"] = common.peak_bytes(devs)
    picked = [(r.prompt, r.out) for r in sample(pool, args.seed,
                                                mix["check_tokens"])]
    server.free()
    jax.clear_caches()
    checks = {}
    worst = gaps(config, mix, args.seed, picked) if picked else float("inf")
    ok = common.check("logit_gap", worst, mix["limits"]["logit_gap"],
                      checks) and failed == 0
    common.emit(ok, attempted, failed, metrics, device, checks, breakdown)
