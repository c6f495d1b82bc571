"""Readings that a cell's limits are set from, on the chip at the cell's
own size, many seeds in one process.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 [--seconds 6]

For each seed it prints one JSON line with the numbers the cell compares
for the program as it runs, for the control (the plain reference in the
program's place, computed in fp8, the precision below the bf16 that the
configuration computes in), and for the faults a cell of that kind can
have:

  training  "half_batch": the reference on the first half of each
            batch's rows (a step that leaves half the batch out and
            takes the mean over the rest); a step that returns its state
            unchanged reads 1 in "change_gap" by construction.
  serving   "altered": each sampled request with its first served token
            replaced by the next id (a token altered where it is made).

Each reading is judged against the cell's own limits (the mix's
``limits``), as a run judges it, and printed with ``correct`` beside it.
The process exits non-zero where the program fails, or where the control
or a fault passes, on any seed.  The benchmark's own runs never run
this."""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the TPU runtime's own logs would go to a fixed path under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path[0:0] = [os.path.join(ROOT, "src"), ROOT]

from bench import common, weights  # noqa: E402


def judged(readings, limits):
    """{name: {"correct", "checks"}} for each reading's numbers; the
    control and the faults have to come out not correct."""
    out = {}
    for name, nums in readings.items():
        checks = {}
        ok = all([common.check(k, v, limits[k], checks)
                  for k, v in nums.items()])
        out[name] = {"correct": ok, "checks": checks}
    return out


def sound(judgements):
    """True where the program passes and every control and fault fails."""
    return all(j["correct"] == (name == "program")
               for name, j in judgements.items())


def train(config, mix, seeds, drv):
    ok = True
    for seed in seeds:
        t0 = time.monotonic()
        job = drv.Job(config, mix, seed)
        got = job.first_steps()
        job.free()
        ref = drv.reference_steps(config, mix, seed)
        ctl = drv.reference_steps(config, mix, seed, "fp8")
        half = drv.reference_steps(config, mix, seed,
                                   rows=mix["batch"] // 2)
        # a step that returns its state unchanged moves no weight: it
        # reads 1 in change_gap by construction and needs no run
        res = judged({"program": drv.compare(got, ref),
                      "control_fp8": drv.compare(ctl, ref),
                      "half_batch": drv.compare(half, ref)}, mix["limits"])
        ok &= sound(res)
        print(json.dumps({"seed": seed, **res, "loss": ref["loss"],
                          "seconds": time.monotonic() - t0}), flush=True)
    return ok


def serve(config, mix, seeds, drv, seconds):
    import jax
    import numpy as np
    from repro.models import model_defs
    from repro.models.param import abstract
    from bench import reference
    server = drv.Server(config, mix, seeds[0])
    server.warm(seeds[0])
    m = common.arch(config).dims(config)
    ok = True
    for seed in seeds:
        t0 = time.monotonic()
        for x in jax.tree.leaves(server.sched.params):
            x.delete()
        flat = weights.make_all(config, common.seed_key(seed, 1))
        w = dict(flat)
        server.sched.params = weights.to_program_tree(
            flat, abstract(model_defs(server.cfg)))
        out = drv.serve(server, mix, seed, seconds)
        # drop what is still queued or running: evict every slot (which
        # frees its blocks) and empty the queue
        server.sched.queue.clear()
        while server.sched._preempt_one():
            pass
        server.sched.queue.clear()
        reqs = out["reqs"].values()
        picked = drv.sample([r for r in reqs if r.done], seed,
                            mix["check_tokens"])
        read = {"program": 0.0, "control_fp8": 0.0, "altered": 0.0}
        n_max = mix["output"]["max"]
        for r in picked:
            p, o = np.asarray(r.prompt), np.asarray(r.out)
            alt = o.copy()
            alt[0] = (alt[0] + 1) % m["vocab"]
            for name, served, prec in (("program", o, "fp32"),
                                       ("control_fp8", o, "fp8"),
                                       ("altered", alt, "fp32")):
                g = reference.token_gaps(w, p, served, m, mix["ctx_max"],
                                         n_max, prec)
                read[name] = max(read[name], float(np.max(g)))
        res = judged({k: {"logit_gap": v if picked else float("inf")}
                      for k, v in read.items()}, mix["limits"])
        ok &= sound(res)
        print(json.dumps({"seed": seed, "requests": len(picked),
                          "tokens": sum(len(r.out) for r in picked),
                          **res, "seconds": time.monotonic() - t0}),
              flush=True)
        server.sched.finished.clear()
    return ok


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=6.0)
    args = ap.parse_args(argv)
    bench = common.load_benchmark()
    cell, config, mix = common.cell_files(bench, args.workload)
    devs = common.claim_devices(cell["chips"])
    common.enable_cache()
    drv = common.driver(mix["driver"])
    seeds = [int(s) for s in args.seeds.split(",")]
    if mix["driver"] == "train":
        ok = train(config, mix, seeds, drv)
    else:
        ok = serve(config, mix, seeds, drv, args.seconds)
    print(json.dumps({"device": common.device_info(devs), "sound": ok}))
    if not ok:
        raise SystemExit("control: the program failed, or the control or "
                         "a fault passed, on some seed (see above)")


if __name__ == "__main__":
    main()
