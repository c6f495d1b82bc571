"""Find a serving cell's knee: the highest arrival rate it sustains.

    python3 bench/sweep.py --workload <serving cell> --rates 2,4,6 [--seconds 15]

One process builds and warms the cell's server once, then offers each
rate in turn as an open loop (the cell's traffic mix with only the rate
changed) and prints, per rate, the requests completed per second, the
time-to-first-token tail and the backlog left at the window's close.  A
rate is sustained when the backlog stays near the slot count and the
tail does not grow with the window.  The cell's rate is then set in its
mix file by hand, from these lines; no run of the benchmark searches."""
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

from bench import common  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--drain-s", type=float, default=60.0)
    args = ap.parse_args(argv)
    bench = common.load_benchmark()
    cell, config, mix = common.cell_files(bench, args.workload)
    devs = common.claim_devices(cell["chips"])
    common.enable_cache()
    drv = common.driver(mix["driver"])
    server = drv.Server(config, mix, args.seed)
    server.warm(args.seed)
    for rate in [float(r) for r in args.rates.split(",")]:
        m = dict(mix, rate_per_s=rate, judge="tails", lead_s=3.0,
                 follow_s=0.0)
        out = drv.serve(server, m, args.seed, args.seconds)
        t0, t1 = out["window"]
        reqs = out["reqs"].values()
        done = [r for r in reqs if r.done and t0 <= r.t_done <= t1]
        due = [r for r in reqs if t0 <= r.t_submit < t1]
        first = [r.t_first - r.t_submit for r in due if r.t_first]
        backlog = sum(1 for r in reqs if r.t_submit <= t1 and not r.done)
        line = {"rate_per_s": rate, "due": len(due),
                "completed_per_s": len(done) / (t1 - t0),
                "tokens_per_s": out["tokens"] / (t1 - t0),
                "ttft_p50_ms": 1e3 * common.nearest_rank(first, 0.5)
                if first else None,
                "ttft_p90_ms": 1e3 * common.nearest_rank(first, 0.9)
                if first else None,
                "backlog_at_close": backlog, "slots": mix["slots"],
                "late_s": out["late_s"]}
        print(json.dumps(line), flush=True)
        # drain what is left before the next rate
        t_end = time.monotonic() + args.drain_s
        while not server.sched.idle and time.monotonic() < t_end:
            server.sched.step()
        server.sched.finished.clear()
        if not server.sched.idle:
            print(json.dumps({"stopped": f"backlog not drained in "
                              f"{args.drain_s}s"}), flush=True)
            break
    print(json.dumps({"device": common.device_info(devs)}))


if __name__ == "__main__":
    main()
