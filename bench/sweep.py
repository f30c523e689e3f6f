"""Find the highest arrival rate a serving cell sustains: one set-up, then
one window per rate, in one process.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> --rates 1,2,3

For each rate it prints the requests due and finished, the time to first
token (median and p90) of the first and the last third of the window,
and the p99 gap between tokens. A backlog that grows through the window
shows as a last third far slower than the first. The cell's rate in its
traffic file is then set at about four fifths of the highest sustained
rate.
"""

from __future__ import annotations

import argparse
import copy
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), os.path.join(os.path.dirname(HERE), "src")]

from bench import run as R  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--rates", required=True)
    args = p.parse_args(argv)
    rates = [float(r) for r in args.rates.split(",")]

    ctx = R.Context(args.workload, args.seed, args.seconds, False)
    R.require_chip(ctx.cell["chips"])
    R.cache_on()
    from bench.loops import serve_open_loop as S
    from bench.lib import program, traffic

    model = ctx.config["model"]
    server = program.build_server(ctx.config, ctx.mix["serve"], args.seed)
    widest = copy.deepcopy(ctx.mix)
    widest["arrivals"]["rate_per_s"] = max(rates)
    prefixes, plan = traffic.schedule(widest, model["vocab_size"], args.seed, args.seconds)
    for pre in prefixes:
        server.register_prefix(pre)
    S.drive(server, [server.submit(pr, n) for pr, n in S.warm_plan(
        plan, prefixes, ctx.mix["serve"]["slots"], model["vocab_size"], args.seed)])
    for rate in rates:
        mix = copy.deepcopy(ctx.mix)
        mix["arrivals"]["rate_per_s"] = rate
        _, plan = traffic.schedule(mix, model["vocab_size"], args.seed, args.seconds)
        gc.collect()
        tracked, t0 = S.window(server, plan, program.reference(ctx.config), model, args.seconds, [])
        lat = S.latencies(tracked, t0, args.seconds)
        third = max(1, len(tracked) // 3)
        first, last = lat["ttft_s"][:third], lat["ttft_s"][-third:]
        row = {
            "rate_per_s": rate, "due": len(tracked),
            "finished": sum(it.req.done for it in tracked),
            "ttft_p50_ms": S.percentile(lat["ttft_s"], 50) * 1e3,
            "ttft_p90_ms": S.percentile(lat["ttft_s"], 90) * 1e3,
            "ttft_p50_first_third_ms": S.percentile(first, 50) * 1e3,
            "ttft_p50_last_third_ms": S.percentile(last, 50) * 1e3,
            "itl_p50_ms": S.percentile(lat["itl_s"], 50) * 1e3,
            "itl_p99_ms": S.percentile(lat["itl_s"], 99) * 1e3,
            "lateness_s": max((it.late for it in tracked), default=0.0),
        }
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
