"""Find a cell's knee: serve its mix open loop at several fixed rates in
one process (one set-up) and print, per rate, attainment, tails, tokens
per second and whether the backlog grew. A tool for whoever defines or
re-defines a cell; the benchmark's runs do not call it.

    python3 bench/sweep.py --workload <cell> --rates 0.5,1,2,3 \
        --seconds 30 [--seeds 1,2] [--derive-limits 4,2,3]

Each rate is served once for each seed. ``--derive-limits a,b,m`` sets the tiers from the first (lowest) rate's
unloaded numbers before the other rates run: interactive time to first
token = a x its p95, time per output token = b x its p95, and the other
tiers m x those.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))


def summary(res):
    from bench import harness
    from repro.serving.session import HandleState
    win = harness.window_requests(res)
    ttft, tpot = harness.ttft_values(res), harness.tpot_values(res)
    open_at_close = sum(1 for r in res.reqs
                        if r.submitted is not None
                        and r.submitted <= res.close
                        and (r.first is None or r.last is None
                             or r.handle is None
                             or r.handle.state is not HandleState.DONE
                             or r.last > res.close))
    half = res.open + (res.close - res.open) / 2

    def att(rs):
        return (100.0 * sum(harness.met_deadline(res, r) for r in rs)
                / len(rs)) if rs else None
    toks = sum(1 for r in res.reqs for t in r.token_times
               if res.open <= t < res.close)
    return {"requests": len(win),
            "attainment": att(win),
            "attainment_1st_half": att([r for r in win
                                        if res.t0 + r.arrival.due < half]),
            "attainment_2nd_half": att([r for r in win
                                        if res.t0 + r.arrival.due >= half]),
            "ttft_p50_ms": 1e3 * harness.percentile(ttft, 50),
            "ttft_p95_ms": 1e3 * harness.percentile(ttft, 95),
            "tpot_p50_ms": 1e3 * (harness.percentile(tpot, 50) or 0),
            "tpot_p95_ms": 1e3 * (harness.percentile(tpot, 95) or 0),
            "output_tok_per_s": toks / (res.close - res.open),
            "in_flight_at_close": open_at_close,
            "drain_s": res.end - res.close,
            "retraces": res.retraces}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--derive-limits", default=None)
    ap.add_argument("--stop-below", type=float, default=None,
                    help="stop after a rate whose attainment (%%) is "
                         "below this")
    args = ap.parse_args()
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from bench import harness, layout
    from bench import traffic as T
    from bench.run import require_chips, setup_compile_cache
    spec = layout.cell(args.workload)
    require_chips(jax, spec["entry"]["chips"])
    setup_compile_cache(jax)
    mix, cell = spec["mix"], dict(spec["cell"])
    seeds = [int(s) for s in args.seeds.split(",")]
    t = time.perf_counter()
    prog = harness.build_program(spec["config"], mix, seeds[0])
    harness.warm_up(prog, mix, seeds[0])
    print(json.dumps({"setup_s": time.perf_counter() - t}), flush=True)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        cell["rate_per_s"] = rate
        rows = []
        for seed in seeds:
            sched = T.schedule(mix, cell, seed, args.seconds)
            res = harness.serve_window(prog, sched, mix, args.seconds, seed,
                                       finish=True)
            rows.append({"rate": rate, "seed": seed, "tiers": cell["tiers"],
                         **summary(res)})
            print(json.dumps(rows[-1]), flush=True)
        att = [r["attainment"] for r in rows if r["attainment"] is not None]
        if args.stop_below is not None and att \
                and sum(att) / len(att) < args.stop_below:
            break
        if i == 0 and args.derive_limits:
            a, b, m = (float(x) for x in args.derive_limits.split(","))
            ttft = max(r["ttft_p95_ms"] for r in rows)
            tpot = max(r["tpot_p95_ms"] for r in rows)
            tiers = []
            for k, tier in enumerate(cell["tiers"]):
                f = 1.0 if k == 0 else m
                tiers.append({**tier, "ttft_ms": round(a * ttft * f),
                              "tpot_ms": round(b * tpot * f, 1)})
            cell["tiers"] = tiers
            print(json.dumps({"derived_tiers": tiers}), flush=True)


if __name__ == "__main__":
    main()
