"""Readings that set a cell's correctness limit: for each seed, serve a
window of the cell at its own load, then judge the served tokens and the
fp8 control's picks at the same positions by the check that decides
``correct`` (``correctness.judge``, at the cell's committed limit). The
control has to come out not correct. The benchmark's own runs do not
call it.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 \
        --seconds 20 [--control-seeds 1,2,3]
"""
import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))


def readings(spec, seed, seconds, control, jax):
    """For the served tokens, and for the fp8 control's picks when
    ``control``: (widest gap, correct, checks), as ``run.py`` judges."""
    from bench import correctness, harness
    from bench import traffic as T
    mix, cell, config = spec["mix"], spec["cell"], spec["config"]
    prog = harness.build_program(config, mix, seed)
    harness.warm_up(prog, mix, seed)
    sched = T.schedule(mix, cell, seed, seconds)
    res = harness.serve_window(prog, sched, mix, seconds, seed)
    samples = correctness.draw_sample(
        harness.finished(res, seed, prog.cfg.vocab_size), seed, cell)
    for r in res.reqs:
        r.handle = r.request = None
    del prog, res
    gc.collect()
    jax.clear_caches()
    gc.collect()
    z = correctness.logits(config, seed, samples)
    g = correctness.widest(correctness.gaps(z, [s.served for s in samples]))
    out = {"served": (g, *correctness.judge(g, samples, cell))}
    if control:
        c = correctness.control_gap(config, seed, samples, z)
        out["control"] = (c, *correctness.judge(c, samples, cell))
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args()
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from bench import layout
    from bench.run import require_chips, setup_compile_cache
    spec = layout.cell(args.workload)
    require_chips(jax, spec["entry"]["chips"])
    setup_compile_cache(jax)
    ctl = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        out = readings(spec, seed, args.seconds, seed in ctl, jax)
        row = {"seed": seed, "wall_s": time.perf_counter() - t}
        for side, (gap, correct, checks) in out.items():
            row[side] = {"gap": gap, "correct": correct, "checks": checks}
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
