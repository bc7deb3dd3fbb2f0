"""Run one cell of the on-chip benchmark and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Run from the root of a checkout on a machine with a TPU. With no TPU, or
fewer chips than the cell asks for, it exits non-zero and prints no
result. The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
a ``breakdown``, and last ``checks``: each number compared beside its
limit, which also end standard error.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from types import SimpleNamespace  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

TRACE_DIR = ROOT / ".bench_traces"


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def require_chips(jax, chips: int):
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise SystemExit(
            f"bench/run.py needs {chips} TPU chip(s); JAX found "
            f"{len(devs)} {devs[0].platform} device(s) "
            f"({devs[0].device_kind}). There is no CPU fallback.")
    return devs


def setup_compile_cache(jax):
    """The program's persistent compilation cache (``.jax_cache`` in the
    checkout, unless ``JAX_COMPILATION_CACHE_DIR`` names one), keeping
    every program however quickly it compiled or small it is."""
    from repro.compile_cache import setup_compile_cache as program_cache
    path = program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class Tracer:
    """Starts the profiler a few seconds before the window opens and
    stops it when the window closes; the window itself is the
    ``bench.slice`` host span that the trace reduction measures."""

    def __init__(self, jax):
        self.jax = jax
        self.start = self.stop = None
        self._ann = None

    def __call__(self, event):
        jax = self.jax
        if event == "lead":
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
        elif event == "open":
            self._ann = jax.profiler.TraceAnnotation("bench.slice")
            self._ann.__enter__()
            self.start = time.perf_counter()
        elif event == "close" and self._ann is not None:
            self._ann.__exit__(None, None, None)
            self.stop = time.perf_counter()
            jax.profiler.stop_trace()

    def reduce(self):
        from bench.trace import read_xplane
        files = sorted(TRACE_DIR.glob("plugins/profile/*/*.xplane.pb"))
        if not files:
            return None
        try:
            return read_xplane(str(files[-1]))
        finally:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)


def run(spec: dict, name: str, seed: int, seconds: float, trace: bool,
        jax, devs) -> dict:
    """Serve one window of cell ``name`` and check it; returns the result
    object."""
    from bench import correctness, harness, layout
    from bench import traffic as T

    config, mix, cell = spec["config"], spec["mix"], spec["cell"]
    prog = harness.build_program(config, mix, seed)
    warm = harness.warm_up(prog, mix, seed)
    log(f"set-up: {warm} programs traced in warm-up")
    sched = T.schedule(mix, cell, seed, seconds)
    tracer = Tracer(jax) if trace else None
    setup_s = time.perf_counter() - T_START
    res = harness.serve_window(prog, sched, mix, seconds, seed, tracer)
    if tracer is not None:
        res.trace_bounds = (tracer.start, tracer.stop)
    stats = devs[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    reduced = tracer.reduce() if tracer is not None else None
    lat = res.lateness
    log(f"window: {len(harness.window_requests(res))} requests due; "
        f"generator lateness p50 {1e3 * harness.percentile(lat, 50):.3f} "
        f"ms, p99 {1e3 * harness.percentile(lat, 99):.3f} ms, max "
        f"{1e3 * max(lat):.3f} ms; drained "
        f"{res.end - res.close:.2f} s past the close")
    if res.retraces:
        log(f"WARNING: {res.retraces} program(s) traced inside the window")

    # what a metric reader reads
    ctx = SimpleNamespace(res=res, dims=config,
                          peak=layout.peaks(devs[0].device_kind),
                          reduced=reduced, setup_s=setup_s)
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in layout.metrics_for(name, kind):
        v = layout.reader(m["name"]).read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    from repro.serving.session import HandleState
    win = harness.window_requests(res)
    bad = {HandleState(s) for s in ("failed", "shed", "rejected")}
    failed = sum(1 for r in win if r.handle is not None
                 and r.handle.state in bad)
    samples = correctness.draw_sample(
        harness.finished(res, seed, prog.cfg.vocab_size), seed, cell)
    # free the served program before the reference takes the chip
    for r in res.reqs:
        r.handle = r.request = None
    del prog
    gc.collect()
    jax.clear_caches()
    gc.collect()
    t_ref = time.perf_counter()
    gap = correctness.served_gap(config, seed, samples)
    correct, checks = correctness.judge(gap, samples, cell)
    log(f"reference: {len(samples)} requests "
        f"({checks['merged_requests_compared']['value']} merged), "
        f"{checks['tokens_compared']['value']} served tokens compared in "
        f"{time.perf_counter() - t_ref:.1f} s")
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    out = {"correct": correct, "attempted": len(win), "failed": failed,
           "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        out["breakdown"] = reduced.breakdown()
    out["checks"] = checks
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        raise SystemExit("--seed must be a whole number >= 0")

    from bench import layout
    spec = layout.cell(args.workload)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else under /tmp
    import jax
    devs = require_chips(jax, spec["entry"]["chips"])
    log(f"device: {devs[0].platform} {devs[0].device_kind} x{len(devs)}; "
        f"compile cache {setup_compile_cache(jax)}")
    out = run(spec, args.workload, args.seed, args.seconds,
              bool(args.trace), jax, devs)
    for k, v in out["checks"].items():
        log(f"check {k}: {v['value']} (limit {v['limit']})")
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
