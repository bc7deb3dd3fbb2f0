"""End-to-end driver: LazyBatching serving a REAL model with batched requests.

Builds a reduced llama-family model, generates a Poisson request trace, and
serves it ONLINE through the ``ServingSession`` front-end: requests are
submitted with streaming callbacks, the LazyBatching scheduler
preempts/merges sub-batches at layer boundaries, and every committed node
run executes actual jitted layer functions on the JAX engine.

Correctness is verified, not assumed:
  * every request's *streamed* tokens (fired from run boundaries) must be
    bit-identical to the engine's batch ``execute_run`` results, and
  * both must match an isolated (no batching, no preemption) reference
    generation of the same prompt — lazy batching must not change results.

  PYTHONPATH=src python examples/serve_real_model.py \
      [--arch llama3.2-1b] [--n 12] [--rate 20]
"""
import argparse

import numpy as np

from repro.configs import get_config
from repro.core.policies import LazyBatching
from repro.core.slack import SlackPredictor
from repro.serving.engine import JaxEngine, reference_generate
from repro.serving.npu_model import NPUPerfModel, TPU_V5E
from repro.serving.session import HandleState, ServingSession
from repro.serving.workload import fixed_length, from_model_config, LengthDist


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--n", type=int, default=12, help="number of requests")
    ap.add_argument("--rate", type=float, default=20.0)
    ap.add_argument("--sla", type=float, default=60.0,
                    help="SLA target (seconds — CPU wall-clock is slow)")
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cfg = get_config(args.arch).reduced()
    rng = np.random.default_rng(args.seed)

    # request population: short prompts, a few decode steps each
    prompt_dist = LengthDist((6, 8, 10, 12), (0.25, 0.25, 0.25, 0.25))
    decode_dist = LengthDist((2, 3, 4, 5), (0.25, 0.25, 0.25, 0.25))
    wl = from_model_config(cfg, prompt_dist=prompt_dist,
                           decode_dist=decode_dist)

    engine = JaxEngine(cfg, max_len=64)
    predictor = SlackPredictor.build([wl], NPUPerfModel(TPU_V5E), args.sla)
    policy = LazyBatching(predictor, max_batch=args.max_batch)
    session = ServingSession(policy, engine, seed=args.seed)

    streamed = {}                       # rid -> tokens seen via on_token

    def on_token(handle, token):
        streamed.setdefault(handle.request.rid, []).append(token)

    handles, prompts = [], {}
    t = 0.0
    for _ in range(args.n):
        t += rng.exponential(1.0 / args.rate)
        r = wl.sample_request(rng, t)
        prompt = rng.integers(2, cfg.vocab_size, size=r.prompt_len)
        prompts[r.rid] = prompt
        handles.append(session.submit(r, prompt_tokens=prompt,
                                      on_token=on_token))
    session.duration = t

    print(f"serving {args.n} requests on reduced {args.arch} "
          f"({cfg.param_count() / 1e6:.1f}M params), "
          f"max_batch={args.max_batch} ...")
    stats = session.drain()

    s = stats.summary()
    print(f"completed {s['completed']}/{args.n}  "
          f"avg latency {s['avg_latency_ms']:.0f}ms (CPU wall-clock)  "
          f"nodes executed {engine.nodes_executed}  "
          f"preemptions {policy.n_preemptions}")
    assert s["completed"] == args.n
    assert all(h.state is HandleState.DONE for h in handles)

    # ---- verify: streamed == batch-executed == isolated reference ------
    print("verifying streamed tokens against batch results and an "
          "isolated (unbatched) reference ...")
    ref_engine = JaxEngine(cfg, max_len=64)     # same seed -> same params
    mismatches = 0
    for h in handles:
        r = h.request
        got = engine.states[r.rid].generated[:r.decode_len]
        assert streamed[r.rid][:r.decode_len] == got == h.tokens[:r.decode_len], \
            f"rid={r.rid}: streamed tokens diverge from batch execute_run"
        ref = reference_generate(ref_engine, wl, prompts[r.rid],
                                 r.decode_len)
        if got != ref:
            mismatches += 1
            print(f"  rid={r.rid}: engine {got} != reference {ref}")
    if mismatches:
        raise SystemExit(f"{mismatches} requests diverged from reference!")
    print(f"all {args.n} generations match the unbatched reference — "
          "lazy batching preserved results exactly.")


if __name__ == "__main__":
    main()
