"""Bring-up smoke test: serve full-width llama3.2-1b on one TPU.

Drives the served path once through the launcher's own code
(``repro.launch.serve.main``): ``ServingSession`` -> ``LazyBatching`` ->
``JaxEngine`` -> the Pallas ragged decode-attention kernel, at the
published width of llama3.2-1b (16 layers, d_model 2048, 32 heads with
8 KV heads, head_dim 64, d_ff 8192, vocab 128,256) with random weights
made from ``--seed``. About 12 Poisson requests with prompts of 128, 256
or 512 tokens ask for 32 output tokens each; the arena holds 1024 tokens
per slot, capped at 32 slots. Then it checks what came out:

  * every request ends DONE and no KV slot is live after drain;
  * the engine runs the Pallas decode kernel compiled for the chip
    (``tpu_custom_call`` in the decode megastep), not interpreted and not
    replaced by the XLA attention path;
  * streamed tokens equal the engine's batch ``execute_run`` tokens;
  * served tokens equal an isolated batch-1 generation by a second engine
    built from the same seed on the XLA attention path, apart from
    near-ties (see ``TIE_REL``).

It needs a TPU and exits non-zero without one; there is no CPU fallback.
Wall-clock times it prints are a smoke, not a metric. Its last line is one
JSON object: ``{"ok": true, "device": {"platform", "kind", "count"}}``.

    python chip_smoke.py [--seed 0]
"""
import argparse
import gc
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

ARCH = "llama3.2-1b"
MAX_LEN = 1024
MEM_SLOTS = 32
PROMPT_LENS = "128,256,512"
DECODE_LEN = 32
RATE, DURATION = 12.0, 1.0            # about 12 Poisson arrivals

# The reference is fed the served tokens, so every step is judged. Where
# a served token is not the reference's greedy pick, the difference is a
# tie only when the served token's reference logit z_s is within
# TIE_REL * (|z_top| + |z_s|) of the top logit z_top; anything wider is a
# divergence and fails. Both engines keep f32 weights, and the TPU runs
# f32 matmuls at default precision, which rounds operands to bf16 (8
# significant bits). The two sides are different compiled programs (batch
# buckets; one fused 16-layer scan with the Pallas kernel against
# per-layer dispatches with XLA attention), so their logits agree only to
# about 2^-9 of their size and a gap that small is a tie the arithmetic
# cannot resolve. On a v5e, flips came at gaps of 0.0017-0.0156 with the
# kernel on both sides and 0.0032-0.0156 against this reference, with |z|
# near 4: at most 2^-9 of |z_top| + |z_s|, and the bound leaves twice
# that. A token picked by a wrong computation falls short by a share of
# the logit spread (top minus mean, printed; median 4.4 there), far above
# the bound.
TIE_REL = 2.0 ** -8

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def fail(msg):
    raise SystemExit(f"chip_smoke FAILED: {msg}")


def check(cond, msg):
    if not cond:
        fail(msg)


class CompileClock:
    """Sums backend compile seconds and persistent-cache hits."""

    def __init__(self, jax):
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == _COMPILE_EVENT:
            self.seconds += secs

    def _event(self, event, **_):
        if event == _CACHE_HIT_EVENT:
            self.cache_hits += 1


def decode_megastep(jax, engine, batch):
    """The deepest-context decode megastep the served run compiled (embed,
    every layer, head), lowered again from shapes at batch ``batch`` and
    compiled: its key, its lowered text and its compiled executable."""
    import jax.numpy as jnp
    keys = [k for k in engine._jit_cache
            if k[0] == "mega" and k[1] == 0 and k[3]]
    check(keys, "the served run compiled no full decode megastep")
    key = max(keys, key=lambda k: k[4])
    fn = engine._jit_cache[key]

    def shapes(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                            tree)

    vec = jax.ShapeDtypeStruct((batch,), jnp.int32)
    lowered = fn.lower(shapes(engine.params), shapes(engine._span_params),
                       shapes(engine.arenas), vec, vec, vec,
                       shapes(engine._offs()))
    return key, lowered.as_text(), lowered.compile()


def head_logits(jax, engine):
    """The engine's head as f32 logits, where the served head returns only
    the greedy pick."""
    import jax.numpy as jnp
    from repro.models import layers as L

    @jax.jit
    def fn(params, x):
        h = L.rms_norm(x, params["final_norm"], engine.cfg.norm_eps)
        return engine.model.unembed(params, h).astype(jnp.float32)

    return fn


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="Serve full-width llama3.2-1b on one TPU and check it.")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the weights, the trace and the prompts")
    args = ap.parse_args(argv)

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs go to /tmp
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"needs a TPU; JAX found platform {dev.platform!r} "
             f"({dev.device_kind}) and this script has no CPU fallback")
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        fail(f"no repro package under {src}: run from a checkout")
    sys.path.insert(0, src)
    from repro.compile_cache import setup_compile_cache
    from repro.configs import get_config
    from repro.launch import serve
    from repro.serving.engine import JaxEngine, reference_generate
    from repro.serving.session import HandleState

    print(f"compile cache: {setup_compile_cache()}", flush=True)
    clock = CompileClock(jax)
    full = get_config(ARCH)
    print(f"model: {full.name} layers={full.num_layers} "
          f"d_model={full.d_model} heads={full.num_heads} "
          f"kv_heads={full.num_kv_heads} head_dim={full.head_dim} "
          f"d_ff={full.d_ff} vocab={full.vocab_size} "
          f"params={full.param_count():,}", flush=True)

    # ---- serve through the launcher ---------------------------------
    serve_argv = ["--engine", "jax", "--arch", ARCH, "--policy", "lazyb",
                  "--hw", "v5e", "--full-width",
                  "--max-len", str(MAX_LEN), "--mem-slots", str(MEM_SLOTS),
                  "--prompt-lens", PROMPT_LENS,
                  "--decode-lens", str(DECODE_LEN),
                  "--rate", str(RATE), "--duration", str(DURATION),
                  "--seed", str(args.seed), "--assert-no-leak"]
    print("launcher: python -m repro.launch.serve " + " ".join(serve_argv))
    print("(the launcher's latency line below is wall-clock: smoke, not a "
          "metric)", flush=True)
    t0 = time.perf_counter()
    session = serve.main(serve_argv)
    serve_s = time.perf_counter() - t0
    engine = session.backend
    cfg = engine.cfg
    n_params = sum(leaf.size for leaf in jax.tree.leaves(engine.params))
    print(f"engine: dtype={engine.model.flags.dtype.__name__} "
          f"max_len={engine.max_len} slot cap={engine.max_slots} "
          f"pallas_decode={engine.model.flags.pallas_decode} "
          f"params allocated={n_params:,}")
    check(cfg == full, "the launcher served a reduced or altered config")
    check(n_params == full.param_count(),
          f"allocated {n_params:,} params, config says "
          f"{full.param_count():,}")
    check(engine.max_len == MAX_LEN and engine.max_slots == MEM_SLOTS,
          "engine arena is not the requested size")

    handles = list(session.handles.values())
    n_done = sum(h.state is HandleState.DONE for h in handles)
    live = engine.memory_stats().slots_live
    print(f"requests: {n_done}/{len(handles)} DONE, slots live after "
          f"drain={live}")
    check(handles, "the trace held no request")
    check(n_done == len(handles), "some request did not end DONE")
    check(live == 0, f"{live} KV slot(s) live after drain")

    check(engine.model.flags.pallas_decode,
          "the engine turned the Pallas decode kernel off")
    batch = 1 << (len(handles) - 1).bit_length()     # the served bucket
    key, hlo, compiled = decode_megastep(jax, engine, batch)
    has_kernel = "tpu_custom_call" in hlo
    mem = compiled.memory_analysis()
    print(f"decode megastep {key[1:]} at batch {batch}: tpu_custom_call "
          f"{'present' if has_kernel else 'MISSING'}; compiled for this "
          f"chip: argument_bytes={mem.argument_size_in_bytes} "
          f"temp_bytes={mem.temp_size_in_bytes} "
          f"alias_bytes={mem.alias_size_in_bytes}")
    check(has_kernel, "the decode megastep does not call the compiled "
                      "Pallas kernel")

    served = {}
    for h in handles:
        r = h.request
        st = engine.states[r.rid]
        got = list(st.generated[:r.decode_len])
        check(len(got) == r.decode_len == DECODE_LEN,
              f"rid={r.rid}: {len(got)} tokens for decode_len "
              f"{r.decode_len}")
        check(h.tokens[:r.decode_len] == got,
              f"rid={r.rid}: streamed tokens differ from execute_run's")
        served[r.rid] = (st.prompt_np.copy(), got)
    wl = handles[0].request.workload
    print(f"streamed tokens equal execute_run tokens for all "
          f"{len(served)} requests")
    stats = dev.memory_stats() or {}
    print(f"arena slots allocated={engine.n_slots} (grows={engine.n_grows}); "
          f"after drain bytes_in_use={stats.get('bytes_in_use')}; since "
          f"start peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
          f"(bytes_limit={stats.get('bytes_limit')})")
    print(f"serve: {serve_s:.1f}s wall including compiles (smoke, not a "
          f"metric); backend compile {clock.seconds:.1f}s, persistent "
          f"cache hits {clock.cache_hits}", flush=True)

    # ---- isolated reference from a second engine, same seed ----------
    # free the served engine first: two full-width f32 engines do not fit
    del session, engine, handles, h, r, st, compiled
    gc.collect()
    stats = dev.memory_stats() or {}
    print(f"bytes_in_use after freeing the served engine="
          f"{stats.get('bytes_in_use')}", flush=True)
    t0 = time.perf_counter()
    # XLA attention, not the kernel: the reference is a witness the
    # compiled kernel does not share
    ref = JaxEngine(cfg, max_len=MAX_LEN, seed=args.seed, n_slots=1,
                    pallas=False)
    stats = dev.memory_stats() or {}
    print(f"reference engine built: bytes_in_use={stats.get('bytes_in_use')} "
          f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}", flush=True)
    logits_fn = head_logits(jax, ref)
    ties, worst, diverged, identical, spreads = 0, 0.0, [], 0, []
    for rid, (prompt, got) in served.items():
        rows = []
        reference_generate(
            ref, wl, prompt, len(got), forced=got,
            on_head=lambda x: rows.append(np.asarray(logits_fn(ref.params,
                                                               x))[0]))
        identical += all(z[tok] == z.max() for tok, z in zip(got, rows))
        for i, (tok, z) in enumerate(zip(got, rows)):
            z1 = float(z.max())
            spreads.append(z1 - float(z.mean()))
            deficit = z1 - float(z[tok])
            if deficit == 0.0:
                continue
            z2 = float(np.partition(z, -2)[-2])
            tol = TIE_REL * (abs(z1) + abs(float(z[tok])))
            tie = deficit < tol
            worst = max(worst, deficit / tol)
            print(f"  rid={rid} prompt={len(prompt)} step {i}: served "
                  f"{tok}, reference pick {int(z.argmax())}; served token "
                  f"{deficit:.6g} below the reference's top (top-2 margin "
                  f"{z1 - z2:.6g}) vs tolerance {tol:.6g} -> "
                  f"{'tie' if tie else 'DIVERGED'}")
            if tie:
                ties += 1
            else:
                diverged.append((rid, i))
    n_steps = sum(len(got) for _, got in served.values())
    print(f"reference (XLA attention, fed the served tokens): "
          f"{identical}/{len(served)} requests identical; {n_steps} steps "
          f"checked, ties={ties}, diverged={len(diverged)}, largest "
          f"deficit/tolerance {worst:.3g}; median logit spread (top minus "
          f"mean) {float(np.median(spreads)):.6g} "
          f"({time.perf_counter() - t0:.1f}s wall, smoke)")
    check(not diverged, f"steps (rid, step) {diverged} diverged from the "
                        f"isolated reference beyond a tie")
    stats = dev.memory_stats() or {}
    print(f"backend compile total {clock.seconds:.1f}s; "
          f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}")
    print(json.dumps({"ok": True,
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(devices)}}))


if __name__ == "__main__":
    main()
