"""Serving benchmark: flagship decode throughput + prefill latency.

Companion to bench.py (training headline): measures the serving path a
reference user would care about — steady-state decode tokens/s of the
KV-cached generate loop (one on-device scan), and prefill
time-to-first-token latency, on the flagship ~700M decoder. One JSON
line per metric, each naming the device. One process (a chip belongs
to one process at a time); exits non-zero when JAX's default device is
not a TPU.

    chiprun -- python bench_serving.py
    PBST_BENCH_TINY=1 JAX_PLATFORMS=cpu python bench_serving.py  # rehearsal
"""

from __future__ import annotations

import json
import os
import sys
import time


def main() -> int:
    tiny = os.environ.get("PBST_BENCH_TINY", "").lower() in ("1", "true")
    import jax
    import jax.numpy as jnp

    from bench_common import bench_device, metric_name

    from pbs_tpu.models import flagship_config, init_params
    from pbs_tpu.models.generate import init_cache, make_generate, prefill

    device = bench_device(rehearsal=tiny)
    cfg = flagship_config(tiny=tiny)
    batch = 2 if tiny else 8
    prompt_len = 16 if tiny else 512
    new_tokens = 8 if tiny else 128

    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)
    jax.block_until_ready(params)
    prompt = jax.random.randint(
        key, (batch, prompt_len), 0, cfg.vocab, jnp.int32)

    # Prefill latency (the TTFT floor): prompt pass into a fresh cache.
    # Timing is bracketed by a HOST FETCH of an in-graph scalar: a
    # device-to-host read cannot complete before its dependency chain
    # — the same sync bench.py uses. The scalar reduce is fused into
    # the jitted fn so the sync costs one transfer, not an extra
    # dispatch.
    @jax.jit
    def pre(params, toks):
        cache = init_cache(cfg, batch, max_len=prompt_len + new_tokens)
        logits, cache = prefill(cfg, params, toks, cache)
        # prefill returns last-position logits, (B, vocab): the sync
        # still covers the whole prompt pass (logits depend on it) and
        # the reduce is negligible, so the timed value is prefill plus
        # one RTT — matching what each timed gen iteration pays below.
        return jnp.sum(logits.astype(jnp.float32))

    float(pre(params, prompt))  # compile + sync
    ttfts = []
    for _ in range(10):
        t0 = time.perf_counter()
        float(pre(params, prompt))
        ttfts.append((time.perf_counter() - t0) * 1e3)
    ttfts.sort()
    print(json.dumps({
        "metric": metric_name("serving_prefill_ms", device),
        "value": round(ttfts[len(ttfts) // 2], 1),
        "unit": "ms",
        **device,
        "p90_ms": round(ttfts[int(len(ttfts) * 0.9) - 1], 1),
        "batch": batch,
        "prompt_len": prompt_len,
    }), flush=True)

    # Decode throughput: the full generate loop (prefill + on-device
    # scan over new_tokens decode steps), steady state.
    gen_fn = make_generate(cfg, max_new_tokens=new_tokens,
                           temperature=0.0)

    @jax.jit
    def gen(params, prompt, key):
        toks = gen_fn(params, prompt, key)
        # In-graph scalar: the host fetch below is the hard sync (the
        # single device stream executes queued iterations in order, so
        # fetching the last syncs them all).
        return toks, jnp.sum(toks)

    toks, s = gen(params, prompt, key)  # compile
    int(s)
    iters = 2 if tiny else 5
    t0 = time.perf_counter()
    for _ in range(iters):
        toks, s = gen(params, prompt, key)
        # Per-iteration fetch: every iteration pays exactly one RTT,
        # like every timed prefill above, so the prefill subtraction
        # below cancels the sync overhead instead of overcorrecting.
        int(s)
    dt = time.perf_counter() - t0
    total_new = batch * new_tokens * iters
    # Subtract the measured prefill share to isolate decode rate.
    decode_dt = max(dt - iters * ttfts[len(ttfts) // 2] / 1e3, 1e-9)
    print(json.dumps({
        "metric": metric_name("serving_decode_throughput", device),
        "value": round(total_new / decode_dt, 1),
        "unit": "tokens/s",
        **device,
        "per_step_ms": round(1e3 * decode_dt / (new_tokens * iters), 2),
        "batch": batch,
        "new_tokens": new_tokens,
    }), flush=True)

    # Continuous batching engines, plain vs speculative, bf16 vs int8
    # weights (the verdict's serving matrix): tokens/s, engine ticks,
    # and the engine's own TTFT/completion percentiles for the same
    # request mix. Self-draft gives the acceptance CEILING (the draft
    # is free to be wrong in deployment; here the point is engine
    # overhead at high acceptance). int8 target + fp draft is the
    # deployment-shaped pair test_spec_serving pins for exactness.
    from pbs_tpu.models import ContinuousBatcher, SpeculativeBatcher
    from pbs_tpu.models.moe import MoEConfig, init_moe_params, moe_slot_mlp
    from pbs_tpu.models.quant import quantize_weights

    qparams = quantize_weights(params)
    jax.block_until_ready(qparams)
    # MoE serving rows (the matrix's second model family): flagship
    # attention dims with E=4 experts sized so total params match the
    # dense flagship (~700M; active/token comparable), routed
    # PROVABLY dropless (MoEConfig.dropless) — the mode engine
    # parity and speculative verification require.
    import dataclasses as _dc

    mcfg = MoEConfig(
        **{**_dc.asdict(cfg), "d_ff": cfg.d_ff // 4},
        n_experts=4, top_k=2, dropless=True)
    # Lazy + memoized: ~2.8 GB of fp32 MoE masters must not sit in
    # HBM while the four DENSE rows run (the loop drops each engine
    # before building the next for exactly this reason).
    _moe_params_cache: list = []

    def mparams():
        if not _moe_params_cache:
            p = init_moe_params(mcfg, key)
            jax.block_until_ready(p)
            _moe_params_cache.append(p)
        return _moe_params_cache[0]

    _qmoe_cache: list = []

    def qmparams():
        # int8 MoE tree (experts int8 per-output-channel, router fp32
        # by design — models/quant.py); lazy like the fp masters.
        if not _qmoe_cache:
            q = quantize_weights(mparams())
            jax.block_until_ready(q)
            _qmoe_cache.append(q)
        return _qmoe_cache[0]

    n_slots = 2 if tiny else 8
    eng_new = 8 if tiny else 64
    bucket = 16 if tiny else 512
    maxlen = bucket + eng_new + 8
    prompts = [
        list(range(1, 1 + (3 + i % 5))) for i in range(2 * n_slots)
    ]
    engines = (
        ("continuous_bf16", lambda: ContinuousBatcher(
            cfg, params, n_slots=n_slots, prompt_bucket=bucket,
            max_len=maxlen)),
        ("continuous_int8", lambda: ContinuousBatcher(
            cfg, qparams, n_slots=n_slots, prompt_bucket=bucket,
            max_len=maxlen)),
        ("spec_continuous_bf16", lambda: SpeculativeBatcher(
            cfg, params, cfg, params, k=4, n_slots=n_slots,
            prompt_bucket=bucket, max_len=maxlen)),
        ("spec_continuous_int8", lambda: SpeculativeBatcher(
            cfg, qparams, cfg, params, k=4, n_slots=n_slots,
            prompt_bucket=bucket, max_len=maxlen)),
        ("continuous_moe_dropless", lambda: ContinuousBatcher(
            mcfg, mparams(), n_slots=n_slots, prompt_bucket=bucket,
            max_len=maxlen, mlp_fn=moe_slot_mlp(mcfg))),
        # Self-draft (MoE drafts for itself), mirroring the dense
        # ceiling row — drafting with the unrelated dense weights
        # would measure the acceptance FLOOR instead (0.0 over the
        # 32k vocab; a tiny vocab masks it).
        ("spec_continuous_moe_dropless", lambda: SpeculativeBatcher(
            mcfg, mparams(), mcfg, mparams(), k=4, n_slots=n_slots,
            prompt_bucket=bucket, max_len=maxlen,
            mlp_fn=moe_slot_mlp(mcfg),
            draft_mlp_fn=moe_slot_mlp(mcfg))),
        # The remaining two cells of the {dense, MoE} x {plain, spec}
        # x {bf16, int8} matrix:
        ("continuous_moe_int8", lambda: ContinuousBatcher(
            mcfg, qmparams(), n_slots=n_slots, prompt_bucket=bucket,
            max_len=maxlen, mlp_fn=moe_slot_mlp(mcfg))),
        # int8 MoE target + fp MoE draft: the deployment-shaped pair,
        # mirroring the dense int8 row.
        ("spec_continuous_moe_int8", lambda: SpeculativeBatcher(
            mcfg, qmparams(), mcfg, mparams(), k=4, n_slots=n_slots,
            prompt_bucket=bucket, max_len=maxlen,
            mlp_fn=moe_slot_mlp(mcfg),
            draft_mlp_fn=moe_slot_mlp(mcfg))),
    )
    any_engine_ok = False
    eng = None
    for name, make_eng in engines:
        # One engine failing (an OOM, a lowering) must not cost the
        # other rows their chip time — an error row IS a result, and
        # the script still exits non-zero unless some engine ran.
        # Drop the previous engine BEFORE building the next so a dead
        # engine's KV caches don't sit in HBM under the new allocation.
        eng = None
        metric = metric_name(f"serving_{name}_throughput", device)
        try:
            eng = make_eng()
            for p in prompts:
                eng.submit(p, max_new_tokens=eng_new)
            t0 = time.perf_counter()
            while eng.has_work():
                eng.step()
            dt = time.perf_counter() - t0
            st = eng.stats()
            row = {
                "metric": metric,
                "value": round(st["tokens_emitted"] / dt, 1),
                "unit": "tokens/s",
                **device,
                "ticks": st["steps"],
                "requests": st["completed"],
                "ttft_p50_s": st["ttft_p50_s"],
                "ttft_p99_s": st["ttft_p99_s"],
                "latency_p99_s": st["latency_p99_s"],
            }
            if "spec_acceptance" in st:
                row["acceptance"] = st["spec_acceptance"]
            any_engine_ok = True
        except Exception as e:  # noqa: BLE001 — keep the matrix going
            row = {"metric": metric, **device,
                   "error": f"{type(e).__name__}: {str(e)[:120]}"}
        print(json.dumps(row), flush=True)
    return 0 if any_engine_ok else 1


if __name__ == "__main__":
    sys.exit(main())
