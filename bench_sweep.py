"""Flagship throughput sweep: justify the benchmarked configuration.

Runs the flagship decoder across remat policy x batch x attention
implementation in ONE process (a chip belongs to one process at a
time), timing a short on-device `lax.scan` training chunk per point.
Output: one JSON line per point, each naming the device, plus a final
`best` line. Exits non-zero when JAX's default device is not a TPU.

Usage:
    chiprun -- python bench_sweep.py          # full grid on the chip
    PBST_SWEEP_TINY=1 JAX_PLATFORMS=cpu python bench_sweep.py  # rehearsal
"""

from __future__ import annotations

import dataclasses
import itertools
import json
import os
import sys
import time

from bench_common import bench_device, mfu, parse_mu_dtype

REMAT = [("none", False, "full"), ("dots", True, "dots"),
         ("full", True, "full")]
BATCHES = [4, 6, 8]
ATTN = ["xla", "pallas"]
SEQ = 1024
STEPS = 8  # per timed chunk (one dispatch)


def run_point(cfg_base, device, remat_name, remat, policy, batch, attn,
              warm_chunks=1, timed_chunks=2, mu_dtype=None):
    import jax
    import jax.numpy as jnp
    from jax import lax

    from pbs_tpu.models import init_params, make_train_step

    cfg = dataclasses.replace(cfg_base, remat=remat, remat_policy=policy,
                              attn_impl=attn)
    n_params = cfg.num_params()
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)
    init_opt, train_step = make_train_step(cfg, learning_rate=3e-4,
                                           mu_dtype=mu_dtype)
    state = (params, jax.jit(init_opt)(params), 0)
    tokens = jax.random.randint(key, (batch, SEQ), 0, cfg.vocab, jnp.int32)

    def chunk_fn(st, toks):
        def body(carry, _):
            carry, m = train_step(carry, toks)
            return carry, m["loss"]

        st, losses = lax.scan(body, st, None, length=STEPS)
        return st, losses[-1]

    chunk = jax.jit(chunk_fn, donate_argnums=(0,))
    t_c0 = time.perf_counter()
    for _ in range(warm_chunks):
        state, loss = chunk(state, tokens)
    float(loss)
    compile_s = time.perf_counter() - t_c0

    t0 = time.perf_counter()
    for _ in range(timed_chunks):
        state, loss = chunk(state, tokens)
    final_loss = float(loss)
    dt = time.perf_counter() - t0

    n_steps = timed_chunks * STEPS
    toks_per_s = batch * (SEQ - 1) * n_steps / dt
    util = mfu(toks_per_s, 6 * n_params, device)
    return {
        **device,
        "remat": remat_name,
        "batch": batch,
        "attn": attn,
        "tokens_per_s": round(toks_per_s, 1),
        **({"mfu": round(util, 4)} if util is not None else {}),
        "step_ms": round(1e3 * dt / n_steps, 1),
        "compile_s": round(compile_s, 1),
        "loss": round(final_loss, 3),
        "n_params": n_params,
    }


def main() -> int:
    tiny = os.environ.get("PBST_SWEEP_TINY", "").lower() in ("1", "true")
    from pbs_tpu.models import flagship_config

    cfg_base = flagship_config(tiny=tiny)
    global SEQ, STEPS, BATCHES, ATTN, REMAT
    if tiny:
        SEQ, STEPS, BATCHES = 128, 2, [2]
    # Env-restricted grids for follow-up runs (e.g. the pallas column
    # alone after a kernel fix).
    lc_env = os.environ.get("PBST_SWEEP_LOSS_CHUNKS")
    if lc_env:
        # Chunked cross-entropy: the (B, S, vocab) fp32 logits tensor
        # never materializes, freeing ~0.8 GB of loss-tail activation.
        cfg_base = dataclasses.replace(cfg_base, loss_chunks=int(lc_env))
    # Reduced-precision Adam moments (models.default_optimizer) free
    # 2.8 GB of optimizer HBM at the flagship shape. One parser shared
    # with bench.py (bench_common) so labels never diverge.
    try:
        mu_dtype, mu_label = parse_mu_dtype(
            os.environ.get("PBST_SWEEP_MU_DTYPE"))
    except ValueError as e:
        print(json.dumps({"error": f"PBST_SWEEP_MU_DTYPE: {e}"}),
              flush=True)
        return 1
    batches_env = os.environ.get("PBST_SWEEP_BATCHES")
    if batches_env:
        # e.g. PBST_SWEEP_BATCHES=8,12,16 — probe beyond the default
        # grid once the HBM levers (flash + chunked CE + bf16 moments)
        # have freed enough headroom for larger batches.
        try:
            BATCHES = [int(b) for b in batches_env.split(",") if b.strip()]
        except ValueError:
            BATCHES = []
        # Fail fast on empty AND on non-positive batches: a 0/-1 batch
        # would only surface as per-point error rows after burning chip
        # time (bench.py's _int_knob enforces >= 1 the same way).
        if not BATCHES or any(b < 1 for b in BATCHES):
            print(json.dumps(
                {"error": "PBST_SWEEP_BATCHES must be ints >= 1: "
                          f"{batches_env}"}),
                flush=True)
            return 1
    attn_env = os.environ.get("PBST_SWEEP_ATTN")
    if attn_env:
        ATTN = attn_env.split(",")
        # flash attention frees the S^2 probs memory, so remat=none
        # and batch 8 may compile where the xla column could not.
        REMAT = [r for r in REMAT if r[0] in ("none", "dots")]

    # Knobs are validated above, before the backend comes up.
    device = bench_device(rehearsal=tiny)
    results = []
    grid = list(itertools.product(REMAT, BATCHES, ATTN))
    for (rname, remat, policy), batch, attn in grid:
        try:
            r = run_point(cfg_base, device, rname, remat, policy, batch,
                          attn, mu_dtype=mu_dtype)
            if cfg_base.loss_chunks > 1:
                r["loss_chunks"] = cfg_base.loss_chunks
            if mu_dtype is not None:
                r["mu_dtype"] = mu_label
        except Exception as e:  # noqa: BLE001 — a point that does not
            # fit or lower (an OOM, say) is a result of the sweep; the
            # script still exits non-zero unless some point ran.
            r = {**device, "remat": rname, "batch": batch, "attn": attn,
                 "error": f"{type(e).__name__}: {str(e)[:120]}"}
        print(json.dumps(r), flush=True)
        results.append(r)
    if not results:
        # A sweep that emitted NOTHING must say so on stdout: a
        # silent exit 1 reads like a crash.
        print(json.dumps({"error": "sweep emitted no points "
                          f"(grid had {len(grid)})"}), flush=True)
        return 1
    ok = [r for r in results if "error" not in r]
    if ok:
        best = max(ok, key=lambda r: r["tokens_per_s"])
        print(json.dumps({"best": best}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
