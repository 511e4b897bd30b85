"""Long-context attention benchmark: where flash earns its keep.

At S=1024 XLA's fused attention is hard to beat; the flash kernel's
case is long context, where dense attention materializes S^2 scores
per head and HBM traffic grows quadratically while flash streams KV
blocks through VMEM at O(S) activation memory (ops/attention.py).
This benchmark measures single-chip training throughput of the
flagship decoder at S in {4096, 8192} with attn in {xla, pallas} and
prints one JSON line per point, each naming the device.

One process (a chip belongs to one process at a time); exits non-zero
when JAX's default device is not a TPU. `PBST_LONGCTX_TINY=1` rehearses
the harness with toy shapes (xla column only — interpret-mode pallas
is too slow to rehearse).
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import time

from bench_common import bench_device, mfu

# (seq, batch): batch shrinks as S grows to hold tokens/step roughly
# constant and fit HBM; global batch is the dp axis's job in training.
POINTS = [(4096, 2), (8192, 1)]
ATTN = ["xla", "pallas"]
STEPS = 6  # per timed chunk (one dispatch)


def run_point(cfg_base, device, seq, batch, attn, warm_chunks=1,
              timed_chunks=2):
    import jax
    import jax.numpy as jnp
    from jax import lax

    from pbs_tpu.models import init_params, make_train_step

    cfg = dataclasses.replace(cfg_base, max_seq=seq, attn_impl=attn,
                              remat=True, remat_policy="dots")
    n_params = cfg.num_params()
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)
    init_opt, train_step = make_train_step(cfg, learning_rate=3e-4)
    state = (params, jax.jit(init_opt)(params), 0)
    tokens = jax.random.randint(key, (batch, seq), 0, cfg.vocab, jnp.int32)

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
    toks_per_s = batch * (seq - 1) * n_steps / dt
    # MFU on the 6ND dense-FLOP convention, consistent with bench.py;
    # at long S the attention FLOPs (12*L*d*S^2 per token batch) are no
    # longer negligible, so report attn-inclusive MFU too.
    dense = 6 * n_params
    attn_flops = 12 * cfg.n_layers * cfg.d_model * seq  # per token
    util = mfu(toks_per_s, dense, device)
    util_attn = mfu(toks_per_s, dense + attn_flops, device)
    return {
        **device,
        "seq": seq,
        "batch": batch,
        "attn": attn,
        "tokens_per_s": round(toks_per_s, 1),
        **({"mfu_dense": round(util, 4),
            "mfu_incl_attn": round(util_attn, 4)}
           if util is not None else {}),
        "step_ms": round(1e3 * dt / n_steps, 1),
        "compile_s": round(compile_s, 1),
        "loss": round(final_loss, 3),
    }


def main() -> int:
    tiny = os.environ.get("PBST_LONGCTX_TINY", "").lower() in ("1", "true")
    from pbs_tpu.models import flagship_config

    device = bench_device(rehearsal=tiny)
    cfg_base = flagship_config(tiny=tiny)
    global POINTS, STEPS, ATTN
    if tiny:
        POINTS, STEPS, ATTN = [(256, 1)], 2, ["xla"]

    results = []
    for (seq, batch), attn in [(p, a) for p in POINTS for a in ATTN]:
        try:
            r = run_point(cfg_base, device, seq, batch, attn)
        except Exception as e:  # noqa: BLE001 — a point that does not
            # fit (an OOM, say) is a result; the script still exits
            # non-zero unless some point ran.
            r = {**device, "seq": seq, "batch": batch, "attn": attn,
                 "error": f"{type(e).__name__}: {str(e)[:120]}"}
        print(json.dumps(r), flush=True)
        results.append(r)
    ok = [r for r in results if "error" not in r]
    for seq, _ in POINTS:
        cols = {r["attn"]: r for r in ok if r["seq"] == seq}
        if "xla" in cols and "pallas" in cols:
            print(json.dumps({
                "seq": seq,
                "pallas_speedup": round(
                    cols["pallas"]["tokens_per_s"]
                    / cols["xla"]["tokens_per_s"], 3),
            }), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
