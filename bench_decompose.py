"""Roofline decomposition of the flagship step: where the MFU goes.

Verdict r2 next-4: if the measured MFU cannot clear 45%, produce the
decomposition showing why — attention FLOP share, remat recompute
tax, dispatch overhead, and the measured compute/memory/collective
split. Each component is measured, not estimated, where the chip
allows:

- **model_flops_per_token**: XLA cost analysis of the compiled train
  step (the whole program: fwd + bwd + AdamW), divided by tokens —
  compared against the 6N dense convention bench.py normalizes with.
  The gap is attention + remat recompute + optimizer.
- **remat_tax**: cost-analysis FLOPs of the same step compiled with
  remat("dots") vs remat=none (compile-only probe: OOM shows at
  compile time, so the none-point compiles or reports its failure
  without a wedge risk).
- **attention_share**: analytic causal attention matmul FLOPs
  (fwd+bwd ~ 12*L*S*d per token with the causal 1/2) over the 6N
  dense convention (the same denominator bench.py's MFU uses), so
  the share reads directly as "MFU points the 6N convention does
  not credit".
- **dispatch_overhead**: per-step time of a 1-step dispatch vs a
  10-step on-device lax.scan chunk — the host dispatch cost the scan
  amortizes.
- **measured split**: one profiled chunk through XlaQuantumProfiler —
  device-lane compute/memory/collective fractions.

One JSON line per section, each naming the device; one chip, one
process. Exits non-zero when JAX's default device is not a TPU;
`PBST_DECOMP_TINY=1` rehearses the harness on the CPU.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import time

from bench_common import bench_device, mfu


def main() -> int:
    tiny = os.environ.get("PBST_DECOMP_TINY", "").lower() in ("1", "true")
    import jax
    import jax.numpy as jnp
    from jax import lax

    from pbs_tpu.models import flagship_config, init_params, make_train_step
    from pbs_tpu.telemetry.profiler import XlaQuantumProfiler
    from pbs_tpu.telemetry.source import cost_analysis_of

    device = bench_device(rehearsal=tiny)

    def emit(section: dict) -> None:
        print(json.dumps({**device, **section}), flush=True)

    cfg = flagship_config(tiny=tiny)
    B, S = (2, 128) if tiny else (6, 1024)
    n_params = cfg.num_params()
    key = jax.random.PRNGKey(0)
    tokens = jax.random.randint(key, (B, S), 0, cfg.vocab, jnp.int32)
    toks_per_step = B * (S - 1)

    def _label(c):
        return f"remat={c.remat_policy if c.remat else 'none'}"

    def compile_abstract(c):
        """Compile against abstract (shape-only) inputs: the cost
        analysis is identical and NOTHING is allocated on device, so
        an OOM here is a genuine compile-time memory-planning verdict,
        not a runtime artifact of probe state."""
        init_opt, train_step = make_train_step(c, learning_rate=3e-4)
        params_s = jax.eval_shape(lambda: init_params(c, key))
        opt_s = jax.eval_shape(init_opt, params_s)
        state_s = (params_s, opt_s, jax.ShapeDtypeStruct((), jnp.int32))
        toks_s = jax.ShapeDtypeStruct((B, S), jnp.int32)
        return jax.jit(train_step, donate_argnums=(0,)).lower(
            state_s, toks_s).compile()

    # -- 1+2: cost analysis, remat tax (shape-only: zero device state)
    flops_base, bytes_base = cost_analysis_of(compile_abstract(cfg))
    emit({
        "config": _label(cfg),
        "flops_per_token": round(flops_base / toks_per_step, 1),
        "dense_6N": 6 * n_params,
        "ratio_vs_6N": round(flops_base / toks_per_step / (6 * n_params), 4),
        "hbm_bytes_per_token": round(bytes_base / toks_per_step, 1),
    })

    try:
        none_cfg = dataclasses.replace(cfg, remat=False)
        flops_none, _ = cost_analysis_of(compile_abstract(none_cfg))
        tax = (flops_base - flops_none) / max(flops_none, 1)
        r = {"remat_tax_frac": round(tax, 4),
             "flops_none_per_token": round(flops_none / toks_per_step, 1)}
    except Exception as e:  # noqa: BLE001 — OOM at compile is a result
        r = {"remat_none": f"does not compile: {type(e).__name__}: "
                           f"{str(e)[:100]}"}
    emit(r)

    # -- 2b: measured HBM bandwidth — the roofline's OTHER axis. The
    # MFU frame argues about where 197 TF/s goes; the memory-bound
    # buckets need the real achievable GB/s, not the datasheet 819.
    # A donated x + 1 over a ~1 GB buffer is the cleanest read+write
    # stream XLA will emit; 2*bytes / t is the achieved bandwidth.
    membw_gbs = None
    try:
        mb = 16 if tiny else 1024
        reps = 8 if tiny else 64
        buf = jnp.zeros((mb, 1024, 256), jnp.float32)  # mb MiB

        # All reps inside ONE dispatch (fori_loop), timing bracketed
        # by a host fetch; a per-rep dispatch would drown 2.6 ms of
        # traffic in dispatch overhead. The remaining single round
        # trip is measured by a no-op fetch and subtracted.
        def stream(a):
            return jax.lax.fori_loop(0, reps, lambda i, x: x + 1.0, a)

        bump = jax.jit(stream, donate_argnums=(0,))
        buf = bump(buf)
        float(buf[0, 0, 0])  # compile + sync
        rtt_probe = jax.jit(lambda: jnp.zeros(()))
        float(rtt_probe())
        t0 = time.perf_counter()
        float(rtt_probe())
        rtt_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        buf = bump(buf)
        float(buf[0, 0, 0])
        dt_bw = max(time.perf_counter() - t0 - rtt_s, 1e-9)
        nbytes = mb * 1024 * 1024
        membw_gbs = round(2 * nbytes * reps / dt_bw / 1e9, 1)
        emit({
            "membw_gbs": membw_gbs,
            "membw_buffer_mib": mb,
            "membw_stream_reps": reps,
            "membw_rtt_ms": round(1e3 * rtt_s, 1),
        })
        del buf
    except Exception as e:  # noqa: BLE001 — a probe, not the bench
        emit({"membw": f"probe failed: "
                          f"{type(e).__name__}: {str(e)[:100]}"})

    # -- 3: analytic attention share (causal matmul FLOPs, fwd+bwd)
    attn_per_tok = 12 * cfg.n_layers * cfg.d_model * S // 2
    emit({
        "attention_flops_per_token": attn_per_tok,
        "attention_share_of_6N": round(attn_per_tok / (6 * n_params), 4),
    })

    # -- 4: dispatch overhead — single-step dispatch vs 10-step scan.
    # Donation everywhere (this is the ~700M flagship: a second
    # resident train state is real HBM), and the two timed variants
    # run SEQUENTIALLY on states created fresh so at most one full
    # state is alive at a time.
    init_opt, train_step = make_train_step(cfg, learning_rate=3e-4)
    one = jax.jit(train_step, donate_argnums=(0,))

    def chunk_fn(st, toks):
        def body(carry, _):
            carry, m = train_step(carry, toks)
            return carry, m["loss"]
        st, losses = lax.scan(body, st, None, length=10)
        return st, losses[-1]

    chunk = jax.jit(chunk_fn, donate_argnums=(0,))

    def fresh_state():
        params = init_params(cfg, key)
        return (params, jax.jit(init_opt)(params), 0)

    state = fresh_state()
    state, l = chunk(state, tokens); float(l)  # warm scan
    t0 = time.perf_counter()
    for _ in range(2):
        state, l = chunk(state, tokens)
    float(l); t_chunk = (time.perf_counter() - t0) / 20

    # -- 5: measured split of one profiled chunk (state still live)
    prof = XlaQuantumProfiler()
    holder = [state]

    def profiled():
        st2, l2 = chunk(holder[0], tokens)
        holder[0] = st2
        return float(l2)

    _, st = prof.profile(profiled)
    del state, holder  # release before the host-loop variant's state

    state_b = fresh_state()
    state_b, m = one(state_b, tokens); float(m["loss"])  # warm 1-step
    t0 = time.perf_counter()
    for _ in range(3):
        state_b, m = one(state_b, tokens)
    float(m["loss"]); t_one = (time.perf_counter() - t0) / 3
    toks_per_s = toks_per_step / t_chunk
    util_6n = mfu(toks_per_s, 6 * n_params, device)
    emit({
        "step_ms_hostloop": round(1e3 * t_one, 2),
        "step_ms_scan": round(1e3 * t_chunk, 2),
        "dispatch_overhead_ms": round(1e3 * (t_one - t_chunk), 2),
        "tokens_per_s_scan": round(toks_per_s, 1),
        **({"mfu_6N": round(util_6n, 4), "mfu_cost_analysis": round(
            mfu(toks_per_s, flops_base / toks_per_step, device), 4)}
           if util_6n is not None else {}),
    })
    if st is not None and st.n_ops:
        emit({
            "measured_source": st.source,
            "compute_frac": round(
                st.compute_ns / max(st.compute_ns + st.memory_ns
                                    + st.collective_ns, 1), 4),
            "stall_frac": round(st.stall_frac, 4),
            "collective_frac": round(st.collective_frac, 4),
            "top_ops": st.top_ops[:5],
        })
        # -- 6: stall-proxy reconciliation. The
        # feedback loop's HBM-stall input is a TIME proxy (non-MXU op
        # time); the roofline predicts the memory-bound share
        # independently from cost-analysis BYTES at the measured
        # bandwidth. Reporting both plus their ratio characterizes
        # the proxy's error on this hardware — the reference's analog
        # calibrates its feedback input against measured LLC misses
        # rather than trusting a model
        # (xen-4.2.1/xen/arch/x86/perfctr.c:1547-1573).
        if membw_gbs:
            bytes_per_s = (bytes_base / toks_per_step) * toks_per_s
            pred = bytes_per_s / (membw_gbs * 1e9)
            meas = st.memory_ns / max(
                st.compute_ns + st.memory_ns + st.collective_ns, 1)
            emit({
                "reconcile_predicted_mem_frac": round(pred, 4),
                "reconcile_measured_mem_frac": round(meas, 4),
                "reconcile_proxy_correction": round(
                    meas / max(pred, 1e-9), 3),
                "reconcile_note": (
                    "proxy correction = measured device-lane memory "
                    "share / roofline-predicted share at the measured "
                    "bandwidth; 1.0 = the proxy is faithful"),
            })
    else:
        emit({"measured_split": f"no sample: "
                          f"{prof.last_error}"})
    return 0


if __name__ == "__main__":
    sys.exit(main())
