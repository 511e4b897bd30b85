"""Flagship training throughput on one TPU chip, scan-loop protocol.

    chiprun -- python bench.py
    PBST_BENCH_TINY=1 JAX_PLATFORMS=cpu python bench.py   # rehearsal

Steady-state tokens/s of the flagship decoder's train step, run ON
DEVICE via ``lax.scan`` (``STEPS_PER_CHUNK`` optimizer steps per
dispatch) on one repeated batch. This is the upper bound a tenant
could reach, not what one gets under a ``Partition`` (the executor
steps from the host — ROADMAP S2). One process: it is the chip's only
client, exits non-zero when JAX's default device is not a TPU, and
prints exactly one JSON row that names the device it ran on.

``PBST_BENCH_{BATCH,LOSS_CHUNKS,ATTN,REMAT,MU_DTYPE}`` select a
candidate configuration; each is validated before the backend is
touched and named in the row.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import time

from bench_common import bench_device, metric_name, mfu, parse_mu_dtype

TARGET_MFU = 0.40  # the bar ``vs_baseline`` normalizes against

WARMUP_CHUNKS = 2
BENCH_CHUNKS = 3
STEPS_PER_CHUNK = 10  # on-device lax.scan: one dispatch per chunk
BATCH = 6
SEQ = 1024

_T0 = time.perf_counter()


def _mark(msg: str) -> None:
    """Stage marker on stderr: says how far a run got."""
    sys.stderr.write(f"[bench +{time.perf_counter() - _T0:7.1f}s] {msg}\n")
    sys.stderr.flush()


def _int_knob(name: str) -> int | None:
    raw = os.environ.get(name)
    if not raw:
        return None
    try:
        v = int(raw)
    except ValueError:
        raise SystemExit(f"{name} must be an int: {raw!r}")
    if v < 1:
        raise SystemExit(f"{name} must be >= 1: {v}")
    return v


def main() -> None:
    # Knobs first: a typo must fail in milliseconds, not after the
    # backend comes up and a 700M step compiles.
    tiny = os.environ.get("PBST_BENCH_TINY", "").lower() in (
        "1", "true", "yes")
    batch, seq = (2, 128) if tiny else (BATCH, SEQ)
    warmup, chunks, steps = (1, 1, 2) if tiny else (
        WARMUP_CHUNKS, BENCH_CHUNKS, STEPS_PER_CHUNK)
    extras = {}
    knob_batch = _int_knob("PBST_BENCH_BATCH")
    if knob_batch:
        batch = extras["batch"] = knob_batch
    # "0" is the explicit spelling of the unchunked default.
    loss_chunks = (None if os.environ.get("PBST_BENCH_LOSS_CHUNKS") == "0"
                   else _int_knob("PBST_BENCH_LOSS_CHUNKS"))
    if loss_chunks:
        if seq % loss_chunks:
            raise SystemExit(f"PBST_BENCH_LOSS_CHUNKS={loss_chunks} must "
                             f"divide seq={seq}")
        extras["loss_chunks"] = loss_chunks
    attn = os.environ.get("PBST_BENCH_ATTN")
    if attn:
        if attn not in ("xla", "pallas"):
            raise SystemExit(f"PBST_BENCH_ATTN must be xla|pallas: {attn}")
        extras["attn"] = attn
    remat = os.environ.get("PBST_BENCH_REMAT")
    if remat:
        if remat not in ("none", "dots", "full"):
            raise SystemExit(
                f"PBST_BENCH_REMAT must be none|dots|full: {remat}")
        extras["remat"] = remat
    try:
        mu_dtype, mu_label = parse_mu_dtype(
            os.environ.get("PBST_BENCH_MU_DTYPE"))
    except ValueError as e:
        raise SystemExit(f"PBST_BENCH_MU_DTYPE: {e}")

    _mark("importing jax")
    import jax
    import jax.numpy as jnp
    from jax import lax

    from pbs_tpu.models import flagship_config, init_params, make_train_step

    device = bench_device(rehearsal=tiny)
    _mark(f"backend init: {jax.devices()}")

    cfg = flagship_config(tiny=tiny)
    if loss_chunks:
        cfg = dataclasses.replace(cfg, loss_chunks=loss_chunks)
    if attn:
        cfg = dataclasses.replace(cfg, attn_impl=attn)
    if remat == "none":
        cfg = dataclasses.replace(cfg, remat=False)
    elif remat:
        cfg = dataclasses.replace(cfg, remat=True, remat_policy=remat)
    n_params = cfg.num_params()
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)
    jax.block_until_ready(params)
    _mark(f"params initialized ({n_params / 1e6:.0f}M)")
    init_opt, train_step = make_train_step(cfg, learning_rate=3e-4,
                                           mu_dtype=mu_dtype)
    state = (params, jax.jit(init_opt)(params), 0)
    tokens = jax.random.randint(key, (batch, seq), 0, cfg.vocab, jnp.int32)

    def run_chunk(st, toks):
        def body(carry, _):
            carry, m = train_step(carry, toks)
            return carry, m["loss"]

        st, losses = lax.scan(body, st, None, length=steps)
        return st, losses[-1]

    chunk = jax.jit(run_chunk, donate_argnums=(0,))
    _mark("compiling train chunk")
    for i in range(warmup):
        state, loss = chunk(state, tokens)
        float(loss)  # host fetch: a hard sync per chunk
        _mark(f"warmup chunk {i} done")
    t0 = time.perf_counter()
    for _ in range(chunks):
        state, loss = chunk(state, tokens)
    final_loss = float(loss)  # fetching the last syncs them all
    dt = time.perf_counter() - t0

    n_steps = chunks * steps
    tokens_per_s = batch * (seq - 1) * n_steps / dt
    row = {
        "metric": metric_name("flagship_train_throughput", device),
        "value": round(tokens_per_s, 1),
        "unit": "tokens/s",
        **device,
        "n_params": n_params,
        "step_ms": round(1e3 * dt / n_steps, 1),
        "loss": round(final_loss, 4),
        "mu_dtype": mu_label,
        **extras,
    }
    util = mfu(tokens_per_s, 6 * n_params, device)
    if util is not None:
        row["mfu"] = round(util, 4)
        row["vs_baseline"] = round(util / TARGET_MFU, 4)
    print(json.dumps(row))
    sys.stdout.flush()


if __name__ == "__main__":
    main()
