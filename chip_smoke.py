"""Chip smoke: the scheduler's main path, once, on the accelerator.

    chiprun -- python chip_smoke.py              # one chip: the legs below
    chiprun --chips 4 -- python chip_smoke.py --four-chip
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearsal   # tiny, on the CPU

One process, no children (a chip belongs to one process at a time). The
flagship (~700M: d_model 2048, 12 layers, vocab 32768, widths and depth
uncut, random weights from a seed) is driven through the entry points a
user calls:

- **kernels** — the Pallas flash-attention forward, both backward
  kernels, the lse variant and the instrumented matmul through Mosaic
  at the flagship's attention shape against the XLA reference, then a
  few ``attn_impl="pallas"`` train steps whose lowered text carries the
  Mosaic custom call and whose step-0 loss matches the ``xla`` path.
- **train** — ``Job`` -> ``Partition(TpuBackend, "credit")`` +
  ``FeedbackPolicy`` -> ``part.run()`` with a donated train step.
- **serve** — ``Gateway([ShardedServeBackend])`` -> ``ContinuousBatcher``
  answering requests of different lengths, one of them checked
  token-exact against the lockstep ``make_generate`` loop.
- **co-resident** — both tenants in one ``Partition`` under credit +
  feedback, serving wrapped by ``make_continuous_serve_step``.

Without a TPU it exits non-zero at once and prints no result; a CPU
run has to be asked for with ``--rehearsal``, and then every line says
so. A leg that fails is reported with its traceback, the remaining legs
still run (one tool call should show everything that is broken), and
the run exits non-zero. The last line of a passing run is one JSON
object naming the device as JAX reports it. Times printed here are
smoke output, not measurements.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib.metadata
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

from pbs_tpu.gateway import Gateway, TenantQuota
from pbs_tpu.models import (
    ContinuousBatcher,
    flagship_config,
    init_params,
    make_continuous_serve_step,
    make_eval_step,
    make_generate,
    make_train_step,
)
from pbs_tpu.models.transformer import TransformerConfig, causal_attention
from pbs_tpu.ops.attention import flash_attention, flash_attention_lse
from pbs_tpu.ops.matmul import instrumented_matmul, scale_stats
from pbs_tpu.perf import native_info
from pbs_tpu.runtime import Job, Partition, SchedParams
from pbs_tpu.sched import FeedbackPolicy
from pbs_tpu.serve import ShardedServeBackend
from pbs_tpu.telemetry import Counter
from pbs_tpu.telemetry.source import TpuBackend
from pbs_tpu.utils.compile_cache import cache_counts, setup_compilation_cache

GIB = float(1 << 30)


@dataclasses.dataclass(frozen=True)
class Sizes:
    cfg: TransformerConfig
    seq: int
    train_batch: int  # the train leg
    colo_batch: int  # the co-resident leg: sized so both tenants fit
    attn_shape: tuple  # (B, S, H, Hkv, hd) of the kernels leg
    matmul_shape: tuple  # (M, K, N)
    train_steps: int = 6
    profile_every: int = 3
    colo_train_steps: int = 8
    colo_serve_ticks: int = 48
    n_slots: int = 4
    prompt_bucket: int = 32
    max_len: int = 64
    prompt_lens: tuple = (5, 9, 17, 3, 12, 7)  # all different: see leg_serve
    max_new: int = 8


def full_sizes() -> Sizes:
    cfg = flagship_config()
    # HBM, from XLA's own buffer assignment for a v5e (15.75 GiB
    # usable): the donated train step peaks at 14.8 GiB at batch 6, so
    # the legs run one after another and free their state in between.
    # Co-resident, 7.8 GiB of train state and 2.6 GiB of fp32 serving
    # params stay; batch 1 x 1024 leaves the train step's 3.8 GiB of
    # temporaries about 1 GiB of slack (batch 2: 0.5 GiB).
    return Sizes(
        cfg=cfg, seq=cfg.max_seq, train_batch=6, colo_batch=1,
        attn_shape=(6, cfg.max_seq - 1, cfg.n_heads, cfg.n_kv_heads,
                    cfg.head_dim),
        matmul_shape=(1024, cfg.d_model, cfg.d_ff))


def rehearsal_sizes() -> Sizes:
    return Sizes(
        cfg=flagship_config(tiny=True), seq=64, train_batch=2,
        colo_batch=1, attn_shape=(1, 127, 4, 2, 64),
        matmul_shape=(256, 256, 512))


class SmokeFailure(AssertionError):
    """A check of this smoke did not hold."""


def require(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class Smoke:
    """The run's printer and shared helpers. Every line goes through
    :meth:`say`, which is how a rehearsal marks all of its output."""

    def __init__(self, sizes: Sizes, rehearsal: bool):
        self.sz = sizes
        self.dev = jax.devices()[0]
        self.on_tpu = self.dev.platform == "tpu"
        self.prefix = (f"[rehearsal platform={self.dev.platform}] "
                       if rehearsal else "")

    def say(self, msg: str = "") -> None:
        print(self.prefix + msg, flush=True)

    def mem(self, label: str) -> None:
        """bytes_in_use / peak_bytes_in_use of every local device (the
        peak is the process's high-water mark, not this leg's)."""
        for d in jax.local_devices():
            st = d.memory_stats()
            if not st:
                self.say(f"  hbm[{label}] {d}: the backend reports no "
                         "memory_stats")
                continue
            self.say(
                f"  hbm[{label}] {d}: in_use="
                f"{st['bytes_in_use'] / GIB:.2f}GiB peak_bytes_in_use="
                f"{st['peak_bytes_in_use'] / GIB:.2f}GiB limit="
                f"{st['bytes_limit'] / GIB:.2f}GiB")

    def require_compiled(self, lowered_text: str, what: str) -> None:
        """The Pallas kernels must have gone through Mosaic, not the
        interpreter. On the CPU (rehearsal) interpret mode is the only
        way they run, and the line says so."""
        if self.on_tpu:
            require("tpu_custom_call" in lowered_text,
                    f"{what}: no Mosaic custom call in the lowered text")
            self.say(f"  {what}: lowered text carries tpu_custom_call")
        else:
            self.say(f"  {what}: interpret mode (no Mosaic off a TPU)")

    def require_clean(self, be: TpuBackend) -> None:
        """Best-effort telemetry stays best-effort, but on this path
        every piece of it has to have worked."""
        require(be.cost_failures == 0,
                f"cost analysis failed: {be.last_cost_error}")
        if be.profiler is not None:
            require(be.profiler.failures == 0,
                    f"profiler failed: {be.profiler.last_error}")


def ledger_counters(part: Partition, job: Job) -> dict:
    """The job's counters as its partition's ledger slot holds them."""
    sums = part.ledger.snapshot(job.contexts[0].ledger_slot)
    return {c.name: int(sums[c]) for c in Counter}


class TsliceLog:
    """A partition recorder that keeps each tenant's ``tslice_us`` as
    the feedback policy leaves it, tick by tick."""

    def __init__(self):
        self.by_job: dict[str, list[int]] = {}

    def on_quantum(self, *_args) -> None:
        pass

    def on_feedback(self, _now_ns, job, _state) -> None:
        self.by_job.setdefault(job.name, []).append(job.params.tslice_us)

    def trajectory(self, name: str) -> str:
        """Run-length form: ``100x3 200x1 ...``."""
        runs: list[list[int]] = []
        for us in self.by_job.get(name, []):
            if runs and runs[-1][0] == us:
                runs[-1][1] += 1
            else:
                runs.append([us, 1])
        return " ".join(f"{us}x{n}" for us, n in runs) or "(no tick)"


class RecordingBatcher(ContinuousBatcher):
    """The gateway's completion record carries a token count only; the
    smoke needs the tokens themselves to compare with the reference."""

    def __init__(self, *args, **kw):
        self.retired = []
        super().__init__(*args, **kw)

    def _retire(self, slot):
        comp = super()._retire(slot)
        self.retired.append(comp)
        return comp


def make_train_job(s: Smoke, be: TpuBackend, batch: int, max_steps: int,
                   losses: list) -> tuple[Job, float]:
    """The train tenant: a donated jit step (fp32 params + AdamW are
    8.4 GB; two copies do not fit in 16 GB) with its executable handed
    to the backend for cost analysis. The AOT compile runs in the job's
    attribution scope, so its cost lands in the job's COMPILES slots."""
    cfg = s.sz.cfg
    init_opt, train_step = make_train_step(cfg, learning_rate=3e-4)
    step = jax.jit(train_step, donate_argnums=(0,))
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (batch, s.sz.seq), 0, cfg.vocab, jnp.int32)
    params = init_params(cfg, jax.random.PRNGKey(0))
    state = (params, jax.jit(init_opt)(params), 0)
    t0 = time.perf_counter()
    with be.compile_meter.attribute("train"):
        compiled = step.lower(state, tokens).compile()
    compile_s = time.perf_counter() - t0

    def train_fn(st):
        st, m = step(st, tokens)
        losses.append(m["loss"])
        return st, {"tokens": m["tokens"]}

    job = Job("train", step_fn=train_fn, state=state, compiled=compiled,
              params=SchedParams(weight=512, boost_on_wake=False),
              max_steps=max_steps)
    return job, compile_s


def make_backend(s: Smoke, params=None, tp: int = 1) -> ShardedServeBackend:
    return ShardedServeBackend(
        "engine", s.sz.cfg, params, tp=tp, dp=1, n_slots=s.sz.n_slots,
        prompt_bucket=s.sz.prompt_bucket, max_len=s.sz.max_len,
        engine_cls=RecordingBatcher)


def make_gateway(backend: ShardedServeBackend) -> Gateway:
    return Gateway(
        [backend],
        quotas={"smoke": TenantQuota(rate=1e9, burst=1e9,
                                     slo="interactive", max_queued=64)})


def prompt_of(cfg: TransformerConfig, n: int) -> list[int]:
    return [int(t) for t in np.random.default_rng(n).integers(
        1, cfg.vocab, size=n)]


# -- legs -------------------------------------------------------------------


def leg_kernels(s: Smoke) -> None:
    cfg = s.sz.cfg
    B, S, H, Hkv, hd = s.sz.attn_shape
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    q = jax.random.normal(ks[0], (B, S, H, hd), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, Hkv, hd), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, Hkv, hd), jnp.bfloat16)
    w = jax.random.normal(ks[3], (B, S, H, hd), jnp.bfloat16)
    xla_cfg = dataclasses.replace(cfg, attn_impl="xla")

    def ref_attn(q, k, v):
        return causal_attention(q, k, v, xla_cfg).astype(jnp.float32)

    def max_err(a, b):
        return float(jnp.max(jnp.abs(a.astype(jnp.float32) - b)))

    s.say(f"  attention shape B={B} S={S} H={H} Hkv={Hkv} hd={hd} bf16")
    fwd = jax.jit(flash_attention)
    s.require_compiled(fwd.lower(q, k, v).as_text(), "flash forward")
    ref = jax.jit(ref_attn)(q, k, v)
    err = max_err(fwd(q, k, v), ref)
    s.say(f"  flash forward vs xla: max|err|={err:.4f}")
    require(err < 0.05, f"flash forward off by {err}")

    o32, lse = jax.jit(flash_attention_lse)(q, k, v)
    err = max_err(o32, ref)

    def ref_lse(q, k):
        kr = jnp.repeat(k, H // Hkv, axis=2).astype(jnp.float32)
        sc = jnp.einsum("bqhd,bkhd->bqhk", q.astype(jnp.float32),
                        kr) / np.sqrt(hd)
        rows = jax.lax.broadcasted_iota(jnp.int32, (S, S), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (S, S), 1)
        sc = jnp.where((cols <= rows)[None, :, None, :], sc, -1e30)
        return jax.nn.logsumexp(sc, axis=-1)

    lse_err = max_err(lse[..., 0], jax.jit(ref_lse)(q, k))
    s.say(f"  flash_attention_lse vs xla: o max|err|={err:.4f} "
          f"lse max|err|={lse_err:.4f}")
    require(err < 0.05 and lse_err < 0.05, "flash_attention_lse off")

    def loss_of(attn):
        return lambda q, k, v: jnp.sum(
            attn(q, k, v).astype(jnp.float32) * w.astype(jnp.float32))

    g_flash = jax.jit(jax.grad(loss_of(flash_attention),
                               argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.jit(jax.grad(loss_of(ref_attn),
                             argnums=(0, 1, 2)))(q, k, v)
    for name, a, b in zip("qkv", g_flash, g_ref):
        b32 = b.astype(jnp.float32)
        rel = max_err(a, b32) / (float(jnp.max(jnp.abs(b32))) + 1e-9)
        s.say(f"  flash backward d{name} vs xla autodiff: rel={rel:.4f}")
        require(rel < 0.05, f"flash backward d{name} off by {rel}")

    M, K, N = s.sz.matmul_shape
    a = jax.random.normal(ks[0], (M, K), jnp.bfloat16)
    b = jax.random.normal(ks[1], (K, N), jnp.bfloat16)
    mm = jax.jit(instrumented_matmul)
    s.require_compiled(mm.lower(a, b).as_text(), "instrumented_matmul")
    out, raw = mm(a, b)
    ref_mm = a.astype(jnp.float32) @ b.astype(jnp.float32)
    rel = max_err(out, ref_mm) / float(jnp.max(jnp.abs(ref_mm)))
    st = scale_stats(np.asarray(raw), 256, 256, 256)
    tiles = (M // 256) * (K // 256) * (N // 256)
    s.say(f"  instrumented_matmul {M}x{K}x{N}: rel={rel:.4f} "
          f"mxu_tiles={st.mxu_tiles} (expect {tiles})")
    require(rel < 0.05 and st.mxu_tiles == tiles, "instrumented_matmul off")
    del q, k, v, w, ref, o32, lse, g_flash, g_ref, a, b, out, ref_mm

    # A few full-width train steps on the Pallas path, same params and
    # batch as the xla path's step-0 loss.
    batch = s.sz.train_batch
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (batch, s.sz.seq), 0, cfg.vocab, jnp.int32)
    params = init_params(cfg, jax.random.PRNGKey(0))
    loss_xla = float(jax.jit(make_eval_step(xla_cfg))(params, tokens))
    pallas_cfg = dataclasses.replace(cfg, attn_impl="pallas")
    init_opt, train_step = make_train_step(pallas_cfg, learning_rate=3e-4)
    step = jax.jit(train_step, donate_argnums=(0,))
    state = (params, jax.jit(init_opt)(params), 0)
    del params
    t0 = time.perf_counter()
    s.require_compiled(step.lower(state, tokens).as_text(),
                       "attn_impl=pallas train step")
    losses = []
    for _ in range(3):
        state, m = step(state, tokens)
        losses.append(float(m["loss"]))
    s.say(f"  pallas train {batch}x{s.sz.seq}: losses="
          f"{[round(x, 4) for x in losses]} xla step-0 loss="
          f"{loss_xla:.4f} (lower+compile+3 steps "
          f"{time.perf_counter() - t0:.1f}s)")
    require(all(np.isfinite(losses)), "pallas train loss not finite")
    require(abs(losses[0] - loss_xla) < 5e-3 * max(1.0, abs(loss_xla)),
            f"pallas step-0 loss {losses[0]} != xla {loss_xla}")
    require(losses[-1] < losses[0], "pallas train loss did not fall")
    s.mem("kernels")


def leg_train(s: Smoke) -> None:
    sz = s.sz
    be = TpuBackend(profile_every=sz.profile_every)
    part = Partition("smoke-train", source=be, scheduler="credit")
    fb = FeedbackPolicy(part)
    losses: list = []
    job, compile_s = make_train_job(s, be, sz.train_batch, sz.train_steps,
                                    losses)
    s.say(f"  train step {sz.train_batch}x{sz.seq} donated: "
          f"lower+compile {compile_s:.1f}s")
    part.add_job(job)
    t0 = time.perf_counter()
    quanta = part.run()
    wall = time.perf_counter() - t0
    require(job.error is None, f"train job failed: {job.error}")
    losses = [float(x) for x in losses]
    c = ledger_counters(part, job)
    shown = ("STEPS_RETIRED", "TOKENS", "DEVICE_TIME_NS", "DEVICE_FLOPS",
             "HBM_STALL_NS", "COMPILES", "COMPILE_TIME_NS", "HBM_BYTES")
    s.say(f"  part.run(): {quanta} quanta in {wall:.1f}s; losses="
          f"{[round(x, 4) for x in losses]}")
    s.say("  ledger: " + " ".join(f"{n}={c[n]}" for n in shown))
    require(job.steps_retired() == sz.train_steps,
            f"retired {job.steps_retired()} of {sz.train_steps} steps")
    require(all(np.isfinite(losses)), "train loss not finite")
    require(losses[-1] < losses[0],
            "train loss did not fall on the repeated batch")
    for n in ("STEPS_RETIRED", "TOKENS", "DEVICE_TIME_NS", "DEVICE_FLOPS",
              "HBM_STALL_NS", "COMPILES"):
        require(c[n] > 0, f"ledger slot {n} is zero")
    require(c["TOKENS"] == sz.train_steps * sz.train_batch * (sz.seq - 1),
            f"TOKENS={c['TOKENS']}")
    ts = be.measured("train")
    require(ts is not None, "no profiled quantum: "
            f"{be.profiler.last_error}")
    s.say(f"  profiled quantum: source={ts.source} n_ops={ts.n_ops} "
          f"stall_frac={ts.stall_frac:.3f} samples={be.profiler.samples} "
          f"failures={be.profiler.failures}")
    s.say("  profiled quantum, top ops (name, ns): "
          + ", ".join(f"{n}={ns}" for n, ns in ts.top_ops[:5]))
    require(ts.source == ("device" if s.on_tpu else "host"),
            f"TraceStats.source={ts.source}")
    s.require_clean(be)
    require(fb.state_of(job).ticks > 0, "feedback policy never ticked")
    s.mem("train")


def leg_serve(s: Smoke) -> None:
    sz, cfg = s.sz, s.sz.cfg
    params = init_params(cfg, jax.random.PRNGKey(7))
    t0 = time.perf_counter()
    backend = make_backend(s, params)
    s.say(f"  ShardedServeBackend tp=1 dp=1 slots={sz.n_slots} bucket="
          f"{sz.prompt_bucket} max_len={sz.max_len}: built (prefill + "
          f"decode compiled) in {time.perf_counter() - t0:.1f}s")
    gw = make_gateway(backend)
    prompts = [prompt_of(cfg, n) for n in sz.prompt_lens]
    for p in prompts:
        r = gw.submit("smoke", {"prompt": p, "max_new": sz.max_new})
        require(r.admitted, f"request of {len(p)} tokens shed: {r.reason}")
    done = []
    t0 = time.perf_counter()
    while gw.busy():
        done += gw.tick()
    s.say(f"  {len(done)} completions through gw.submit/gw.tick in "
          f"{time.perf_counter() - t0:.1f}s; tokens="
          f"{[info['tokens'] for _, info in done]}")
    require(len(done) == len(prompts),
            f"{len(done)} of {len(prompts)} requests completed")
    require(all(info["tokens"] == sz.max_new for _, info in done),
            "a request completed short of max_new")

    # Check used: TOKEN-EXACT greedy continuation against the lockstep
    # make_generate loop, for the longest prompt.
    probe = max(prompts, key=len)
    comp = next(c for c in backend.engine.retired
                if c.prompt_len == len(probe))
    ref = jax.jit(make_generate(cfg, sz.max_new, temperature=0.0))(
        params, jnp.asarray([probe], jnp.int32), jax.random.PRNGKey(0))
    ref = [int(t) for t in np.asarray(ref)[0]]
    s.say(f"  greedy check (token-exact vs make_generate), prompt of "
          f"{len(probe)}: engine={comp.tokens} reference={ref}")
    require(comp.tokens == ref, "engine continuation != lockstep reference")
    s.mem("serve")


def leg_colo(s: Smoke) -> None:
    sz, cfg = s.sz, s.sz.cfg
    be = TpuBackend()
    part = Partition("smoke-colo", source=be, scheduler="credit")
    fb = FeedbackPolicy(part)
    log = part.recorder = TsliceLog()
    # The serving backend first: its construction holds a second copy
    # of the params for a moment, which must not land on top of the
    # train state.
    backend = make_backend(s)
    losses: list = []
    train, compile_s = make_train_job(s, be, sz.colo_batch,
                                      sz.colo_train_steps, losses)
    s.say(f"  sizes: train {sz.colo_batch}x{sz.seq} donated (lower+"
          f"compile {compile_s:.1f}s), serve slots={sz.n_slots} bucket="
          f"{sz.prompt_bucket} max_len={sz.max_len}")
    prompts = [prompt_of(cfg, n) for n in sz.prompt_lens]

    def feed(tick):
        # One new request every fourth tick until the list is spent.
        if tick % 4 == 0 and tick // 4 < len(prompts):
            return [(prompts[tick // 4], sz.max_new)]
        return []

    serve = Job(
        "serve",
        step_fn=make_continuous_serve_step(backend.engine,
                                           next_requests=feed),
        state={"step": 0, "completed": 0},
        params=SchedParams(weight=256, boost_on_wake=True),
        max_steps=sz.colo_serve_ticks)
    part.add_job(train)
    part.add_job(serve)
    s.mem("co-resident, before run")
    t0 = time.perf_counter()
    quanta = part.run()
    s.say(f"  part.run(): {quanta} quanta in "
          f"{time.perf_counter() - t0:.1f}s")
    s.mem("co-resident, after run")
    for job in (train, serve):
        require(job.error is None, f"{job.name} job failed: {job.error}")
        c = ledger_counters(part, job)
        ticks = fb.state_of(job).ticks
        s.say(f"  {job.name}: steps={c['STEPS_RETIRED']} device_ms="
              f"{c['DEVICE_TIME_NS'] / 1e6:.1f} tokens={c['TOKENS']} "
              f"policy_ticks={ticks} tslice_us: "
              f"{log.trajectory(job.name)}")
        require(c["STEPS_RETIRED"] > 0, f"{job.name} retired no step")
        require(c["DEVICE_TIME_NS"] > 0, f"{job.name} has no device time")
        require(ticks > 0, f"the policy never ticked for {job.name}")
    require(all(np.isfinite([float(x) for x in losses])),
            "co-resident train loss not finite")
    st = backend.engine.stats()
    s.say(f"  engine: completed={st['completed']} tokens_emitted="
          f"{st['tokens_emitted']}")
    require(st["completed"] > 0, "the serving tenant completed no request")
    s.require_clean(be)


def leg_four_chip(s: Smoke) -> None:
    """dp2 x tp2 training and tp=4 serving at full width on one host of
    four chips: do all four devices hold shards and do work?"""
    from pbs_tpu.parallel import batch_sharding, make_mesh, make_sharded_train

    cfg = s.sz.cfg
    devices = jax.devices()
    require(len(devices) >= 4, f"--four-chip needs 4 devices, found "
            f"{len(devices)}")
    devices = devices[:4]

    def holders(tree) -> set:
        return {sh.device for leaf in jax.tree.leaves(tree)
                for sh in leaf.addressable_shards}

    mesh = make_mesh({"dp": 2, "tp": 2}, devices=devices)
    t0 = time.perf_counter()
    state, step = make_sharded_train(cfg, mesh, learning_rate=3e-4)
    tokens = jax.device_put(
        jax.random.randint(jax.random.PRNGKey(1), (4, s.sz.seq), 0,
                           cfg.vocab, jnp.int32), batch_sharding(mesh))
    state, m = step(state, tokens)
    loss = float(m["loss"])
    s.say(f"  make_sharded_train dp2 x tp2, batch 4x{s.sz.seq}: loss="
          f"{loss:.4f} ({time.perf_counter() - t0:.1f}s incl. compile); "
          f"params on {len(holders(state[0]))} devices")
    require(np.isfinite(loss), "sharded train loss not finite")
    require(holders(state[0]) == set(devices),
            "train params do not span the four devices")
    s.mem("four-chip train")
    del state, m, tokens

    t0 = time.perf_counter()
    backend = make_backend(s, tp=4)
    gw = make_gateway(backend)
    r = gw.submit("smoke", {"prompt": prompt_of(cfg, 9),
                            "max_new": s.sz.max_new})
    require(r.admitted, f"request shed: {r.reason}")
    done = []
    while gw.busy():
        done += gw.tick()
    s.say(f"  ShardedServeBackend tp=4: {len(done)} completion, tokens="
          f"{[info['tokens'] for _, info in done]} "
          f"({time.perf_counter() - t0:.1f}s incl. compile); params on "
          f"{len(holders(backend.engine.params))} devices, kv cache on "
          f"{len(holders(backend.engine.cache['k']))}")
    require(len(done) == 1 and done[0][1]["tokens"] == s.sz.max_new,
            "the tp=4 request did not complete with max_new tokens")
    require(holders(backend.engine.params) == set(devices),
            "serve params do not span the four devices")
    s.mem("four-chip serve")


LEGS = {"kernels": leg_kernels, "train": leg_train, "serve": leg_serve,
        "co-resident": leg_colo}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny sizes on whatever platform JAX has; "
                         "every line is marked, nothing is a result")
    ap.add_argument("--four-chip", action="store_true",
                    help="run the four-chip leg instead of the "
                         "one-chip legs")
    ap.add_argument("--legs", default=",".join(LEGS),
                    help="comma-separated subset of: " + ", ".join(LEGS))
    args = ap.parse_args(argv)

    cache_dir = setup_compilation_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearsal:
        # With JAX_PLATFORMS unset and no chip, JAX falls back to the
        # CPU without a word; so the check is explicit.
        print(f"chip_smoke: JAX's default device is platform="
              f"{dev.platform} ({dev.device_kind}), not a TPU; run it "
              "through the chip tool (or ask for --rehearsal)",
              file=sys.stderr)
        return 2

    s = Smoke(rehearsal_sizes() if args.rehearsal else full_sizes(),
              args.rehearsal)
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    s.say(f"device: platform={dev.platform} device_kind="
          f"{dev.device_kind} count={len(jax.devices())}")
    s.say(f"versions: jax={jax.__version__} jaxlib="
          f"{importlib.metadata.version('jaxlib')} libtpu={libtpu}")
    s.say(f"compile cache: {cache_dir}")
    s.say(f"native tier: {native_info()}")
    s.say(f"model: d_model={s.sz.cfg.d_model} n_layers="
          f"{s.sz.cfg.n_layers} vocab={s.sz.cfg.vocab} params="
          f"{s.sz.cfg.num_params() / 1e6:.0f}M")

    if args.four_chip:
        legs = {"four-chip": leg_four_chip}
    else:
        names = [n for n in args.legs.split(",") if n]
        unknown = [n for n in names if n not in LEGS]
        if unknown:
            ap.error(f"unknown legs {unknown}; known: {list(LEGS)}")
        legs = {n: LEGS[n] for n in names}

    failed = []
    for name, leg in legs.items():
        s.say(f"== leg {name} ==")
        before = cache_counts()
        t0 = time.perf_counter()
        try:
            leg(s)
            verdict = "PASS"
        except Exception as e:  # noqa: BLE001 — reported; the run fails
            verdict = "FAIL"
            failed.append(name)
            s.say(traceback.format_exc())
            # The traceback's frames hold the leg's device arrays.
            traceback.clear_frames(e.__traceback__)
        after = cache_counts()
        gc.collect()
        s.say(f"== leg {name}: {verdict} in "
              f"{time.perf_counter() - t0:.1f}s; persistent cache hits="
              f"{after['hits'] - before['hits']} misses="
              f"{after['misses'] - before['misses']} ==")

    summary = {"ok": not failed, "device": device}
    if failed:
        summary["failed_legs"] = failed
    if args.rehearsal:
        summary["rehearsal"] = True
    s.say()
    s.say(json.dumps(summary))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
