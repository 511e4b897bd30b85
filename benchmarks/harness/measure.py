"""The result line: end-to-end metrics from the benchmark's own stamps
and clock, per-layer metrics through their readers, the device as JAX
reports it."""

from __future__ import annotations

import dataclasses
import functools
import json
import os
import shutil
import tempfile

from . import reduce, trace


@dataclasses.dataclass
class Context:
    """What a per-layer reader may look at."""

    family: object          # the configuration's family module
    config: dict
    traffic: dict
    device_kind: str
    t0: float
    t1: float
    requests: list          # Book.requests (stamps), [] without serving
    ticks: list             # Book.ticks
    train_steps: int        # train steps retired in the window
    train_tokens_per_step: int
    events: list | None     # reduced trace events (--trace 1)
    trace_span: tuple | None  # (host t0, host t1) of the traced part
    ledger_trace: dict      # job -> counter deltas over the traced part
    backlog: tuple | None   # gateway queue depth at (t0, t1)
    rehearsal: bool = False

    @functools.cached_property
    def programs(self) -> list:
        """Executions of compiled programs in the trace, reduced once."""
        return trace.programs(self.events) if self.events else []


class Tracer:
    """The profiler around the last ``seconds`` of a window."""

    def __init__(self, seconds: float, describe: bool = False):
        self.seconds = seconds
        self.description = [] if describe else None
        self.events: list | None = None
        self._dir = None

    def start(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # the bench.* spans are TraceMes
        self._dir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(self._dir, profiler_options=opts)

    def stop(self) -> None:
        import jax

        jax.profiler.stop_trace()
        try:
            self.events = trace.load_xplane(self._dir)
            if self.description is not None:
                self.description = trace.describe(self._dir)
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)


def end_to_end(ctx: Context, names, setup_s: float) -> dict:
    window = ctx.t1 - ctx.t0
    out = {}
    for name in names:
        if name == "setup_s":
            out[name] = setup_s
        elif name == "train_tokens_per_s":
            out[name] = ctx.train_steps * ctx.train_tokens_per_step / window
        elif name == "output_tokens_per_s":
            out[name] = sum(reduce.tokens_in(r["stamps"], ctx.t0, ctx.t1)
                            for r in ctx.requests) / window
        elif name in ("tpot_p95_ms", "ttft_p95_ms"):
            out[name] = reduce.percentile(reduce.quantity(
                ctx.requests, name.replace("_p95", ""), ctx.t0, ctx.t1), 95)
        else:
            raise KeyError(f"no arithmetic for end-to-end metric {name!r}")
    return out


def per_layer(spec, ctx: Context, metrics) -> dict:
    out = {}
    for m in metrics:
        mf = spec.metric_file(m["name"])
        value = spec.reader(mf["reader"])(ctx, **mf.get("args", {}))
        if value is not None:
            out[m["name"]] = float(value)
    return out


def breakdown(ctx: Context) -> dict:
    idle = sorted(trace.idle_by_annotation(ctx.events).items(),
                  key=lambda kv: -kv[1])[:10]
    return {"device_ops": trace.top_ops(ctx.events, ctx.programs, 10),
            "idle_gaps": [[k, v / 1e9] for k, v in idle]}


def device_block(ctx: Context | None) -> dict:
    import jax

    devs = jax.devices()
    peak = 0
    for d in jax.local_devices():
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    out = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs), "memory_peak_bytes": peak}
    if ctx is not None and ctx.events is not None:
        out["busy_s"] = trace.busy_seconds(ctx.events)
        out["window_s"] = ctx.trace_span[1] - ctx.trace_span[0]
    return out


def summary(ctx: Context, ten) -> str:
    """One line of what the window looked like, whatever the cell
    reports: for the builder's sweeps and for a reader of the log."""
    parts = []
    if ctx.requests:
        window = ctx.t1 - ctx.t0
        toks = sum(reduce.tokens_in(r["stamps"], ctx.t0, ctx.t1)
                   for r in ctx.requests)
        parts.append(f"output {toks / window:.1f} tok/s")
        for q in ("tpot_ms", "ttft_ms", "queue_wait_ms", "token_gap_ms"):
            vals = reduce.quantity(ctx.requests, q, ctx.t0, ctx.t1)
            if vals:
                parts.append(f"{q} p50 {reduce.percentile(vals, 50):.2f} "
                             f"p95 {reduce.percentile(vals, 95):.2f} "
                             f"p99 {reduce.percentile(vals, 99):.2f} "
                             f"(n={len(vals)})")
    if ctx.backlog is not None:
        parts.append(f"gateway queue {ctx.backlog[0]} -> {ctx.backlog[1]}")
    late = getattr(ten, "late_s", None)
    if late:
        parts.append(f"generator late p95 "
                     f"{reduce.percentile(late, 95) * 1e3:.3f} ms")
    return "; ".join(parts)


def units(spec, kind: str) -> dict:
    return {m["name"]: m["unit"] for m in spec.bench[kind]}


def dump(stem: str, ctx: Context, ten, tracer) -> None:
    """Every stamp of the run (and the start of its trace) as files, for
    the builder's studies (``tools/steadiness.py``)."""
    os.makedirs(os.path.dirname(stem), exist_ok=True)
    steps = ten.trainer.step_times if ten.trainer else []
    with open(stem + ".json", "w") as f:
        json.dump({"t0": ctx.t0, "t1": ctx.t1, "settle": ten.settle,
                   "train_steps": ctx.train_steps,
                   "train_tokens_per_step": ctx.train_tokens_per_step,
                   "train_step_times": [t for t in steps if t >= ctx.t0],
                   "requests": [{k: r[k] for k in
                                 ("due", "admit", "stamps", "done")}
                                for r in ctx.requests],
                   "ticks": ctx.ticks}, f)
    if tracer is not None:
        w0 = min(e["start"] for e in tracer.events)
        with open(stem + ".trace.json", "w") as f:
            json.dump([e for e in tracer.events
                       if e["start"] < w0 + 300_000_000], f)
        with open(stem + ".planes.txt", "w") as f:
            f.write("\n".join(tracer.description))
