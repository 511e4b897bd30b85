"""One cell, once.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in ``BENCHMARK.json`` and its configuration (with the
family it names), traffic mix and per-layer metrics by name under
``benchmarks/``; builds the tenants from ``--seed``; warms up (all of
that is ``setup_s``); measures for ``--seconds``; then frees the program
and checks what the timed path produced against the float32 reference.
The last line of standard output is the result object; each number the
check compared stands beside its limit in its last key, ``check``, and
in the last lines of standard error. Without a TPU (or with fewer chips than
the cell asks for) it exits non-zero and prints no result; ``--rehearsal``
runs the cell's tiny preset on whatever JAX has and prefixes every
metric with ``rehearsal_``.

``--control 1`` also reads the check's numbers for the reference in int8
(the lower precision that has to fail); ``--dump DIR`` writes every
token stamp; ``--set traffic.key=value`` overrides a traffic parameter.
Those three are the builder's study tools; the driver uses none.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def overlay(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = overlay(base[k], v) if isinstance(v, dict) and \
            isinstance(base.get(k), dict) else v
    return out


def apply_sets(traffic: dict, sets) -> dict:
    for item in sets:
        path, _, raw = item.partition("=")
        keys = path.split(".")
        if keys[0] != "traffic":
            raise SystemExit(f"--set handles traffic.* only, got {item!r}")
        node = traffic
        for k in keys[1:-1]:
            node = node[k]
        node[keys[-1]] = json.loads(raw)
    return traffic


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dump")
    ap.add_argument("--set", action="append", default=[])
    args = ap.parse_args(argv)

    import jax

    from benchmarks.harness import check, measure, reduce
    from benchmarks.harness.spec import Spec
    from benchmarks.harness.tenancy import KINDS
    from pbs_tpu.utils.compile_cache import setup_compilation_cache

    spec = Spec()
    cell = spec.cell(args.workload)
    dev = jax.devices()[0]
    if not args.rehearsal and (dev.platform != "tpu"
                               or len(jax.devices()) < cell["chips"]):
        print(f"benchmarks/run.py: {args.workload} needs {cell['chips']} "
              f"TPU chip(s); JAX has {len(jax.devices())} x "
              f"platform={dev.platform}. No CPU fallback "
              "(--rehearsal runs the tiny preset).", file=sys.stderr)
        return 3
    setup_compilation_cache()

    config, traffic = spec.config(cell["config"]), spec.traffic(cell["traffic"])
    if args.rehearsal:
        config = overlay(config, config["rehearsal"])
        traffic = overlay(traffic, traffic.get("rehearsal", {}))
    traffic = apply_sets(traffic, args.set)
    prefix = "rehearsal_" if args.rehearsal else ""

    family = spec.family(config["family"])
    ten = KINDS[traffic["tenancy"]](family, config, traffic, args.seed,
                                    args.seconds)
    ten.setup()
    setup_s = time.monotonic() - T_START
    print(f"setup {setup_s:.2f}s phases={ten.phases} settle={ten.settle}",
          flush=True)

    tracer = measure.Tracer(float(traffic["trace_s"]),
                            bool(args.dump)) if args.trace else None
    ten.window(args.seconds, tracer)

    book = ten.server.book if ten.server is not None else None
    ctx = measure.Context(
        family=family, config=config, traffic=traffic, device_kind=dev.device_kind,
        t0=ten.t0, t1=ten.t1,
        requests=book.requests if book else [],
        ticks=book.ticks if book else [],
        train_steps=ten.train_steps_window,
        train_tokens_per_step=(ten.trainer.tokens_per_step
                               if ten.trainer else 0),
        events=tracer.events if tracer else None,
        trace_span=(ten.trace_t0, ten.trace_t1) if tracer else None,
        ledger_trace=ten.ledger_trace,
        backlog=getattr(ten, "backlog", None), rehearsal=args.rehearsal)
    kind = "per_layer" if args.trace else "end_to_end"
    wanted = spec.metrics_of(args.workload, kind)
    if args.trace:
        values = measure.per_layer(spec, ctx, wanted)
    else:
        values = measure.end_to_end(ctx, [m["name"] for m in wanted], setup_s)
    unit = measure.units(spec, kind)
    device = measure.device_block(ctx)
    breakdown = measure.breakdown(ctx) if tracer else None

    # Operations: requests that were due (or sent) in the window, and
    # train steps. A request fails if it was shed, or if the mix drains
    # its window and the request still had no token when the drain
    # gave up. In a mix above the knee the queue grows by design, and a
    # request still waiting when the window closes has not failed.
    in_window = [r for r in ctx.requests if ctx.t0 <= r["due"] < ctx.t1]
    drained = traffic.get("serve", {}).get("drain_s", 0) > 0
    failed = sum(1 for r in in_window
                 if r["shed"] or (drained and not r["stamps"]))
    attempted = len(in_window) + ctx.train_steps
    completed = reduce.completed_in(ctx.requests, ctx.t0, ctx.t1)
    print(f"window {ctx.t1 - ctx.t0:.2f}s: {len(in_window)} requests due, "
          f"{len(completed)} completed, {failed} failed, "
          f"{ctx.train_steps} train steps; " + measure.summary(ctx, ten),
          flush=True)
    if args.dump:
        measure.dump(os.path.join(args.dump, f"{args.workload}.{args.seed}"),
                     ctx, ten, tracer)

    # The check, in the memory the program gives back.
    first_steps = ten.first_steps
    sample = check.pick_sample(ctx.requests, ctx.t0, ctx.t1, args.seed)
    ten.free()
    del ctx, book, tracer
    t_check = time.monotonic()
    correct, compared, lines = check.run(
        family.reference, config, traffic, args.seed, first_steps, sample,
        bool(args.control))
    print(f"check took {time.monotonic() - t_check:.2f}s", flush=True)

    result = {
        "correct": bool(correct),
        "attempted": attempted, "failed": failed,
        "metrics": {prefix + k: {"value": v, "unit": unit[k]}
                    for k, v in values.items()},
        "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    # Each number compared beside its limit: the last key of the result
    # line and the last lines of standard error.
    result["check"] = compared
    sys.stdout.flush()
    print("\n".join(lines), file=sys.stderr, flush=True)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
