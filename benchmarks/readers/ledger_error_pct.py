"""How far the ledger's DEVICE_TIME_NS of a job (host wall around
``block_until_ready``, what the policy schedules on) is from the device
time of that job's programs in the trace, over the traced part."""
from benchmarks.harness import trace


def read(ctx, job: str, match: list):
    if ctx.events is None or job not in ctx.ledger_trace:
        return None
    dev = sum(sum(trace.program_times(ctx.programs, m)) for m in match)
    if dev <= 0:
        return None
    return 100.0 * abs(ctx.ledger_trace[job]["DEVICE_TIME_NS"] - dev) / dev
