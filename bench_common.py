"""Shared bits for the repo-root bench scripts.

Every script is one process (a chip belongs to one process at a time),
needs a TPU unless its tiny rehearsal mode was asked for, names the
device in every row it prints, and takes its peaks from the package's
table (``pbs_tpu.telemetry.peaks``), never from a constant of its own.
"""

from __future__ import annotations


def parse_mu_dtype(raw: str | None):
    """One parser for the PBST_*_MU_DTYPE knobs -> (mu_dtype, label).

    Accepts bf16/bfloat16 and f32/fp32/float32 (or empty/None for the
    default); raises ValueError on anything else so a typo fails in
    milliseconds, before any backend touch. Import of jax.numpy is
    deferred so calling this costs nothing pre-init."""
    key = (raw or "").strip().lower()
    if key in ("bf16", "bfloat16"):
        import jax.numpy as jnp

        return jnp.bfloat16, "bf16"
    if key in ("", "f32", "fp32", "float32"):
        return None, "f32"
    raise ValueError(f"mu_dtype {raw!r} unknown; expected bf16/bfloat16 "
                     "or f32/fp32/float32")


def bench_device(rehearsal: bool) -> dict:
    """Set up the compile cache, touch the backend, and return the
    device fields every row carries. Without a TPU this exits non-zero
    — a measurement path does not fall back to the CPU — unless the
    script's tiny rehearsal mode was asked for, and then every row says
    ``rehearsal`` and names the platform it ran on."""
    import jax

    from pbs_tpu.utils.compile_cache import setup_compilation_cache

    setup_compilation_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not rehearsal:
        raise SystemExit(
            f"bench: JAX's default device is platform={dev.platform} "
            f"({dev.device_kind}), not a TPU; run through the chip tool "
            "(the *_TINY env knobs rehearse the harness on the CPU)")
    fields = {"platform": dev.platform, "device_kind": dev.device_kind,
              "device_count": len(jax.devices())}
    if rehearsal:
        fields["rehearsal"] = True
    return fields


def metric_name(name: str, device: dict) -> str:
    """A rehearsal's number never goes under the device metric's name."""
    return f"rehearsal_{name}" if device.get("rehearsal") else name


def mfu(tokens_per_s: float, flops_per_token: float, device: dict
        ) -> float | None:
    """Model FLOP/s utilization against the chip's published bf16 peak;
    None off a TPU (a utilization of a chip the run was not on would be
    a CPU number under a device metric's name)."""
    if device["platform"] != "tpu":
        return None
    from pbs_tpu.telemetry.peaks import device_peaks

    return tokens_per_s * flops_per_token / device_peaks().flops
