"""Whether ``check.serving.served_gap_mean`` would catch a Mamba-2
block whose state is not held in the precision the configuration states
(float32; NVIDIA's serving recipe for Nemotron-3-Nano asks for a
float32 state cache): the family's float32 reference beside the same
reference with the state ``H`` held in bfloat16 between tokens
(``state``), every product float32, over the same seeded rows of tokens.
Beside it the harness's own control (every matrix product in int8),
which a run's ``--control 1`` reads over served tokens, so that the two
kinds of reading can be laid side by side. Nothing of the program
(``pbs_tpu``) runs.

    python3 benchmarks/tools/mamba2_state_control.py [config] [--seed N]

Printed, a control each: ``control_gap_max`` / ``control_gap_mean`` as
``harness/check.py`` reads them (how far the token the control puts
first lies below the float32 reference's best, at every position), and
the configuration's limit.
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.harness.spec import Spec  # noqa: E402

CONTROLS = {"int8": True, "state": "state"}


def readings(ref, c: dict, seed: int, tokens) -> dict:
    """``{control: (gap max, gap mean)}`` over every position of
    ``tokens`` (B, S)."""
    sv = c["serve"]
    B, S = tokens.shape
    rows, cols = np.repeat(np.arange(B), S), np.tile(np.arange(S), B)
    args = (c, seed, sv["num_hidden_layers"], jnp.dtype(sv["weights_dtype"]),
            tokens, rows.astype(np.int32), cols.astype(np.int32))
    none = np.zeros((1, B * S), np.int32)
    picks = np.stack([
        np.asarray(ref.score_tokens(*args, none, quant=q)[1], np.int32)
        for q in CONTROLS.values()])
    best, _, picked = ref.score_tokens(*args, picks)
    gaps = best[None, :] - picked
    return {name: (float(g.max()), float(g.mean()))
            for name, g in zip(CONTROLS, gaps)}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("config", nargs="?", default="nemotron-3-nano-30b-a3b")
    ap.add_argument("--seed", type=int, default=4300800043)
    ap.add_argument("--rows", type=int, default=4)
    ap.add_argument("--len", type=int, default=2048)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)
    spec = Spec()
    c = spec.config(args.config)
    if args.rehearsal:
        from benchmarks.run import overlay

        c = overlay(c, c["rehearsal"])
    ref = spec.family(c["family"]).reference
    tokens = np.random.default_rng([args.seed, 9]).integers(
        0, c["vocab_size"], (args.rows, args.len)).astype(np.int32)
    limit = c["check"]["serving"]["served_gap_mean"]
    for name, (widest, mean) in readings(ref, c, args.seed, tokens).items():
        print(f"check-reading {name} control_gap_max: {widest}")
        print(f"check-reading {name} control_gap_mean: {mean} (limit "
              f"{limit}) {'caught' if mean > limit else 'NOT caught'}",
              flush=True)


if __name__ == "__main__":
    main()
