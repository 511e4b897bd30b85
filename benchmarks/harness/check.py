"""What decides ``correct``: the timed path's own output against the
plain reference, each number beside its limit (the limits, and the
readings they were set from, are in the configuration file and PERF.md).
The reference is the configuration's family's (``family.reference``).

Serving: a seeded sample of the requests the window finished, the
longest among them; the reference runs once over each prompt with its
served tokens, and the number is how far a served token's logit lies
below the reference's best at its position (widest and mean). Greedy
tokens only, which is what the traffic sends.

Training: the first three steps went through the window's own partition
and feed during set-up; the reference follows them: each step's loss,
the first gradient's norm and the parameters' change, by the worst leaf,
and the first gradient itself through a fixed random sketch (the norms
move only in the second order with rounding noise and cannot tell bf16
from int8; the sketch can).
"""

from __future__ import annotations

import numpy as np

SAMPLE = 8
PAD_TO = 256


def pick_sample(requests, t0: float, t1: float, seed: int) -> list[dict]:
    done = [r for r in requests
            if r["done"] is not None and t0 <= r["done"] < t1]
    if not done:
        return []
    longest = max(done, key=lambda r: len(r["prompt"]) + len(r["tokens"]))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([seed, 4])
    idx = rng.permutation(len(rest))[:SAMPLE - 1]
    return [longest] + [rest[i] for i in idx]


def serving_readings(ref, c: dict, seed: int, sample, max_new: int,
                     control: bool = False) -> dict:
    """Gaps below the best logit of ``ref`` (the family's reference): of
    the served tokens, and with ``control`` of the tokens the int8
    reference puts first."""
    import jax.numpy as jnp

    sv = c["serve"]
    dtype = jnp.dtype(sv["weights_dtype"])
    n_layers = sv["num_hidden_layers"]
    longest = max(len(r["prompt"]) + len(r["tokens"]) - 1 for r in sample)
    S = -(-longest // PAD_TO) * PAD_TO
    tokens = np.zeros((SAMPLE, S), np.int32)
    n_pos = SAMPLE * max_new
    rows, cols = np.zeros(n_pos, np.int32), np.zeros(n_pos, np.int32)
    served, valid = np.zeros(n_pos, np.int32), np.zeros(n_pos, bool)
    k = 0
    for b, r in enumerate(sample):
        seq = list(r["prompt"]) + list(r["tokens"][:-1])
        tokens[b, :len(seq)] = seq
        for i, tok in enumerate(r["tokens"]):
            rows[k], cols[k] = b, len(r["prompt"]) - 1 + i
            served[k], valid[k] = tok, True
            k += 1
    cand = [served]
    if control:
        _, ctrl_tok, _ = ref.score_tokens(
            c, seed, n_layers, dtype, tokens, rows, cols,
            np.stack(cand), quant=True)
        cand.append(np.asarray(ctrl_tok, np.int32))
    best, _, picked = ref.score_tokens(
        c, seed, n_layers, dtype, tokens, rows, cols, np.stack(cand))
    gaps = (best[None, :] - picked)[:, valid]
    out = {"served_positions": int(valid.sum()),
           "served_gap_max": float(gaps[0].max()),
           "served_gap_mean": float(gaps[0].mean())}
    if control:
        out["control_gap_max"] = float(gaps[1].max())
        out["control_gap_mean"] = float(gaps[1].mean())
    return out


def _worst_leaf(prog: dict, want: dict) -> float:
    """The widest gap between the program's norm and the reference's,
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger (some gradients are all but zero)."""
    median = float(np.median(list(want.values())))
    return max(abs(prog[k] - want[k]) / max(want[k], median) for k in want)


def _sketch_gap(prog: dict, want: dict, norms: dict) -> float:
    """The worst leaf's estimated norm of (program's gradient minus the
    reference's), from their sketches, against that leaf's norm or the
    median leaf's, whichever is larger."""
    median = float(np.median(list(norms.values())))
    return max(float(np.sqrt(np.mean(np.square(prog[k] - want[k]))))
               / max(norms[k], median) for k in want)


def training_readings(ref, c: dict, seed: int, first_steps: dict,
                      control: bool = False) -> dict:
    tr = c["train"]

    def against(want: dict, got: dict, tag: str) -> dict:
        return {
            tag + "loss_gap": max(abs(a - b) / abs(b) for a, b in
                                  zip(got["loss"], want["loss"])),
            tag + "grad_norm_gap": _worst_leaf(got["grad_norm"],
                                               want["grad_norm"]),
            tag + "grad_sketch_gap": _sketch_gap(
                got["grad_sketch"], want["grad_sketch"], want["grad_norm"]),
            tag + "dparam_norm_gap": _worst_leaf(got["dparam_norm"],
                                                 want["dparam_norm"])}

    want = ref.train_readings(c, seed, tr["num_hidden_layers"],
                              first_steps["rows"], tr["learning_rate"])
    out = against(want, first_steps, "")
    out["loss_first"] = first_steps["loss"][0]
    if control:
        low = ref.train_readings(c, seed, tr["num_hidden_layers"],
                                 first_steps["rows"], tr["learning_rate"],
                                 quant=True)
        out.update(against(want, low, "control_"))
    return out


def run(ref, config: dict, traffic: dict, seed: int,
        first_steps: dict | None, sample: list, control: bool
        ) -> tuple[bool, dict, list[str]]:
    """Read every number the cell's tenants give and say whether all of
    them hold: ``(correct, {name: {"value", "limit"}}, lines)``, each
    line a number beside its limit."""
    limits, readings = {}, {}
    if first_steps is not None:
        limits.update(config["check"]["training"])
        readings.update(training_readings(ref, config, seed, first_steps,
                                          control))
    if "serve" in traffic:
        limits.update(config["check"]["serving"])
        if sample:
            readings.update(serving_readings(
                ref, config, seed, sample,
                int(traffic["serve"]["output_len"]["max"]), control))
    ok, lines = judge(readings, limits)
    for k, v in readings.items():
        if k not in limits:
            print(f"check-reading {k}: {v}")
    compared = {k: {"value": readings.get(k), "limit": limit}
                for k, limit in limits.items()}
    return ok, compared, lines


def judge(readings: dict, limits: dict) -> tuple[bool, list[str]]:
    """Every limited number has to be there and within its limit."""
    ok, lines = True, []
    for name, limit in limits.items():
        value = readings.get(name)
        good = value is not None and value <= limit
        ok = ok and good
        lines.append(f"check {name}: {value} (limit {limit}) "
                     f"{'ok' if good else 'FAIL'}")
    return ok, lines
