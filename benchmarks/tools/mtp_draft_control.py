"""How close the program's drafts lie to the reference's, at the
published widths on the chip: ``correct`` cannot see the drafting block
(a draft that the stack's own argmax does not confirm is never served,
so a wrong drafting block costs acceptance and no token), this tool and
the CPU tests do.

It builds the cell's own engine (``family.serve_backend`` over
``ContinuousBatcher``: the programs the cell runs, at its lanes and
cache), serves a few seeded requests with settled ticks, and after every
tick records what each lane holds on the device: its cursor, the last
token it emitted and the token drafted to follow it (``cache["dr"]``:
the prompt forward's first draft, then every tick's). Then the family's
float32 reference (``draft_scores``) over each request's prompt and
served tokens: the number is how far the logit of a recorded draft lies
below the reference's best draft logit at its position (widest and
mean), the drafting block's ``served_gap``. Beside it the control, as
``harness/check.py`` reads it for served tokens: the token the
reference puts first when every weight product is int8, scored the same
way. The program's gap should read like the cell's own
``served_gap_mean`` (bfloat16 against float32) and well under the
control's.

    python3 benchmarks/tools/mtp_draft_control.py [config] [--seed N]
        [--requests 8] [--prompt 384] [--new 48] [--rehearsal]

On a CPU it runs with ``--rehearsal`` only (the tiny preset: a smoke of
the tool, not a reading).
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.harness.spec import Spec  # noqa: E402

PAD_TO = 128


def record_drafts(engine, prompts, new: int) -> list[dict]:
    """Serve ``prompts`` for ``new`` tokens each and return, a request,
    its served tokens and its drafts as ``{position: token}``: the
    draft at position ``i`` was computed from the pair ``(h_i,
    t_{i+1})`` and predicts ``t_{i+2}``."""
    rids = {engine.submit(p, new): i for i, p in enumerate(prompts)}
    out = [{"prompt": list(map(int, p)), "tokens": None, "drafts": {}}
           for p in prompts]
    while engine.has_work():
        done = engine.step_settled()
        pos, dr = (np.asarray(engine.cache[k]) for k in ("pos", "dr"))
        for slot, rid in enumerate(engine.slot_req):
            if rid in rids:
                # the cursor is the last emitted token's position: the
                # draft behind it came from the position before
                out[rids[rid]]["drafts"][int(pos[slot]) - 1] = int(dr[slot])
        for comp in done:
            out[rids[comp.request_id]]["tokens"] = list(map(int, comp.tokens))
    return out


def readings(ref, c: dict, seed: int, records) -> dict:
    sv = c["serve"]
    longest = max(len(r["prompt"]) + len(r["tokens"]) for r in records)
    S = -(-longest // PAD_TO) * PAD_TO
    tokens = np.zeros((len(records), S), np.int32)
    rows, cols, drafts = [], [], []
    for b, r in enumerate(records):
        seq = r["prompt"] + r["tokens"]
        tokens[b, :len(seq)] = seq
        for at, tok in sorted(r["drafts"].items()):
            if at + 1 < len(seq):   # the pair's token was served
                rows.append(b), cols.append(at), drafts.append(tok)
    rows, cols, drafts = (np.asarray(a, np.int32)
                          for a in (rows, cols, drafts))
    args = (c, seed, sv["num_hidden_layers"], jnp.dtype(sv["weights_dtype"]),
            tokens, rows, cols)
    _, control, _ = ref.draft_scores(*args, drafts[None], quant=True)
    best, arg, picked = ref.draft_scores(
        *args, np.stack([drafts, np.asarray(control, np.int32)]))
    gaps = best[None, :] - picked
    return {"draft_positions": len(drafts),
            "drafts_equal_to_the_references": float(np.mean(arg == drafts)),
            "draft_gap_max": float(gaps[0].max()),
            "draft_gap_mean": float(gaps[0].mean()),
            "control_draft_gap_max": float(gaps[1].max()),
            "control_draft_gap_mean": float(gaps[1].mean())}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("config", nargs="?", default="deepseek-v3")
    ap.add_argument("--seed", type=int, default=5000800021)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt", type=int, default=384)
    ap.add_argument("--new", type=int, default=48)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if not args.rehearsal and dev.platform != "tpu":
        raise SystemExit("mtp_draft_control.py reads the program at the "
                         "published widths on a TPU (--rehearsal runs the "
                         "tiny preset on anything)")
    from benchmarks.harness.build import Server

    spec = Spec()
    c = spec.config(args.config)
    if args.rehearsal:
        from benchmarks.run import overlay

        c = overlay(c, c["rehearsal"])
        args.prompt = min(args.prompt, c["serve"]["prompt_bucket"] // 2)
        args.new = min(args.new, 16)
    fam = spec.family(c["family"])
    server = Server(fam, c, args.seed)
    rng = np.random.default_rng([args.seed, 11])
    prompts = [rng.integers(0, c["vocab_size"], n).astype(np.int32)
               for n in rng.integers(args.prompt // 2, args.prompt + 1,
                                     args.requests)]
    records = record_drafts(server.engine, prompts, args.new)
    st = server.engine.stats()
    print(f"served {len(records)} requests of {args.new} tokens on "
          f"{dev.device_kind}: drafts proposed {st['drafts_proposed']}, "
          f"accepted {st['drafts_accepted']}", flush=True)
    del server
    limit = c["check"]["serving"]["served_gap_mean"]
    for name, value in readings(fam.reference, c, args.seed,
                                records).items():
        print(f"check-reading {name}: {value}"
              + (f" (the cell's served_gap_mean limit {limit})"
                 if name.endswith("gap_mean") else ""), flush=True)


if __name__ == "__main__":
    main()
