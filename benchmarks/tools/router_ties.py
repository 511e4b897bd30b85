"""How far rounding alone moves a model whose router picks a top k of
many near-tied experts: the family's float32 reference beside the same
reference with its residual stream held in bfloat16 (rounded after each
half layer, and the normed input of every product with it; every
product still float32), over the same seeded rows of tokens. Nothing of
the program (``pbs_tpu``) runs: what this reads is the configuration's.

    python3 benchmarks/tools/router_ties.py [config] [--seed N]

Printed: in how many (position, expert layer) pairs the two pick another
set of experts, how many of those swap a held expert for an absent one,
the margin between the last expert chosen and the first one left out,
and ``served_gap_max`` / ``served_gap_mean`` as ``harness/check.py``
would read them if the rounded reference had served its own greedy
tokens: over all positions, and apart for positions where the sets
differ in some layer and where they do not.
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


def forward(ref, c: dict, seed: int, tokens, rounded: bool):
    """Logits (B, S, V) and, an expert layer each, the routing weights
    (B * S, experts_total) and the router's scores' margin (B * S,)."""
    sv = c["serve"]
    dtype = jnp.dtype(sv["weights_dtype"])
    word = ref.seed_word(seed)
    first, held = ref.held_range(c)
    block = min(ref.EXPERT_BLOCK, held)
    k = c["num_experts_per_tok"]
    eps = c["rms_norm_eps"]

    def stream(x):
        return x.astype(jnp.bfloat16).astype(jnp.float32) if rounded else x

    @jax.jit
    def embed(tokens):
        return stream(ref.outer_weights(c, word, dtype)["embed"].astype(
            jnp.float32)[tokens])

    def attn(layer):
        return jax.jit(lambda x: stream(ref.attention(
            c, x, ref._f32(ref.attn_weights(c, word, layer, layer, dtype)),
            layer)))

    @jax.jit
    def dense(x, at):
        w = ref._f32(ref.dense_weights(c, word, at, dtype))
        h = stream(ref.rms_norm(x, w["mlp_norm"], eps))
        return stream(x + ref.swiglu(h, w["w1"], w["w3"], w["w2"], False))

    @jax.jit
    def route(x, at):
        w = ref._f32(ref.sparse_outer_weights(c, word, at, dtype))
        h = stream(ref.rms_norm(x, w["mlp_norm"], eps))
        h = h.reshape(-1, h.shape[-1])
        top = jax.lax.top_k(ref.matmul(h, w["router"], False), k + 1)[0]
        return (h, ref.routing(c, h, w["router"], False),
                ref.swiglu(h, w["ws1"], w["ws3"], w["ws2"], False),
                top[:, k - 1] - top[:, k])

    @jax.jit
    def experts(y, h, gate, at, start):
        wb = ref._f32(ref.expert_block(c, word, at, start, block, dtype))
        g = jax.lax.dynamic_slice_in_dim(gate, start, block, axis=1)
        return y + ref.block_of_experts(h, g, wb, False)

    @jax.jit
    def tail(x):
        outer = ref.outer_weights(c, word, dtype)
        h = stream(ref.rms_norm(x, outer["final_norm"].astype(jnp.float32),
                                eps))
        return ref.matmul(h, outer["head"].astype(jnp.float32), False)

    x, gates, margins = embed(tokens), [], []
    for layer in range(sv["num_hidden_layers"]):
        x = attn(layer)(x)
        if c["mlp_layer_types"][layer] == "dense":
            x = dense(x, layer)
            continue
        h, gate, y, margin = route(x, layer)
        for start in range(first, first + held, block):
            y = experts(y, h, gate, layer, start)
        x = stream(x + y.reshape(x.shape))
        gates.append(np.asarray(gate) > 0)
        margins.append(np.asarray(margin))
    return np.asarray(tail(x)), gates, margins


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("config", nargs="?", default="laguna-s-2.1")
    ap.add_argument("--seed", type=int, default=2800700029)
    ap.add_argument("--rows", type=int, default=8)
    ap.add_argument("--len", type=int, default=768)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)
    spec = Spec()
    c = spec.config(args.config)
    if args.rehearsal:
        from benchmarks.run import overlay

        c = overlay(c, c["rehearsal"])
    ref = spec.family(c["family"]).reference
    first, held = ref.held_range(c)
    tokens = np.random.default_rng([args.seed, 9]).integers(
        0, c["vocab_size"], (args.rows, args.len)).astype(np.int32)
    want, sets, margins = forward(ref, c, args.seed, tokens, False)
    got, low_sets, _ = forward(ref, c, args.seed, tokens, True)

    differ = np.stack([(a != b).any(-1) for a, b in zip(sets, low_sets)])
    across = np.stack([
        (a[:, first:first + held].sum(-1)
         != b[:, first:first + held].sum(-1))
        for a, b in zip(sets, low_sets)])
    pairs = differ.size
    print(f"{args.config} seed {args.seed}: {tokens.size} positions x "
          f"{len(sets)} expert layers = {pairs} pairs; top-"
          f"{c['num_experts_per_tok']} of {sets[0].shape[-1]} differs in "
          f"{differ.sum()} ({100 * differ.mean():.2f}%), of which "
          f"{across.sum()} swap a held expert for an absent one; positions "
          f"with a differing layer: {differ.any(0).sum()} "
          f"({100 * differ.any(0).mean():.2f}%)")
    m = np.concatenate(margins)
    print("margin in logit between the last expert chosen and the first "
          f"left out: p10 {np.percentile(m, 10):.4f} p50 "
          f"{np.percentile(m, 50):.4f}; under 0.01 in "
          f"{100 * (m < 0.01).mean():.2f}% of pairs")
    want, got = (t.reshape(-1, t.shape[-1]) for t in (want, got))
    gap = want.max(-1) - np.take_along_axis(
        want, got.argmax(-1)[:, None], -1)[:, 0]
    flipped = differ.any(0)
    for name, sel in (("all positions", slice(None)),
                      ("positions where a layer's set differs", flipped),
                      ("positions where none does", ~flipped)):
        g = gap[sel]
        if g.size:
            print(f"served_gap of the rounded reference's greedy tokens, "
                  f"{name} ({g.size}): max {g.max():.4f} mean "
                  f"{g.mean():.5f}")


if __name__ == "__main__":
    main()
