"""Gated delta-rule linear attention (KDA) through the slot engine: a
recurrent float32 state a slot beside a softmax layer's keys and values
in one cache, experts behind a sigmoid router, and the plain float32
reference they are held to (``benchmarks/reference/moe_kda_gqa.py``,
which imports nothing of the program and runs the delta rule a token at
a time). Toy sizes, CPU, float32, seeded weights: the configuration
file's own rehearsal preset (hidden 48, 4 heads of 16 in both kinds of
layer over 2 KV heads, kernel 4, 8 experts top-3 with 4 held, layers
softmax, KDA, KDA, KDA).

Tolerances: program and reference both compute in float32, in another
order (a chunked scan and a cached state against a token-by-token scan
over the whole row, sorted grouped products against every expert for
every token), so logits of magnitude ~4 agree to a few float32
roundings: 2e-4 absolute. bfloat16 in place of float32 misses that by
two orders (asserted below).
"""

import copy
import functools
import glob
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness.spec import ROOT, Spec
from benchmarks.run import overlay
from pbs_tpu.models import plan as P
from pbs_tpu.models.moe import (
    held_expert_ffn, route_top_k, shared_expert_ffn)
from pbs_tpu.models.kda import KDA_CHUNK, kda_chunked
from pbs_tpu.models.serving import ContinuousBatcher
from pbs_tpu.models.slot_programs import slot_program
from pbs_tpu.models.spec_serving import SpeculativeBatcher
from pbs_tpu.serve import ShardedServeBackend
from pbs_tpu.serve.partition import (
    PARTITION_RULES, iter_leaf_paths, match_partition_rules)

SEED = 11
TOL = 2e-4
SPEC = Spec()
FAMILY = SPEC.family("moe-kda-gqa")
ref = FAMILY.reference
CELL = "serve-kda-longform-surge"


def toy(first: int = 0, held: int = 4, dtype: str = "float32") -> dict:
    full = SPEC.config("solar-open2-250b")
    c = copy.deepcopy(overlay(full, full["rehearsal"]))
    c["n_routed_experts"], c["deployment"]["experts_first"] = held, first
    c["compute_dtype"] = c["serve"]["weights_dtype"] = dtype
    return c


MAX_LEN, BUCKET, SLOTS, ROW = 48, 24, 3, 40


@functools.lru_cache(maxsize=None)
def program(dtype: str = "float32"):
    """The toy model's configuration, weights and the two programs the
    engine makes of it (jitted once for the module)."""
    c = toy(dtype=dtype)
    n = c["serve"]["num_hidden_layers"]
    cfg = FAMILY.program_config(c, n, MAX_LEN)
    params = jax.jit(lambda s: ref.init_tree(
        c, s, n, jnp.dtype(dtype)))(ref.seed_word(SEED))
    prog = slot_program(cfg)

    @jax.jit
    def decode(params, cache, last_tok, active):
        logits, new, _, route = prog.decode(params, cache, last_tok, active)
        new["pos"] = cache["pos"] + active.astype(jnp.int32)
        return logits[:, 0], new, route

    return cfg, params, prog, jax.jit(prog.ingest), decode


@functools.lru_cache(maxsize=None)
def tokens_and_reference():
    """Three rows of ROW tokens and the reference's full forward over
    them: (B, S, V) logits (causal, so a row's prefix reads the same)."""
    c = toy()
    tokens = np.random.default_rng(SEED).integers(
        1, c["vocab_size"], (SLOTS, ROW)).astype(np.int32)
    B, S, V = SLOTS, ROW, c["vocab_size"]
    rows, cols = (a.reshape(-1) for a in np.mgrid[:B, :S])
    cand = np.tile(np.arange(V, dtype=np.int32)[:, None], (1, B * S))
    _, _, picked = ref.score_tokens(
        c, SEED, c["serve"]["num_hidden_layers"], jnp.float32, tokens,
        rows.astype(np.int32), cols.astype(np.int32), cand)
    return tokens, picked.T.reshape(B, S, V)


def padded(tokens, plen: int, rows: int = BUCKET):
    out = np.zeros(rows, np.int32)
    out[:plen] = tokens[:plen]
    return jnp.asarray(out)


# -- state and cache against the full forward ---------------------------------


def served_logits(dtype, tokens, plens, admit_at, length):
    """Teacher-forced serving of the first ``length`` tokens of each row
    of ``tokens``: slot b is given its prompt (``plens[b]`` tokens) at
    tick ``admit_at[b]`` and then decodes the rest, one position a tick,
    beside whatever else is in the cache (lanes not yet admitted ride
    along inactive). Returns ``{(b, position): logits}`` for the
    prompt's last position and every decoded one."""
    _cfg, params, prog, ingest, decode = program(dtype)
    B = len(tokens)
    cache = prog.init_cache(B, MAX_LEN)
    out, pos, active = {}, np.zeros(B, np.int64), np.zeros(B, bool)
    for tick in range(max(admit_at) + length):
        for b in range(B):
            if admit_at[b] == tick:
                last, cache, _, _ = ingest(
                    params, cache, b, padded(tokens[b], plens[b]), plens[b])
                out[b, plens[b] - 1] = np.asarray(last)
                pos[b], active[b] = plens[b], True
        active &= pos < length
        if not active.any():
            continue
        last_tok = tokens[np.arange(B), np.minimum(pos, length - 1)]
        logits, cache, _ = decode(params, cache, jnp.asarray(last_tok),
                                  jnp.asarray(active))
        for b in np.flatnonzero(active):
            out[b, pos[b]] = np.asarray(logits[b])
        pos += active
    return out


def worst_gap(got, want) -> float:
    return max(float(np.abs(lg - want[b, p]).max())
               for (b, p), lg in got.items())


@pytest.mark.parametrize("admit_at", [(0, 0, 0), (0, 3, 7), (5, 0, 2)])
@pytest.mark.parametrize("plens", [(1, 2, 3), (3, 17, BUCKET), (4, 5, 23)])
def test_prefill_then_decode_agrees_with_the_full_forward(plens, admit_at):
    """Prompts shorter than the convolution's kernel, of a chunk's
    length and of the whole bucket; lanes admitted together and one
    after another, so that a lane's first ticks run beside idle ones."""
    tokens, want = tokens_and_reference()
    got = served_logits("float32", tokens, plens, admit_at, ROW)
    assert len(got) == sum(ROW - p + 1 for p in plens)
    assert worst_gap(got, want) < TOL


def test_bfloat16_in_place_of_float32_fails_the_tolerance():
    tokens, want = tokens_and_reference()
    got = served_logits("bfloat16", tokens, [3, 7, 11], (0, 0, 0), 18)
    assert worst_gap(got, want) > 50 * TOL


@pytest.mark.parametrize("dtype,holds", [("float32", True),
                                         ("bfloat16", False)])
def test_the_kernel_in_the_decode_step_holds_the_same_tolerance(
        monkeypatch, dtype, holds):
    """The one-pass kernel (``ops/kda_step.py``, interpreted: these
    heads of 16 channels are under its tiling on a chip) put where a
    TPU lowering has it: the float32 engine agrees with the full
    forward as it does through the ``jax.numpy`` step, beside idle
    lanes too, and bfloat16 weights still miss by two orders."""
    from pbs_tpu.models import kda
    from pbs_tpu.ops.kda_step import kda_state_step

    stepped = []

    def step(state, *rest):
        stepped.append(state.shape)
        return kda_state_step(state, *rest, interpret=True)

    monkeypatch.setattr(kda, "_state_step", step)
    monkeypatch.setitem(globals(), "program", functools.lru_cache(
        maxsize=None)(program.__wrapped__))
    tokens, want = tokens_and_reference()
    if holds:
        got = served_logits(dtype, tokens, (3, 17, BUCKET), (0, 3, 7), ROW)
        assert worst_gap(got, want) < TOL
    else:
        got = served_logits(dtype, tokens, [3, 7, 11], (0, 0, 0), 18)
        assert worst_gap(got, want) > 50 * TOL
    assert stepped == [(SLOTS, 4, 16, 16)] * 3      # a trace a layer


# -- the chunked scan against the recurrence ----------------------------------


def recurrence(q, k, v, g, beta):
    """The delta rule a token at a time, float64 on the host:
    ``(o (S, H, D), state after the last token (H, D, D))``."""
    q, k, v, g, beta = (np.asarray(t, np.float64) for t in (q, k, v, g, beta))
    S, H, D = q.shape
    state, out = np.zeros((H, D, D)), np.zeros((S, H, D))
    for t in range(S):
        state = state * np.exp(g[t])[..., None]
        seen = np.einsum("hkv,hk->hv", state, k[t])
        state = state + beta[t][:, None, None] * k[t][..., None] \
            * (v[t] - seen)[:, None, :]
        out[t] = np.einsum("hkv,hk->hv", state, q[t])
    return out, state


def recurrence_inputs(n: int, fast: bool = False):
    """Seeded inputs of ``n`` positions, 2 heads of 8: unit keys, beta
    over the whole of (0, 2), decays a token from 0.999 down to 0.2
    (``fast``: down to e^-30, where a product of ``exp(G)`` and
    ``exp(-G)`` over a chunk would overflow float32)."""
    keys = jax.random.split(jax.random.PRNGKey(n), 5)
    shape = (n, 2, 8)
    q, k, v = (jax.random.normal(kk, shape, jnp.float32) for kk in keys[:3])
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    lo = 30.0 if fast else 1.6
    g = -jnp.exp(jax.random.uniform(keys[3], shape, jnp.float32,
                                    np.log(1e-3), np.log(lo)))
    beta = 2.0 * jax.random.uniform(keys[4], shape[:2], jnp.float32)
    return q, k, v, g, beta


#: prompt lengths 1, one under, at and over a chunk, and a non-multiple
#: of the chunk under each of two rungs (2 and 4 chunks)
LENGTHS = [(1, 128), (KDA_CHUNK - 1, 128), (KDA_CHUNK, 128),
           (KDA_CHUNK + 1, 128), (100, 128), (100, 256), (200, 256)]


@pytest.mark.parametrize("plen,rung", LENGTHS)
def test_the_chunked_scan_equals_the_recurrence(plen, rung):
    """Padded to a rung with no-op positions (decay 1, beta 0), the
    chunkwise form gives the outputs and the final state the
    token-by-token recurrence gives over the exact length."""
    q, k, v, g, beta = recurrence_inputs(plen)
    want_o, want_s = recurrence(q, k, v, g, beta)
    pad = lambda t: jnp.pad(  # noqa: E731
        t, ((0, rung - plen),) + ((0, 0),) * (t.ndim - 1),
        constant_values=0.0)
    # padding holds garbage where a no-op allows it (q, k, v)
    junk = lambda t: pad(t).at[plen:].set(7.0)  # noqa: E731
    o, s = jax.jit(kda_chunked)(junk(q), junk(k), junk(v), pad(g),
                                 pad(beta))
    assert o.shape == (rung, 2, 8)
    assert float(np.abs(np.asarray(o[:plen]) - want_o).max()) < 2e-5
    assert float(np.abs(np.asarray(s) - want_s).max()) < 2e-5


def test_a_decay_that_would_overflow_a_chunk_does_not():
    """Exponents are differences of a float32 running sum that here
    reaches hundreds (half an ulp of 6e-5 each), hence 2e-4 and not the
    2e-5 of the decays a model has."""
    q, k, v, g, beta = recurrence_inputs(2 * KDA_CHUNK, fast=True)
    assert float(jnp.cumsum(g, 0)[KDA_CHUNK - 1].min()) < -150  # e^150
    want_o, want_s = recurrence(q, k, v, g, beta)
    o, s = jax.jit(kda_chunked)(q, k, v, g, beta)
    assert np.isfinite(np.asarray(o)).all()
    assert float(np.abs(np.asarray(o) - want_o).max()) < 2e-4
    assert float(np.abs(np.asarray(s) - want_s).max()) < 2e-4


@pytest.mark.parametrize("plen", [1, 2, 3, 11, BUCKET])
def test_the_same_prompt_at_both_rungs_leaves_the_same_state(plen):
    """Every rung gives the state and the convolution tail the exact
    length would: padding is a no-op and the tail is the prompt's last
    three *real* positions (zeros before a prompt shorter than that)."""
    tokens, _ = tokens_and_reference()
    _cfg, params, prog, ingest, _ = program()
    short, full = (ingest(
        params, prog.init_cache(2, MAX_LEN), 1,
        padded(tokens[0], plen, rows), plen) for rows in (BUCKET, 2 * BUCKET))
    assert float(jnp.abs(short[0] - full[0]).max()) < 1e-5  # last logits
    for name in ("01", "02", "03"):
        a, b = short[1]["state"][name][1], full[1]["state"][name][1]
        assert float(jnp.abs(a).max()) > 1e-3
        assert float(jnp.abs(a - b).max()) < 1e-5  # chunks of 24 and 48
        a, b = short[1]["conv"][name][1], full[1]["conv"][name][1]
        assert float(jnp.abs(a - b).max()) < 1e-5
        assert bool((a[:max(0, 3 - plen)] == 0).all())
        assert float(jnp.abs(a[max(0, 3 - plen):]).min()) > 0
        # and nothing of it reached the other slot
        assert float(jnp.abs(short[1]["state"][name][0]).max()) == 0.0
    assert set(short[1]["k"]) == {"00"} and set(short[1]["state"]) == {
        "01", "02", "03"}


def test_ingestion_starts_from_zero_whatever_the_slot_held():
    tokens, _ = tokens_and_reference()
    _cfg, params, prog, ingest, _ = program()
    clean = ingest(params, prog.init_cache(2, MAX_LEN), 1,
                   padded(tokens[0], 9), 9)
    dirty = prog.init_cache(2, MAX_LEN)
    for key in ("state", "conv"):
        dirty[key] = {n: jnp.full_like(x, 3.0)
                      for n, x in dirty[key].items()}
    used = ingest(params, dirty, 1, padded(tokens[0], 9), 9)
    assert bool((used[0] == clean[0]).all())
    for name in ("01", "02", "03"):
        for key in ("state", "conv"):
            assert bool((used[1][key][name][1]
                         == clean[1][key][name][1]).all())
            assert bool((used[1][key][name][0] == 3.0).all())


def test_an_inactive_lanes_state_is_bit_identical_after_a_tick():
    tokens, _ = tokens_and_reference()
    _cfg, params, prog, ingest, decode = program()
    cache = prog.init_cache(SLOTS, MAX_LEN)
    for b in range(SLOTS):
        _, cache, _, _ = ingest(params, cache, b, padded(tokens[b], 6), 6)
    before = jax.tree.map(np.asarray, cache)
    active = np.array([True, False, True])
    _, after, _ = decode(params, cache, jnp.asarray(tokens[:, 6]),
                         jnp.asarray(active))
    for key in ("state", "conv"):
        for name, new in after[key].items():
            old = before[key][name]
            assert np.array_equal(np.asarray(new[1]), old[1])
            assert not np.array_equal(np.asarray(new[0]), old[0])
            assert not np.array_equal(np.asarray(new[2]), old[2])
    assert [int(p) for p in after["pos"]] == [7, 6, 7]


# -- the engine: a lane reused ------------------------------------------------


def serve(engine, prompts, max_new):
    done = {}
    for p in prompts:
        engine.submit(p, max_new)
    while engine.has_work():
        done.update({c.request_id: list(c.tokens) for c in engine.step()})
    return [done[i] for i in range(len(prompts))]


PROMPTS = [[5, 9, 2], [7] * 10, [3, 1, 4, 1, 5, 9, 2, 6], [11, 12],
           list(range(20, 44))]


def engine(slots: int) -> ContinuousBatcher:
    cfg, params = program()[:2]
    return ContinuousBatcher(cfg, params, n_slots=slots,
                             prompt_bucket=BUCKET, max_len=MAX_LEN)


@pytest.mark.parametrize("slots", [1, 2])
def test_a_lane_retired_and_readmitted_serves_what_a_fresh_engine_serves(
        slots):
    """One or two lanes for five requests: each later request is
    ingested into a lane whose state the last tenant left, beside a
    lane in mid-answer, and reads what it reads alone in a new engine
    (whose warm-up has run both programs over the cache already)."""
    alone = [serve(engine(1), [p], 12)[0] for p in PROMPTS]
    assert serve(engine(slots), PROMPTS, 12) == alone
    assert all(len(t) == 12 for t in alone)


def test_sharded_serve_backend_serves_the_tree():
    cfg, params = program()[:2]
    backend = ShardedServeBackend("engine", cfg, params, tp=1, dp=1,
                                  n_slots=2, prompt_bucket=BUCKET,
                                  max_len=MAX_LEN)
    assert backend.stats()["param_leaves"] == 3 + 6 + 3 * 16 + 4 * 9
    assert serve(backend.engine, PROMPTS[:3], 8) == serve(
        engine(3), PROMPTS[:3], 8)


# -- the expert layer behind a sigmoid router ----------------------------------


def expert_layer_inputs(c, n_tokens=40, at=2):
    h = jax.random.normal(jax.random.PRNGKey(3),
                          (n_tokens, c["hidden_size"]), jnp.float32)
    return h, ref.sparse_outer_weights(c, ref.seed_word(SEED), at,
                                       jnp.float32)


def mlp_kind(c, first, count):
    return P.MlpKind("experts", c["moe_intermediate_size"],
                     n_experts=c["deployment"]["experts_total"],
                     top_k=c["num_experts_per_tok"], held=(first, count),
                     shared_d_ff=ref.shared_width(c),
                     routed_scale=c["routed_scaling_factor"],
                     scoring="sigmoid")


def held_part(c, h, outer, first, count, at=2):
    lp = {**outer, **ref.expert_block(c, ref.seed_word(SEED), at, first,
                                      count, jnp.float32)}
    return held_expert_ffn(h, lp, mlp_kind(c, first, count),
                           jnp.ones(h.shape[0], bool), jnp.float32)


def test_the_shares_add_up_to_the_uncut_layer():
    """The eight shares of 40 experts at toy size: four shares of 2 of
    8 experts, the shared expert counted once, against the reference's
    layer over all 8."""
    c = toy()
    h, outer = expert_layer_inputs(c)
    total = c["deployment"]["experts_total"]
    gate = ref.routing(c, h, outer["router"], outer["router_bias"], False)
    want = ref.block_of_experts(h, gate, ref.expert_block(
        c, ref.seed_word(SEED), 2, 0, total, jnp.float32), False) \
        + ref.swiglu(h, outer["ws1"], outer["ws3"], outer["ws2"], False)
    parts, counts = zip(*(held_part(c, h, outer, first, 2)
                          for first in range(0, total, 2)))
    got = sum(parts) + shared_expert_ffn(h, outer, jnp.float32)
    assert float(jnp.abs(got - want).max()) < 1e-5
    chosen = np.asarray(gate > 0)
    assert (chosen.sum(1) == 3).all()
    for s, cnt in enumerate(counts):
        mine = chosen[:, 2 * s:2 * s + 2]
        assert [int(x) for x in cnt] == [
            mine.sum(), chosen.sum() - mine.sum(),
            mine.any(0).sum(), mine.sum(0).max()]
    assert sum(int(cnt[0]) for cnt in counts) == 40 * 3  # none dropped


def test_the_selection_bias_changes_the_chosen_set_and_not_the_weights():
    c = toy()
    kind = mlp_kind(c, 0, 8)
    h, outer = expert_layer_inputs(c)
    scores = jax.nn.sigmoid(h @ outer["router"])
    plain_w, plain_i = route_top_k(h, outer["router"], kind, jnp.zeros(8))
    # Expert 5 is lifted over every other; it is chosen by every token,
    # and weighted by its own score, not by score + bias.
    bias = jnp.zeros(8).at[5].set(2.0)
    w, idx = route_top_k(h, outer["router"], kind, bias)
    assert bool((idx == 5).any(-1).all())
    assert not bool((plain_i == 5).any(-1).all())
    picked = jnp.take_along_axis(scores, idx, -1)
    assert float(jnp.abs(
        w - picked / picked.sum(-1, keepdims=True)).max()) < 1e-6
    assert float(jnp.abs(w.sum(-1) - 1.0).max()) < 1e-6
    # the seeded bias (0.005 x normal) is no no-op either: over 4,000
    # tokens it changes some chosen sets, and few
    many, _ = expert_layer_inputs(c, n_tokens=4000)
    _, seeded = route_top_k(many, outer["router"], kind,
                            outer["router_bias"])
    _, unbiased = route_top_k(many, outer["router"], kind, jnp.zeros(8))
    moved = np.mean(np.any(np.sort(np.asarray(seeded))
                           != np.sort(np.asarray(unbiased)), axis=-1))
    assert 0.001 < moved < 0.2
    # and what the reference routes is what the program routes
    gate = np.asarray(ref.routing(c, h, outer["router"],
                                  outer["router_bias"], False))
    _, idx = route_top_k(h, outer["router"], kind, outer["router_bias"])
    assert np.array_equal(np.sort(np.asarray(idx)),
                          np.sort(np.argsort(-gate, -1)[:, :3]))


def test_a_softmax_router_routes_as_it_did():
    c = toy()
    h, outer = expert_layer_inputs(c)
    kind = P.MlpKind("experts", 24, n_experts=8, top_k=3, held=(0, 8))
    assert kind.scoring == "softmax"
    w, idx = route_top_k(h, outer["router"], kind)
    p = jax.nn.softmax(h @ outer["router"], -1)
    topv, topi = jax.lax.top_k(p, 3)
    assert np.array_equal(np.asarray(idx), np.asarray(topi))
    assert float(jnp.abs(w - topv / topv.sum(-1, keepdims=True)).max()) < 1e-6


# -- serve/: rules; what this plan does not do ---------------------------------

NEW_LEAVES = ("cq", "ck", "cv", "wa1", "wa2", "a_log", "dt_bias", "wb",
              "wg1", "wg2", "o_norm", "router_bias")


@pytest.mark.parametrize("leaf", NEW_LEAVES + ("wg",))
def test_every_new_leaf_meets_exactly_one_rule(leaf):
    cfg, params = program()[:2]
    found = [(p, x) for p, x in iter_leaf_paths(params)
             if p.rsplit("/", 1)[-1] == leaf]
    assert len(found) == (4 if leaf == "router_bias" else
                          1 if leaf == "wg" else 3)
    specs = match_partition_rules(PARTITION_RULES, params)
    for path, x in found:
        hits = [pat for pat, _ in PARTITION_RULES if re.search(pat, path)]
        assert len(hits) == 1, (path, hits)
        spec = specs
        for part in path.split("/"):
            spec = spec[part]
        # what feeds a head's state lies along the head axis
        assert spec == ((None, -1) if leaf in ("cq", "ck", "cv", "wb")
                        else ()), (path, spec)
        assert not spec or len(spec) == x.ndim


def test_the_whole_tree_is_the_plans_and_every_leaf_has_one_rule():
    cfg, params = program()[:2]
    for path, _ in iter_leaf_paths(params):
        hits = [pat for pat, _ in PARTITION_RULES if re.search(pat, path)]
        assert len(hits) == 1, (path, hits)
    specs = match_partition_rules(PARTITION_RULES, params)
    is_leaf = lambda x: isinstance(x, tuple)  # noqa: E731
    assert jax.tree.structure(specs, is_leaf=is_leaf) \
        == jax.tree.structure(P.plan_shapes(cfg), is_leaf=is_leaf)
    shapes = jax.tree.map(lambda x: tuple(x.shape), params)
    assert shapes == P.plan_shapes(cfg)
    assert jax.tree.map(lambda x: tuple(x.shape), P.init_plan_params(
        cfg, jax.random.PRNGKey(0))) == shapes


REFUSALS = {
    "prefix": (ValueError, "snapshot of that state", lambda cfg, params:
               ContinuousBatcher(cfg, params, n_slots=2, prompt_bucket=12,
                                 max_len=40, prefix_cache_size=2)),
    "speculation": (NotImplementedError, "snapshot of that state",
                    lambda cfg, params: SpeculativeBatcher(
                        cfg, params, cfg, params, n_slots=2,
                        prompt_bucket=12, max_len=40)),
    "tensor-axis": (NotImplementedError, "recurrent state",
                    lambda cfg, params: ShardedServeBackend(
                        "engine", cfg, params, tp=2, dp=1)),
    "mlp_fn": (ValueError, "mlp_fn", lambda cfg, params: slot_program(
        cfg, mlp_fn=lambda lp, h: (h, 0.0))),
    "training": (NotImplementedError, "backward", lambda cfg, params:
                 FAMILY.train_step(cfg, 1e-3)),
    "two-gates": (ValueError, "two output gates", lambda cfg, params:
                  P.AttnKind("full", 4, head_gate=True, wide_gate=True)),
}


@pytest.mark.parametrize("what", sorted(REFUSALS))
def test_what_this_plan_does_not_do_raises_with_the_reason(what):
    cfg, params = program()[:2]
    error, reason, call = REFUSALS[what]
    with pytest.raises(error, match=reason):
        call(cfg, params)


def test_the_plan_names_kinds_not_models():
    c = toy()
    plan = FAMILY.layer_plan(c, 4)
    assert [type(plan.kinds(l)[0]).__name__ for l in range(4)] == [
        "AttnKind", "KdaKind", "KdaKind", "KdaKind"]
    full, kda = plan.attn
    assert full.rope is None and full.gate == "elementwise"
    assert (kda.n_heads, kda.head_dim, kda.conv, kda.rank) == (4, 16, 4, 16)
    assert plan.mlp[0].scoring == "sigmoid" and plan.recurrent
    assert not P.uniform_plan(program()[0]).recurrent
    published = FAMILY.layer_plan(SPEC.config("solar-open2-250b"), 4)
    kda = published.attn[1]
    assert (kda.n_heads, kda.head_dim, kda.conv, kda.rank) == (64, 128, 4, 128)
    assert published.mlp[0].held == (0, 40)
    assert published.mlp[0].n_experts == 320


# -- the scope names the metrics match -----------------------------------------


@functools.lru_cache(maxsize=None)
def lowered_text() -> dict:
    """The engine's two programs as lowered, with the name stack of
    every op (what the profiler shows as an op's scope)."""
    eng = engine(2)
    key = jax.random.PRNGKey(0)
    return {
        "jit__decode": eng._decode_fn.lower(
            eng.params, eng.cache, jnp.zeros((2,), jnp.int32),
            jnp.zeros((2,), jnp.int32), key).as_text(debug_info=True),
        "jit__prefill": eng._prefill_fn.lower(
            eng.params, eng.cache, jnp.zeros((2,), jnp.int32), 0,
            jnp.zeros((BUCKET,), jnp.int32), 1,
            key).as_text(debug_info=True)}


def _cell_scopes():
    """(metric, program, scope) of every metric file the new cell
    reports that names scopes."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        mine = {m["name"] for m in json.load(f)["per_layer"]
                if CELL in m.get("workloads", [])}
    out = []
    for path in sorted(glob.glob(os.path.join(
            ROOT, "benchmarks", "metrics", "*.json"))):
        name = os.path.basename(path)[:-5]
        with open(path) as f:
            args = json.load(f).get("args", {})
        if name in mine:
            out += [(name, args["match"], s)
                    for s in args.get("scopes") or []
                    if s != "attn.window"]  # this stack has no window layer
    return out


@pytest.mark.parametrize("metric,match,scope", _cell_scopes())
def test_a_metrics_scope_names_ops_of_its_program(metric, match, scope):
    """A renamed ``jax.named_scope`` breaks this test, not a metric that
    would silently find no op in the trace."""
    assert re.search(rf'"[^"]*/{re.escape(scope)}/[^"]*"',
                     lowered_text()[match]), (metric, scope)


def test_the_state_update_lies_inside_the_mixers_scope():
    for text in lowered_text().values():
        for inner in ("kda.conv", "kda.state"):
            assert f"/attn.kda/{inner}/" in text
        assert "/attn.full/" in text and "/moe.route/" in text
