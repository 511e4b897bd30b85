"""Latent attention under a learned indexer (MLA + DSA) through the slot
engine: a latent row, a rotary key and an indexer key a position in one
cache under one cursor, attention over the positions the indexer picks,
experts behind a sigmoid router, and the plain float32 reference they
are held to (``benchmarks/reference/moe_mla_dsa.py``, which imports
nothing of the program, reads every head's keys and values off the
latent rows and chooses with ``lax.top_k``). Toy sizes, CPU, float32,
seeded weights: the configuration file's own rehearsal preset (hidden
48, 4 heads of 12 + 4 over latents of 24 and 16, an indexer of 2 heads
of 8 that picks 16 positions, 8 experts top-3 with 4 held, one dense
layer and two expert layers), prompts of 40-64 so that the choice
bites.

Tolerances: program and reference both compute in float32, in another
order (the absorbed form over cached rows against the per-head form
over the whole row, a bisection against a sort, sorted grouped products
against every expert for every token), so logits of magnitude ~4 agree
to a few float32 roundings: 2e-4 absolute. Attention over every
position in place of the chosen 16 misses that by three orders
(asserted below).
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
from pbs_tpu.models import mla
from pbs_tpu.models import moe
from pbs_tpu.models import plan as P
from pbs_tpu.models.moe import held_expert_ffn, shared_expert_ffn
from pbs_tpu.models.serving import ContinuousBatcher
from pbs_tpu.models.slot_programs import slot_program
from pbs_tpu.models.spec_serving import SpeculativeBatcher
from pbs_tpu.obs.trace import Ev
from pbs_tpu.serve import ShardedServeBackend
from pbs_tpu.serve.partition import (
    PARTITION_RULES, iter_leaf_paths, match_partition_rules)

SEED = 11
TOL = 2e-4
SPEC = Spec()
FAMILY = SPEC.family("moe-mla-dsa")
ref = FAMILY.reference
CELL = "serve-dsa-agentcode-surge"
TOPK = 16
NEW_LEAVES = ("wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm", "wkv_b",
              "wi_q", "wi_k", "ik_norm", "ik_bias", "wi_w")


def toy(first: int = 0, held: int = 4, total: int = 8) -> dict:
    full = SPEC.config("glm-5")
    c = copy.deepcopy(overlay(full, full["rehearsal"]))
    c["n_routed_experts"] = c["num_experts"] = held
    c["deployment"].update(experts_first=first, experts_total=total)
    assert c["index_topk"] == TOPK
    return c


MAX_LEN, BUCKET, SLOTS, ROW = 96, 64, 3, 80
N_LAYERS = 3


@functools.lru_cache(maxsize=None)
def program():
    """The toy model's configuration, weights and the two programs the
    engine makes of it (jitted once for the module)."""
    c = toy()
    cfg = FAMILY.program_config(c, N_LAYERS, MAX_LEN)
    params = jax.jit(lambda s: ref.init_tree(
        c, s, N_LAYERS, jnp.float32))(ref.seed_word(SEED))
    prog = slot_program(cfg)

    @jax.jit
    def decode(params, cache, last_tok, active):
        logits, new, _, route = prog.decode(params, cache, last_tok, active)
        new["pos"] = cache["pos"] + active.astype(jnp.int32)
        return logits[:, 0], new, route

    return cfg, params, prog, jax.jit(prog.ingest), decode


def reference_logits(tokens, quant=False):
    """The reference's full forward over ``tokens`` (B, S): (B, S, V)
    logits (causal, so a row's prefix reads the same)."""
    c = toy()
    B, S = tokens.shape
    V = c["vocab_size"]
    rows, cols = (a.reshape(-1) for a in np.mgrid[:B, :S])
    cand = np.tile(np.arange(V, dtype=np.int32)[:, None], (1, B * S))
    _, _, picked = ref.score_tokens(
        c, SEED, N_LAYERS, jnp.float32, tokens, rows.astype(np.int32),
        cols.astype(np.int32), cand, quant=quant)
    return picked.T.reshape(B, S, V)


@functools.lru_cache(maxsize=None)
def tokens_and_reference():
    tokens = np.random.default_rng(SEED).integers(
        1, toy()["vocab_size"], (SLOTS, ROW)).astype(np.int32)
    return tokens, reference_logits(tokens)


def padded(tokens, plen: int, rows: int = BUCKET):
    out = np.zeros(rows, np.int32)
    out[:plen] = tokens[:plen]
    return jnp.asarray(out)


# -- the cache against the full forward ---------------------------------------


def served_logits(tokens, plens, admit_at, length):
    """Teacher-forced serving of the first ``length`` tokens of each row
    of ``tokens``: slot b is given its prompt (``plens[b]`` tokens) at
    tick ``admit_at[b]`` and then decodes the rest, one position a tick,
    beside whatever else is in the cache (lanes not yet admitted ride
    along inactive). Returns ``{(b, position): logits}`` for the
    prompt's last position and every decoded one, and the cache."""
    _cfg, params, prog, ingest, decode = program()
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
    return out, cache


def worst_gap(got, want) -> float:
    return max(float(np.abs(lg - want[b, p]).max())
               for (b, p), lg in got.items())


@pytest.mark.parametrize("admit_at", [(0, 0, 0), (0, 3, 7), (5, 0, 2)])
@pytest.mark.parametrize("plens", [(40, 52, BUCKET), (1, TOPK, TOPK + 1),
                                   (47, 3, 61)])
def test_prefill_then_decode_agrees_with_the_full_forward(plens, admit_at):
    """Prompts past the indexer's 16 (the choice bites in the ingestion
    and in every tick), of one token, of exactly 16 and 17, and of the
    whole bucket; lanes admitted together and one after another, so that
    a lane's first ticks run beside idle ones."""
    tokens, want = tokens_and_reference()
    got, _ = served_logits(tokens, plens, admit_at, ROW)
    assert len(got) == sum(ROW - p + 1 for p in plens)
    assert worst_gap(got, want) < TOL


def test_attention_over_every_position_fails_the_tolerance():
    """The dense control: the reference with the choice left out is not
    what the program serves past 16 positions, and is up to there."""
    tokens, _ = tokens_and_reference()
    dense = reference_logits(tokens, quant="dense")
    got, _ = served_logits(tokens, (40, 52, BUCKET), (0, 0, 0), ROW)
    assert worst_gap(got, dense) > 1000 * TOL
    short, _ = served_logits(tokens, (3, 5, 9), (0, 0, 0), TOPK)
    assert max(p for _, p in short) == TOPK - 1
    assert worst_gap(short, dense) < TOL


@pytest.mark.parametrize("plen", [1, 7, TOPK])
def test_a_prompt_no_longer_than_topk_is_dense_latent_attention(plen):
    """Up to ``topk`` positions the indexer has nothing to rule out."""
    tokens, _ = tokens_and_reference()
    dense = reference_logits(tokens[:, :TOPK], quant="dense")
    _cfg, params, prog, ingest, _ = program()
    last, _, _, _ = ingest(params, prog.init_cache(1, MAX_LEN), 0,
                           padded(tokens[0], plen), plen)
    assert float(np.abs(np.asarray(last) - dense[0, plen - 1]).max()) < TOL


@pytest.mark.parametrize("n", [TOPK + 3, 40, BUCKET - 1])
def test_the_absorbed_decode_equals_the_per_head_form(n):
    """Position n by the ingestion of n + 1 tokens (every head's keys
    and values read off the latent rows) and by the ingestion of n and
    one decode step (the query carried into the latent space)."""
    tokens, _ = tokens_and_reference()
    _cfg, params, prog, ingest, decode = program()
    whole, _, _, _ = ingest(params, prog.init_cache(1, MAX_LEN), 0,
                            padded(tokens[1], n + 1), n + 1)
    _, cache, _, _ = ingest(params, prog.init_cache(1, MAX_LEN), 0,
                            padded(tokens[1], n), n)
    step, _, _ = decode(params, cache, jnp.asarray(tokens[1, n:n + 1]),
                        jnp.ones((1,), bool))
    assert float(jnp.abs(step[0] - whole).max()) < TOL


# -- the choice ------------------------------------------------------------------


def layer0(tokens_row):
    """Layer 0's input (the embedding rows), its normed form and the
    mixer's float32 weights, as the reference and the program hold
    them."""
    c = toy()
    _cfg, params, _, _, _ = program()
    w = ref._f32(ref.attn_weights(c, ref.seed_word(SEED), 0, jnp.float32))
    x = params["embed"][jnp.asarray(tokens_row)]
    return c, x, w, params["blocks"]["00"]["attn"]


def test_the_set_a_decode_position_attends_is_the_references():
    """Every position of a row past the prompt: the positions the
    program's decode step chooses among the indexer keys its own
    ingestion and ticks cached (layer 0, whose input is the embedding)
    are the reference's S_t: 16 of them, and where the 16th score is
    tied (with two indexer heads a score is exactly 0 wherever both
    dot products are negative) every tied position besides."""
    tokens, _ = tokens_and_reference()
    plen = 40
    _, cache = served_logits(tokens[:1], (plen,), (0,), ROW)
    c, x, w, ap = layer0(tokens[0])
    want = np.asarray(ref.mixer_row(c, x, w, picked=True))       # (S, S)
    cfg = program()[0]
    a = cfg.layer_plan.attn[0]
    h = ref.rms_norm(x, w["attn_norm"], c["rms_norm_eps"])[None]
    pos = jnp.arange(ROW)
    cos, sin = (t[pos][None] for t in P.rope_table(a.rope, 0, MAX_LEN))
    *_, qi, wi = mla._rows(a, ap, h, cos, sin, cfg.norm_eps, jnp.float32)
    ik = cache["ik"]["00"]                                        # (1, T, D)
    sizes = []
    for t in range(plen, ROW):
        got = np.asarray(mla.decode_choice(
            a, qi[:, t], wi[:, t], ik, jnp.asarray([t])))[0]
        assert got[:ROW].tolist() == want[t].tolist(), t
        assert got.sum() >= TOPK and not got[t + 1:].any()
        sizes.append(int(got.sum()))
    assert min(sizes) == TOPK


@pytest.mark.parametrize("block,keys", [(16, 8), (32, 2048), (BUCKET, 24)])
def test_the_ingestion_attends_the_references_set_in_every_block(
        block, keys, monkeypatch):
    """Queries in blocks of 16 (four spans: the first chooses nothing,
    the others each against their own span of keys), of 32 and in one
    block; keys in chunks of 8 (a query's chosen 16 then leave whole
    chunks it sees nothing of), of 24 and all at once: the same logits,
    the reference's."""
    monkeypatch.setattr(mla, "MLA_BLOCK", block)
    monkeypatch.setattr(mla, "MLA_KEYS", keys)
    tokens, want = tokens_and_reference()
    _cfg, params, prog, _, _ = program()
    for b, plen in enumerate((BUCKET, 50, 33)):
        last, _, _, _ = jax.jit(prog.ingest)(
            params, prog.init_cache(1, MAX_LEN), 0,
            padded(tokens[b], plen), plen)
        assert float(np.abs(np.asarray(last)
                            - want[b, plen - 1]).max()) < TOL


def test_the_spans_cover_the_prompt_in_whole_blocks():
    assert mla._spans(8192, 256) == [(0, 2048), (2048, 2048), (4096, 2048),
                                     (6144, 2048)]
    assert mla._spans(4096, 256) == [(i * 1024, 1024) for i in range(4)]
    assert mla._spans(64, 32) == [(0, 32), (32, 32)]
    assert mla._spans(48, 48) == [(0, 48)]


@pytest.mark.parametrize("k", [1, 5, 16, 63, 64, 100])
def test_top_mask_is_the_k_largest_with_ties_in(k):
    rng = np.random.default_rng(k)
    x = rng.normal(size=(7, 64)).astype(np.float32)
    x[0, :40] = -np.inf            # a row that sees 24 positions
    x[1, ::2] = x[1, 1]            # ties
    x[2] = 0.0
    x[3, 5] = -0.0
    got = np.asarray(mla.top_mask(jnp.asarray(x), k))
    for row, m in zip(x, got):
        kth = np.sort(row)[::-1][min(k, 64) - 1]
        assert m.tolist() == (row >= kth).tolist()
    assert got.sum(1).min() >= min(k, 64)


@pytest.mark.parametrize("interleave", [True, False])
def test_the_rotary_turns_the_pairs_it_is_told_to(interleave):
    """Adjacent pairs against the reference's own ``turn``; the
    half-split form against the engine's rotary for the other kinds."""
    from pbs_tpu.models.slot_programs import _rope_leading

    S, rot = 9, 4
    x = jax.random.normal(jax.random.PRNGKey(1), (1, S, 3, 10), jnp.float32)
    rope = P.Rope(theta=1e6, rotary_dim=rot, interleave=interleave)
    cos, sin = (t[None] for t in P.rope_table(rope, 0, S))
    got = mla._turn(x, cos, sin, interleave)
    want = ref.turn(x[0], 1e6, rot)[None] if interleave \
        else _rope_leading(x, cos, sin)
    assert float(jnp.abs(got - want).max()) < 1e-6
    assert (got[..., rot:] == x[..., rot:]).all()


# -- padding and idle lanes ---------------------------------------------------------


def latent_rows(cache):
    return {(key, name): np.asarray(rows) for key in ("ckv", "kr", "ik")
            for name, rows in cache[key].items()}


def test_an_idle_lane_and_a_prompts_padding_change_no_cache_row():
    tokens, _ = tokens_and_reference()
    _cfg, params, prog, ingest, decode = program()
    cache = prog.init_cache(SLOTS, MAX_LEN)
    cache = jax.tree.map(lambda x: x + 1 if x.dtype != jnp.int32 else x,
                         cache)
    before = latent_rows(cache)
    plen = 41
    _, cache, _, _ = ingest(params, cache, 1, padded(tokens[1], plen), plen)
    after = latent_rows(cache)
    for key, rows in after.items():
        assert (rows[[0, 2]] == before[key][[0, 2]]).all(), key
        assert (rows[1, plen:] == before[key][1, plen:]).all(), key
        assert (rows[1, :plen] != before[key][1, :plen]).any(), key
    _, cache, _ = decode(params, cache, jnp.asarray(tokens[:, plen]),
                         jnp.asarray([False, True, False]))
    ticked = latent_rows(cache)
    assert np.asarray(cache["pos"]).tolist() == [0, plen + 1, 0]
    for key, rows in ticked.items():
        assert (rows[[0, 2]] == before[key][[0, 2]]).all(), key
        assert (rows[1, :plen] == after[key][1, :plen]).all(), key
        assert (rows[1, plen] != after[key][1, plen]).any(), key
        assert (rows[1, plen + 1:] == after[key][1, plen + 1:]).all(), key


def test_an_idle_lane_attends_its_first_row_alone(monkeypatch):
    """An idle lane's cursor rests where its last request ended: the
    decode's choice and attention are given 0 in its place (one row
    live, one chosen, one block for the kernel to stream), an active
    lane's its own."""
    cfg, params, _prog, _ingest, _decode = program()
    a = P.plan_of(cfg).kinds(0)[0]
    ap = params["blocks"][P.block_name(0)]["attn"]
    seen = {}

    def spy(q_lat, q_r, ckv, kr, chosen, row_pos, live, *, scale):
        seen.update(chosen=np.asarray(chosen), at=np.asarray(row_pos))
        return mla.attend_rows(q_lat, q_r, ckv, kr, chosen, scale=scale)

    monkeypatch.setattr(mla, "_attend", spy)
    B, half = 3, a.rope_dim // 2
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    h = jax.random.normal(ks[0], (B, 1, cfg.d_model), jnp.float32)
    ckv, kr, ik = (
        jax.random.normal(k, (B, MAX_LEN, w), jnp.float32)
        for k, w in zip(ks[1:], (a.kv_rank, a.rope_dim, a.index_dim)))
    out, *_ = mla.mla_decode(
        a, ap, h, (ckv, kr, ik), jnp.asarray([50, 70, 33]),
        jnp.asarray([True, False, True]), jnp.ones((B, 1, half)),
        jnp.zeros((B, 1, half)), cfg.norm_eps, jnp.float32)
    assert seen["at"].tolist() == [50, 0, 33]
    chosen = seen["chosen"]
    # relu leaves many a score 0: the ties at the TOPK-th are all in
    assert chosen[0].sum() >= TOPK and chosen[2].sum() >= TOPK
    assert not chosen[0, 51:].any() and not chosen[2, 34:].any()
    assert chosen[1].tolist() == [True] + [False] * (MAX_LEN - 1)
    assert np.isfinite(np.asarray(out)).all()


def test_the_cache_holds_latent_rows_and_nothing_a_head():
    cfg, _, prog, _, _ = program()
    cache = prog.init_cache(SLOTS, MAX_LEN)
    assert cache["k"] == {} and cache["v"] == {}
    a = cfg.layer_plan.attn[0]
    for key, width in (("ckv", a.kv_rank), ("kr", a.rope_dim),
                       ("ik", a.index_dim)):
        assert sorted(cache[key]) == ["00", "01", "02"]
        assert all(rows.shape == (SLOTS, MAX_LEN, width)
                   for rows in cache[key].values())
    assert cache["pos"].shape == (SLOTS,)


# -- through the engine -----------------------------------------------------------


def serve(engine, prompts, max_new):
    done = {}
    for p in prompts:
        engine.submit(p, max_new)
    while engine.has_work():
        done.update({c.request_id: list(c.tokens) for c in engine.step()})
    return [done[i] for i in range(len(prompts))]


def prompts_of(lengths):
    rng = np.random.default_rng(SEED + 1)
    return [rng.integers(1, toy()["vocab_size"], n).tolist()
            for n in lengths]


def engine(slots: int) -> ContinuousBatcher:
    cfg, params = program()[:2]
    return ContinuousBatcher(cfg, params, n_slots=slots,
                             prompt_bucket=BUCKET, max_len=MAX_LEN)


@pytest.mark.parametrize("slots", [1, 2, 3])
def test_what_the_engine_serves_is_the_references_best_token(slots):
    """Greedy through ``ContinuousBatcher`` (pipelined ticks, lanes
    retired and readmitted over whatever the last tenant left): every
    served token's logit in the reference's full forward over the
    served row lies within the tolerance of the best one there."""
    prompts = prompts_of((40, 64, 47, 55, 3))
    served = serve(engine(slots), prompts, 14)
    rows = np.zeros((len(prompts), BUCKET + 14), np.int32)
    for b, (p, out) in enumerate(zip(prompts, served)):
        rows[b, :len(p) + len(out)] = p + out
    want = reference_logits(rows)
    for b, (p, out) in enumerate(zip(prompts, served)):
        assert len(out) == 14
        for i, tok in enumerate(out):
            at = want[b, len(p) - 1 + i]
            assert at.max() - at[tok] < TOL, (b, i)


def test_sharded_serve_backend_serves_the_tree():
    cfg, params = program()[:2]
    backend = ShardedServeBackend("engine", cfg, params, tp=1, dp=1,
                                  n_slots=2, prompt_bucket=BUCKET,
                                  max_len=MAX_LEN)
    assert backend.stats()["param_leaves"] == 3 + 3 * 13 + 4 + 2 * 9
    prompts = prompts_of((40, 52))
    assert serve(backend.engine, prompts, 6) == serve(engine(2), prompts, 6)


def records(eng, event):
    return [r for r in eng.trace.peek().tolist() if r[1] == int(event)]


@pytest.mark.parametrize("block", [0, 16])
def test_eng_select_counts_live_and_chosen_positions_from_the_slot_table(
        block):
    """One record a prefill (its prompt's pairs) and one a dispatched
    decode (its busy lanes' positions), each stamped like the record of
    the call it belongs to; sums a reader can divide. The decode's last
    argument counts the blocks its one-pass attention streams (a CPU
    runs the ``jax.numpy`` form: ``block`` 0, and so the count; at 16,
    as if the kernel ran, a lane whose cursor is 15 counts one block
    and at 16 two), a prefill's is 0."""
    eng = engine(2)
    assert eng._live == {}
    if block:
        eng._live = {("latent", MAX_LEN, block): N_LAYERS}
    lengths = (40, 14)
    serve(eng, prompts_of(lengths), 5)
    selects = records(eng, Ev.ENG_SELECT)
    prefills = {r[0]: r for r in records(eng, Ev.ENG_PREFILL)}
    decodes = {r[0] for r in records(eng, Ev.ENG_DECODE)}
    pre = [r for r in selects if r[0] in prefills]
    dec = [r for r in selects if r[0] in decodes]
    assert len(pre) == 2 and len(pre) + len(dec) == len(selects)
    for r, n in zip(pre, lengths):
        live = np.arange(1, n + 1)
        assert r[3:8] == [n, live.sum(), np.minimum(live, TOPK).sum(), TOPK,
                          0]
    # four decodes after each prefill's first token, both lanes busy
    assert len(dec) == 4
    for i, r in enumerate(dec):
        live = [n + 1 + i for n in lengths]
        assert r[3:7] == [2, sum(live), sum(min(v, TOPK) for v in live),
                          TOPK]
        # cursors 40-43 lie in the third block; 14, 15 | 16, 17
        assert r[7] == (3 + (1, 1, 2, 2)[i] if block else 0)
    assert records(ContinuousBatcher(
        *uniform_model(), n_slots=1, prompt_bucket=8, max_len=16),
        Ev.ENG_SELECT) == []


def uniform_model():
    from pbs_tpu.models.transformer import TransformerConfig, init_params

    cfg = TransformerConfig(vocab=64, d_model=16, n_layers=1, n_heads=2,
                            n_kv_heads=2, d_ff=32, max_seq=16)
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


# -- the expert layer: sixteen shares -------------------------------------------------


def mlp_kind(c, first, count):
    return P.MlpKind("experts", c["moe_intermediate_size"],
                     n_experts=c["deployment"]["experts_total"],
                     top_k=c["num_experts_per_tok"], held=(first, count),
                     shared_d_ff=ref.shared_width(c),
                     routed_scale=c["routed_scaling_factor"],
                     scoring="sigmoid")


def expert_layer(c, n_tokens=40, at=2):
    h = jax.random.normal(jax.random.PRNGKey(3),
                          (n_tokens, c["hidden_size"]), jnp.float32)
    return h, ref.sparse_outer_weights(c, ref.seed_word(SEED), at,
                                       jnp.float32)


def held_part(c, h, outer, first, count, at=2):
    lp = {**outer, **ref.expert_block(c, ref.seed_word(SEED), at, first,
                                      count, jnp.float32)}
    return held_expert_ffn(h, lp, mlp_kind(c, first, count),
                           jnp.ones(h.shape[0], bool), jnp.float32)


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """Sixteen shares of one of 16 experts at toy size, the shared
    expert counted once, against the reference's layer over all 16,
    weighted by 2.5."""
    c = toy(total=16, held=1)
    assert c["routed_scaling_factor"] == 2.5
    h, outer = expert_layer(c)
    gate = ref.routing(c, h, outer["router"], outer["router_bias"], False)
    want = ref.block_of_experts(h, gate, ref.expert_block(
        c, ref.seed_word(SEED), 2, 0, 16, jnp.float32), False) \
        + ref.swiglu(h, outer["ws1"], outer["ws3"], outer["ws2"], False)
    parts, counts = zip(*(held_part(c, h, outer, first, 1)
                          for first in range(16)))
    got = sum(parts) + shared_expert_ffn(h, outer, jnp.float32)
    assert float(jnp.abs(got - want).max()) < 1e-5
    chosen = np.asarray(gate > 0)
    assert (chosen.sum(1) == 3).all()
    assert np.allclose(np.asarray(gate).sum(1), 2.5, atol=1e-5)
    for s, cnt in enumerate(counts):
        mine = chosen[:, s]
        assert [int(x) for x in cnt] == [
            mine.sum(), chosen.sum() - mine.sum(), int(mine.any()),
            mine.sum()]
    assert sum(int(cnt[0]) for cnt in counts) == 40 * 3  # none dropped


@pytest.mark.parametrize("rows", [8, 16])
def test_a_long_prompts_rows_go_through_the_experts_in_pieces(
        rows, monkeypatch):
    """More rows than ``EXPERT_ROWS``: the same result and the same
    counters (an expert two pieces touch is touched once) as at once."""
    c = toy()
    h, outer = expert_layer(c, n_tokens=48)
    valid = jnp.arange(48) < 41
    lp = {**outer, **ref.expert_block(c, ref.seed_word(SEED), 2, 0, 4,
                                      jnp.float32)}
    kind = mlp_kind(c, 0, 4)
    want, counts = held_expert_ffn(h, lp, kind, valid, jnp.float32)
    monkeypatch.setattr(moe, "EXPERT_ROWS", rows)
    got, pieces = held_expert_ffn(h, lp, kind, valid, jnp.float32)
    assert float(jnp.abs(got - want).max()) < 1e-6
    assert pieces.tolist() == counts.tolist()


# -- the tree, the rules, the refusals ---------------------------------------------


@pytest.mark.parametrize("leaf", NEW_LEAVES)
def test_every_new_leaf_meets_exactly_one_rule(leaf):
    path = f"blocks/03/attn/{leaf}"
    hits = [pat for pat, _ in PARTITION_RULES if re.search(pat, path)]
    assert len(hits) == 1, (path, hits)


def test_the_whole_tree_is_the_plans_and_every_leaf_has_one_rule():
    cfg, params = program()[:2]
    for path, _leaf in iter_leaf_paths(params):
        hits = [pat for pat, _ in PARTITION_RULES if re.search(pat, path)]
        assert len(hits) == 1, (path, hits)
    specs = match_partition_rules(PARTITION_RULES, params)
    is_leaf = lambda x: isinstance(x, tuple)  # noqa: E731
    assert jax.tree.structure(specs, is_leaf=is_leaf) \
        == jax.tree.structure(P.plan_shapes(cfg), is_leaf=is_leaf)
    shapes = jax.tree.map(lambda x: tuple(x.shape), params)
    assert shapes == P.plan_shapes(cfg)
    drawn = P.init_plan_params(cfg, jax.random.PRNGKey(0))
    assert jax.tree.map(lambda x: tuple(x.shape), drawn) == shapes
    attn = drawn["blocks"]["00"]["attn"]
    assert sorted(attn) == sorted(NEW_LEAVES + ("attn_norm", "wo"))
    assert float(jnp.abs(attn["ik_bias"]).max()) > 0
    assert all((attn[n] == 1).all() for n in ("q_norm", "kv_norm",
                                              "ik_norm"))


REFUSALS = {
    "prefix": (ValueError, "a latent row, a rotary key", lambda cfg, params:
               ContinuousBatcher(cfg, params, n_slots=2, prompt_bucket=12,
                                 max_len=40, prefix_cache_size=2)),
    "speculation": (NotImplementedError, "indexer's choice for k \\+ 1",
                    lambda cfg, params: SpeculativeBatcher(
                        cfg, params, cfg, params, n_slots=2,
                        prompt_bucket=12, max_len=40)),
    "tensor-axis": (NotImplementedError, "latent rows'",
                    lambda cfg, params: ShardedServeBackend(
                        "engine", cfg, params, tp=2, dp=1)),
    "mlp_fn": (ValueError, "mlp_fn", lambda cfg, params: slot_program(
        cfg, mlp_fn=lambda lp, h: (h, 0.0))),
    "training": (NotImplementedError, "backward", lambda cfg, params:
                 FAMILY.train_step(cfg, 1e-3)),
    "rotary-width": (ValueError, "its rotary turns", lambda cfg, params:
                     P.MlaKind("mla", 4, 24, 16, 12, 4, 16, 2, 8, 16,
                               P.Rope(rotary_dim=8))),
    "softmax-pairs": (NotImplementedError, "half-split", lambda cfg, params:
                      P.AttnKind("full", 4, None,
                                 P.Rope(interleave=True))),
}


@pytest.mark.parametrize("what", sorted(REFUSALS))
def test_what_this_plan_does_not_do_raises_with_the_reason(what):
    cfg, params = program()[:2]
    error, reason, call = REFUSALS[what]
    with pytest.raises(error, match=reason):
        call(cfg, params)


def test_the_plan_names_kinds_not_models():
    plan = FAMILY.layer_plan(toy(), N_LAYERS)
    assert [type(plan.kinds(l)[0]).__name__ for l in range(N_LAYERS)] \
        == ["MlaKind"] * N_LAYERS
    assert [plan.kinds(l)[1].name for l in range(N_LAYERS)] \
        == ["dense", "experts", "experts"]
    assert plan.select_topk == TOPK and not plan.recurrent
    assert P.uniform_plan(program()[0]).select_topk is None
    published = FAMILY.layer_plan(SPEC.config("glm-5"), 5)
    a = published.attn[0]
    assert (a.n_heads, a.q_rank, a.kv_rank, a.nope_dim, a.rope_dim, a.v_dim,
            a.index_heads, a.index_dim, a.topk) == (
        64, 2048, 512, 192, 64, 256, 32, 128, 2048)
    assert a.rope == P.Rope(theta=1e6, rotary_dim=64, interleave=True)
    experts = published.mlp[1]
    assert (experts.n_experts, experts.top_k, experts.held, experts.d_ff,
            experts.shared_d_ff, experts.routed_scale, experts.scoring) == (
        256, 8, (0, 16), 2048, 2048, 2.5, "sigmoid")
    assert [m for _, m in published.layers] == [0, 1, 1, 1, 1]


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
                    for s in args.get("scopes") or []]
    return out


@pytest.mark.parametrize("metric,match,scope", _cell_scopes())
def test_a_metrics_scope_names_ops_of_its_program(metric, match, scope):
    """A renamed ``jax.named_scope`` breaks this test, not a metric that
    would silently find no op in the trace."""
    assert re.search(rf'"(?:[^"]*/)?{re.escape(scope)}/[^"]*"',
                     lowered_text()[match]), (metric, scope)


def test_index_choice_and_attention_lie_inside_the_mixers_scope():
    for text in lowered_text().values():
        for inner in ("mla.index", "mla.select", "mla.attend"):
            # the ingestion's blocks run inside a loop
            assert re.search(rf"/attn\.mla/(while/body/)?{inner}/", text)
        assert "/moe.route/" in text and "/mlp.dense/" in text
