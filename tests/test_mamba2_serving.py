"""Blocks that are a mixer or an expert layer alone, Mamba-2 mixers (a
float32 matrix state a head under one scalar decay, ingested in the
chunked matrix form) and ungated relu^2 experts through the slot
engine, and the plain float32 reference they are held to
(``benchmarks/reference/moe_mamba2_gqa.py``, which imports nothing of
the program and runs the recurrence a position at a time). Toy sizes,
CPU, float32, seeded weights: the configuration file's rehearsal widths
(hidden 48, 4 Mamba heads of 8 in 2 groups over a state of 8, 4 query
heads of 16 over 2 KV heads, 4 of 8 experts of 24 held, top-3, a shared
expert of 48, vocabulary 384) over the pattern's first seven blocks
``MEMEM*E``, the prompt's matrix form in **chunks of 8** (the module's
fixture), so that prompts of 20-48 cross several hand-overs and end in
a ragged chunk.

Tolerances: program and reference both compute in float32, in another
order (a chunk's matrix products and a cached state against a
position-by-position scan over the whole row), so logits of magnitude
~3 agree to a few float32 roundings (the limit is 2e-4 absolute, the
other families'). A state held in bfloat16 between tokens misses it
(asserted below at 10 x), bfloat16 weights and activations and int8
products by more (100 x).
"""

import copy
import dataclasses
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
from pbs_tpu.models import mamba2
from pbs_tpu.models import plan as P
from pbs_tpu.models.moe import held_expert_ffn, mlp_ffn, shared_expert_ffn
from pbs_tpu.models.serving import ContinuousBatcher
from pbs_tpu.models.slot_programs import _plan_forward, slot_program
from pbs_tpu.models.spec_serving import SpeculativeBatcher
from pbs_tpu.models.transformer import TransformerConfig
from pbs_tpu.serve import ShardedServeBackend
from pbs_tpu.serve.partition import (
    PARTITION_RULES, iter_leaf_paths, match_partition_rules)

SEED = 43
TOL = 2e-4
SPEC = Spec()
FAMILY = SPEC.family("moe-mamba2-gqa")
ref = FAMILY.reference
CELL = "serve-mamba2-tooluse-surge"
CONFIG = "nemotron-3-nano-30b-a3b"
LAYERS = 7          # MEMEM*E
CHUNK = 8
MAX_LEN, BUCKET, SLOTS, ROW = 64, 48, 3, 56


@pytest.fixture(scope="module", autouse=True)
def chunks_of_eight():
    """The prompt's matrix form in chunks of 8 for this module's
    programs (read when a program is traced), and the module's own
    chunk put back after."""
    was = mamba2.MAMBA2_CHUNK
    mamba2.MAMBA2_CHUNK = CHUNK
    yield
    mamba2.MAMBA2_CHUNK = was
    program.cache_clear()


def toy(dtype: str = "float32", total: int = 8, held: int = 4) -> dict:
    full = SPEC.config(CONFIG)
    c = copy.deepcopy(overlay(full, full["rehearsal"]))
    c["compute_dtype"] = c["serve"]["weights_dtype"] = dtype
    c["serve"]["num_hidden_layers"] = LAYERS
    c["deployment"]["experts_total"] = total
    c["n_routed_experts"] = c["num_experts"] = held
    return c


@functools.lru_cache(maxsize=None)
def program(dtype: str = "float32"):
    """The toy model's configuration, weights and the two programs the
    engine makes of it (jitted once for the module)."""
    c = toy(dtype)
    cfg = FAMILY.program_config(c, LAYERS, MAX_LEN)
    params = jax.jit(lambda s: ref.init_tree(
        c, s, LAYERS, jnp.dtype(dtype)))(ref.seed_word(SEED))
    prog = slot_program(cfg)

    @jax.jit
    def decode(params, cache, last_tok, active):
        logits, new, _, route = prog.decode(params, cache, last_tok, active)
        new["pos"] = cache["pos"] + active.astype(jnp.int32)
        return logits[:, 0], new, route

    return cfg, params, prog, jax.jit(prog.ingest), decode


@functools.lru_cache(maxsize=None)
def tokens_and_reference(quant=False):
    """Three rows of ROW tokens and the reference's full forward over
    them: (B, S, V) logits (causal, so a row's prefix reads the same)."""
    c = toy()
    tokens = np.random.default_rng(SEED).integers(
        1, c["vocab_size"], (SLOTS, ROW)).astype(np.int32)
    B, S, V = SLOTS, ROW, c["vocab_size"]
    rows, cols = (a.reshape(-1) for a in np.mgrid[:B, :S])
    cand = np.tile(np.arange(V, dtype=np.int32)[:, None], (1, B * S))
    _, _, picked = ref.score_tokens(
        c, SEED, LAYERS, jnp.float32, tokens, rows.astype(np.int32),
        cols.astype(np.int32), cand, quant=quant)
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


@pytest.mark.parametrize("admit_at", [(0, 0, 0), (0, 3, 7)])
@pytest.mark.parametrize("plens", [(1, 2, 3), (20, 33, BUCKET), (7, 8, 41)])
def test_prefill_then_decode_agrees_with_the_full_forward(plens, admit_at):
    """Prompts shorter than the convolution's kernel, of whole chunks,
    with a ragged last chunk and of the whole bucket (six hand-overs);
    lanes admitted together and one after another, so that a lane's
    first ticks run beside idle ones."""
    tokens, want = tokens_and_reference()
    assert float(np.abs(want).max()) > 1.0      # logits of order one
    got = served_logits("float32", tokens, plens, admit_at, ROW)
    assert len(got) == sum(ROW - p + 1 for p in plens)
    assert worst_gap(got, want) < TOL


def test_a_bfloat16_state_fails_the_tolerance():
    """The reference with its state rounded to bfloat16 between tokens
    (everything else float32 at ``HIGHEST``) lies 10 x the tolerance
    from what the program serves: a program that held its state so
    would fail the test above by that much."""
    tokens, rounded = tokens_and_reference(quant="state")
    got = served_logits("float32", tokens, (20, 33, BUCKET), (0, 0, 0), ROW)
    assert worst_gap(got, rounded) > 10 * TOL


def test_bfloat16_in_place_of_float32_fails_the_tolerance():
    tokens, want = tokens_and_reference()
    got = served_logits("bfloat16", tokens, [20, 24, 29], (0, 0, 0), 34)
    assert worst_gap(got, want) > 100 * TOL


def test_int8_products_fail_the_tolerance():
    tokens, want = tokens_and_reference()
    _, int8 = tokens_and_reference(quant=True)
    assert float(np.abs(int8 - want).max()) > 100 * TOL


# -- the matrix form against the recurrence -----------------------------------


def recurrence(x, dt, bm, cm, a_log):
    """The recurrence a position at a time, float64 on the host: ``(y
    (S, H, P), state after the last position (H, P, N))``."""
    x, dt, bm, cm, a_log = (np.asarray(t, np.float64)
                            for t in (x, dt, bm, cm, a_log))
    S, H, P = x.shape
    G, N = bm.shape[1:]
    A = -np.exp(a_log)
    h, out = np.zeros((H, P, N)), np.zeros(x.shape)
    for t in range(S):
        bh, ch = (np.repeat(v[t], H // G, axis=0) for v in (bm, cm))
        h = np.exp(dt[t] * A)[:, None, None] * h \
            + (dt[t][:, None] * x[t])[:, :, None] * bh[:, None, :]
        out[t] = (h * ch[:, None, :]).sum(-1)
    return out, h


def scan_inputs(n: int, fast: bool = False):
    """Seeded inputs of ``n`` positions, 4 heads of 6 in 2 groups over a
    state of 8: steps log-uniform in [0.001, 0.1] against a = -(1 ..
    16) (``fast``: steps up to 30, decays down to e^-480 a token)."""
    keys = jax.random.split(jax.random.PRNGKey(n), 5)
    H, P, G, N = 4, 6, 2, 8
    x = jax.random.normal(keys[0], (n, H, P), jnp.float32)
    bm, cm = (jax.random.normal(k, (n, G, N), jnp.float32)
              for k in keys[1:3])
    dt = jnp.exp(jax.random.uniform(
        keys[3], (n, H), jnp.float32, np.log(1e-3),
        np.log(30.0 if fast else 0.1)))
    a_log = jnp.log(jax.random.uniform(keys[4], (H,), jnp.float32, 1.0,
                                       16.0))
    return x, dt, bm, cm, a_log


def _pad(t, rows):
    return jnp.pad(t, ((0, rows - t.shape[0]),) + ((0, 0),) * (t.ndim - 1))


#: prompt lengths 1, one under, at and over a chunk, several chunks with
#: a ragged last one under each of two rungs, and a rung that is no
#: multiple of the chunk
LENGTHS = [(1, 24), (CHUNK - 1, 24), (CHUNK, 24), (CHUNK + 1, 24),
           (20, 24), (20, 48), (43, 48), (48, 48), (13, 21)]


@pytest.mark.parametrize("plen,rung", LENGTHS)
def test_the_matrix_form_equals_the_recurrence(plen, rung):
    """Padded to a rung with no-op positions (``dt`` 0) that hold
    garbage in everything else, the chunked matrix form gives the
    outputs and the final state the position-by-position recurrence
    gives over the exact length, and the program's own recurrence
    (:func:`mamba2.mamba2_recurrence`) gives them too."""
    x, dt, bm, cm, a_log = scan_inputs(plen)
    want_y, want_h = recurrence(x, dt, bm, cm, a_log)
    junk = lambda t: _pad(t, rung).at[plen:].set(7.0)  # noqa: E731
    args = (junk(x), _pad(dt, rung), junk(bm), junk(cm), a_log)
    for form in (mamba2.mamba2_chunked, mamba2.mamba2_recurrence):
        y, h = jax.jit(form)(*args)
        assert y.shape == (rung, 4, 6) and h.shape == (4, 6, 8)
        assert float(np.abs(np.asarray(y[:plen]) - want_y).max()) < 2e-5
        assert float(np.abs(np.asarray(h) - want_h).max()) < 2e-5


def test_the_same_positions_at_two_rungs_leave_the_same_bits():
    """A chunk's arithmetic does not know how many chunks follow."""
    x, dt, bm, cm, a_log = scan_inputs(20)
    outs = []
    for rung in (24, 48):
        y, h = jax.jit(mamba2.mamba2_chunked)(
            *(_pad(t, rung) for t in (x, dt, bm, cm)), a_log)
        outs.append((np.asarray(y[:20]), np.asarray(h)))
    assert np.array_equal(outs[0][0], outs[1][0])
    assert np.array_equal(outs[0][1], outs[1][1])


def test_a_decay_of_e_to_the_minus_thirty_a_token_neither_overflows_nor_nans():
    """Every exponent the matrix form takes is ``<= 0`` (the pairs above
    the diagonal are masked before the exponential, not after): heads
    that forget at e^-30 .. e^-480 a token underflow to zero and the
    rest agree with float64."""
    x, dt, bm, cm, a_log = scan_inputs(3 * CHUNK, fast=True)
    assert float((dt * jnp.exp(a_log)).max()) > 100
    want_y, want_h = recurrence(x, dt, bm, cm, a_log)
    y, h = jax.jit(mamba2.mamba2_chunked)(x, dt, bm, cm, a_log)
    assert np.isfinite(np.asarray(y)).all() and np.isfinite(
        np.asarray(h)).all()
    scale = float(np.abs(want_y).max())
    assert float(np.abs(np.asarray(y) - want_y).max()) < 2e-5 * scale
    assert float(np.abs(np.asarray(h) - want_h).max()) < 2e-5 * scale


# -- padding and idle lanes ----------------------------------------------------

MAMBA_BLOCKS, ATTN_BLOCKS, EXPERT_BLOCKS = (
    ("00", "02", "04"), ("05",), ("01", "03", "06"))


@pytest.mark.parametrize("plen", [1, 2, 3, 20, 24])
def test_the_same_prompt_at_both_rungs_leaves_the_same_state(plen):
    """Every rung gives the state and the convolution tail the exact
    length would: padding is a no-op and the tail is the prompt's last
    three *real* positions (zeros before a prompt shorter than that).
    The cache holds a state and a tail for the Mamba-2 blocks, keys and
    values for the attention block, nothing for an expert block."""
    tokens, _ = tokens_and_reference()
    _cfg, params, prog, ingest, _ = program()
    short, full = (ingest(
        params, prog.init_cache(2, MAX_LEN), 1,
        padded(tokens[0], plen, rows), plen) for rows in (24, BUCKET))
    assert float(jnp.abs(short[0] - full[0]).max()) < 1e-5  # last logits
    for name in MAMBA_BLOCKS:
        a, b = short[1]["ssm"][name][1], full[1]["ssm"][name][1]
        assert a.shape == (4, 8, 8) and a.dtype == jnp.float32
        assert float(jnp.abs(a).max()) > 1e-4
        assert float(jnp.abs(a - b).max()) < 1e-5
        a, b = short[1]["conv"][name][1], full[1]["conv"][name][1]
        assert a.shape == (3, 4 * 8 + 2 * 2 * 8)
        assert float(jnp.abs(a - b).max()) < 1e-5
        assert bool((a[:max(0, 3 - plen)] == 0).all())
        assert float(jnp.abs(a[max(0, 3 - plen):]).min()) > 0
        # and nothing of it reached the other slot
        assert float(jnp.abs(short[1]["ssm"][name][0]).max()) == 0.0
    assert set(short[1]["k"]) == set(ATTN_BLOCKS) == set(short[1]["v"])
    assert set(short[1]["ssm"]) == set(MAMBA_BLOCKS) == set(
        short[1]["conv"])
    assert "state" not in short[1]


def test_ingestion_starts_from_zero_whatever_the_slot_held():
    tokens, _ = tokens_and_reference()
    _cfg, params, prog, ingest, _ = program()
    clean = ingest(params, prog.init_cache(2, MAX_LEN), 1,
                   padded(tokens[0], 21), 21)
    dirty = prog.init_cache(2, MAX_LEN)
    for key in ("ssm", "conv"):
        dirty[key] = {n: jnp.full_like(x, 3.0)
                      for n, x in dirty[key].items()}
    used = ingest(params, dirty, 1, padded(tokens[0], 21), 21)
    assert bool((used[0] == clean[0]).all())
    for name in MAMBA_BLOCKS:
        for key in ("ssm", "conv"):
            assert bool((used[1][key][name][1]
                         == clean[1][key][name][1]).all())
            assert bool((used[1][key][name][0] == 3.0).all())


def test_an_inactive_lanes_state_is_bit_identical_after_a_tick():
    tokens, _ = tokens_and_reference()
    _cfg, params, prog, ingest, decode = program()
    cache = prog.init_cache(SLOTS, MAX_LEN)
    for b in range(SLOTS):
        _, cache, _, _ = ingest(params, cache, b, padded(tokens[b], 20), 20)
    before = jax.tree.map(np.asarray, cache)
    active = np.array([True, False, True])
    _, after, route = decode(params, cache, jnp.asarray(tokens[:, 20]),
                             jnp.asarray(active))
    for key in ("ssm", "conv"):
        for name, new in after[key].items():
            old = before[key][name]
            assert np.array_equal(np.asarray(new[1]), old[1])
            assert not np.array_equal(np.asarray(new[0]), old[0])
            assert not np.array_equal(np.asarray(new[2]), old[2])
    assert [int(p) for p in after["pos"]] == [21, 20, 21]
    # two tokens routed; 3 choices each in each of the 3 expert blocks
    assert int(route[0]) == 2 and int(route[1] + route[2]) == 2 * 3 * 3


# -- a block that is one half --------------------------------------------------


def _tiny(plan, n_layers):
    return TransformerConfig(
        vocab=64, d_model=32, n_layers=n_layers, n_heads=4, n_kv_heads=2,
        d_ff=64, max_seq=24, dtype=jnp.float32, layer_plan=plan)


def _forward(cfg, params, tokens):
    prog = slot_program(cfg)
    cache = prog.init_cache(1, 24)
    last, cache, _, _ = jax.jit(prog.ingest)(
        params, cache, 0, jnp.asarray(tokens), len(tokens) - 2)
    logits, _, _, _ = jax.jit(prog.decode)(
        params, dict(cache, pos=cache["pos"]), jnp.asarray(tokens[-2:-1]),
        jnp.ones((1,), bool))
    return np.asarray(last), np.asarray(logits)


def test_a_block_of_two_halves_is_the_two_blocks_of_one_half_each():
    """Two layers, each a gated full-attention mixer and a block of
    experts (the plan every earlier configuration has), against the same
    weights laid as four blocks of one half each: the same operations in
    the same order, so the same bits, through ingestion and a decode
    tick. And the joined plan's tree is what it always was (``attn`` and
    ``mlp`` under every block)."""
    attn = P.AttnKind("full", 4, None, P.Rope(), head_gate=True)
    mlp = P.MlpKind("experts", 16, n_experts=4, top_k=2, held=(0, 4),
                    shared_d_ff=16)
    joined = _tiny(P.LayerPlan((attn,), (mlp,), ((0, 0), (0, 0))), 2)
    split = _tiny(P.LayerPlan((attn,), (mlp,), (
        (0, None), (None, 0), (0, None), (None, 0))), 4)
    params = P.init_plan_params(joined, jax.random.PRNGKey(3))
    assert all(set(b) == {"attn", "mlp"} for b in params["blocks"].values())
    halves = dict(params, blocks={
        "00": {"attn": params["blocks"]["00"]["attn"]},
        "01": {"mlp": params["blocks"]["00"]["mlp"]},
        "02": {"attn": params["blocks"]["01"]["attn"]},
        "03": {"mlp": params["blocks"]["01"]["mlp"]}})
    assert jax.tree.map(lambda x: tuple(x.shape), halves) \
        == P.plan_shapes(split)
    tokens = np.arange(3, 15, dtype=np.int32)
    for got, want in zip(_forward(split, halves, tokens),
                         _forward(joined, params, tokens)):
        assert np.array_equal(got, want) and float(np.abs(want).max()) > 0.1


def test_a_block_has_a_mixer_an_mlp_or_both():
    with pytest.raises(ValueError, match="a mixer, an MLP or both"):
        P.LayerPlan((P.AttnKind("full", 4),), (P.MlpKind("dense", 8),),
                    ((0, 0), (None, None)))
    with pytest.raises(ValueError, match="unknown form"):
        P.MlpKind("dense", 8, form="gelu")
    with pytest.raises(ValueError, match="groups"):
        P.Mamba2Kind("mamba2", 6, 8, 4, 8)


# -- the expert's form ----------------------------------------------------------


def expert_layer(c, at: int = 1):
    """Normed rows and the outer weights of expert block ``at``."""
    h = jax.random.normal(jax.random.PRNGKey(7), (40, c["hidden_size"]),
                          jnp.float32)
    return h, ref.sparse_outer_weights(c, ref.seed_word(SEED), at,
                                       jnp.float32)


def held_part(c, h, outer, first: int, count: int, at: int = 1):
    kind = dataclasses.replace(
        FAMILY.layer_plan(c, LAYERS).mlp[0], held=(first, count))
    lp = dict(outer, **ref.expert_block(c, ref.seed_word(SEED), at, first,
                                        count, jnp.float32))
    return held_expert_ffn(h, lp, kind, jnp.ones((h.shape[0],), bool),
                           jnp.float32)


def test_the_ungated_expert_is_relu_squared_between_two_matrices():
    """``mlp_ffn`` against a hand product in both forms; the shared
    expert reads its layer's form; the gated form is what it was."""
    k = jax.random.split(jax.random.PRNGKey(11), 4)
    h = jax.random.normal(k[0], (5, 12), jnp.float32)
    w1, w3 = (jax.random.normal(kk, (12, 20), jnp.float32) for kk in k[1:3])
    w2 = jax.random.normal(k[3], (20, 12), jnp.float32)
    dot = lambda rows, w: rows @ w  # noqa: E731
    want = np.square(np.maximum(np.asarray(h) @ np.asarray(w1), 0)) \
        @ np.asarray(w2)
    got = mlp_ffn(h, w1, None, w2, "relu2", dot)
    assert float(np.abs(np.asarray(got) - want).max()) < 1e-4
    gated = (jax.nn.silu(h @ w1) * (h @ w3)) @ w2
    assert np.array_equal(np.asarray(mlp_ffn(h, w1, w3, w2, "silu", dot)),
                          np.asarray(gated))
    lp = {"ws1": w1, "ws3": w3, "ws2": w2}
    assert np.array_equal(np.asarray(shared_expert_ffn(h, lp, jnp.float32)),
                          np.asarray(gated))
    assert float(np.abs(np.asarray(shared_expert_ffn(
        h, {"ws1": w1, "ws2": w2}, jnp.float32, "relu2")) - want).max()) \
        < 1e-4
    with pytest.raises(ValueError, match="unknown MLP form"):
        mlp_ffn(h, w1, w3, w2, "gelu", dot)


@pytest.mark.parametrize("dense_pairs", [4096, 0])
def test_the_shares_add_up_to_the_uncut_layer(dense_pairs, monkeypatch):
    """The four shares of 32 experts at toy size: four shares of 2 of 8
    experts, the shared expert counted once, against the reference's
    block over all 8, weighted by 2.5; no token is dropped and the
    counters count what the router chose. Through every held expert (a
    tick's few rows) and through the sorted rows' grouped product."""
    from pbs_tpu.models import moe

    monkeypatch.setattr(moe, "DENSE_PAIRS", dense_pairs)
    c = toy(total=8, held=2)
    assert c["routed_scaling_factor"] == 2.5
    h, outer = expert_layer(c)
    gate = ref.routing(c, h, outer["router"], outer["router_bias"], False)
    want = ref.block_of_experts(h, gate, ref.expert_block(
        c, ref.seed_word(SEED), 1, 0, 8, jnp.float32), False) \
        + ref.relu2(h, outer["ws1"], outer["ws2"], False)
    parts, counts = zip(*(held_part(c, h, outer, first, 2)
                          for first in range(0, 8, 2)))
    got = sum(parts) + shared_expert_ffn(h, outer, jnp.float32, "relu2")
    assert float(jnp.abs(got - want).max()) < 1e-4
    chosen = np.asarray(gate > 0)
    for s, cnt in enumerate(counts):
        mine = chosen[:, 2 * s:2 * s + 2]
        assert [int(x) for x in cnt] == [
            mine.sum(), chosen.sum() - mine.sum(),
            mine.any(0).sum(), mine.sum(0).max()]
    assert sum(int(cnt[0]) for cnt in counts) == 40 * 3


@pytest.mark.parametrize("form", ["relu2", "silu"])
def test_few_rows_through_every_expert_are_the_sorted_rows_sum(
        form, monkeypatch):
    """Rows that make ``DENSE_PAIRS`` (row, held expert) pairs or fewer
    skip the sort and go through every
    held expert under a weight that is zero where a row did not choose
    it: the same sum and the same counters as the grouped product over
    sorted rows, in both forms, rows that are no tokens (idle lanes)
    adding nothing and counted nowhere."""
    from pbs_tpu.models import moe

    c = toy(total=8, held=4)
    h, outer = expert_layer(c)
    kind = dataclasses.replace(FAMILY.layer_plan(c, LAYERS).mlp[0],
                               held=(2, 4), form=form)
    lp = dict(outer, **ref.expert_block(c, ref.seed_word(SEED), 1, 2, 4,
                                        jnp.float32))
    if form == "silu":
        lp["we3"] = jax.random.normal(jax.random.PRNGKey(3),
                                      lp["we1"].shape, jnp.float32) / 7
    valid = jnp.arange(h.shape[0]) % 5 != 0
    assert h.shape[0] * kind.held[1] <= moe.DENSE_PAIRS
    dense, dense_counts = held_expert_ffn(h, lp, kind, valid, jnp.float32)
    monkeypatch.setattr(moe, "DENSE_PAIRS", 0)
    rows, row_counts = held_expert_ffn(h, lp, kind, valid, jnp.float32)
    assert float(jnp.abs(dense - rows).max()) < 1e-5
    assert float(jnp.abs(rows).max()) > 0.1
    assert np.array_equal(np.asarray(dense_counts), np.asarray(row_counts))
    assert not np.asarray(dense)[::5].any()


# -- the engine: a lane reused ------------------------------------------------


def serve(engine, prompts, max_new):
    done = {}
    for p in prompts:
        engine.submit(p, max_new)
    while engine.has_work():
        done.update({c.request_id: list(c.tokens) for c in engine.step()})
    return [done[i] for i in range(len(prompts))]


PROMPTS = [[5, 9, 2], [7] * 21, [3, 1, 4, 1, 5, 9, 2, 6], [11, 12],
           list(range(20, 20 + BUCKET))]


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


@pytest.mark.parametrize("dense_pairs", [4096, 0])
def test_the_engine_serves_the_references_best_tokens(dense_pairs,
                                                      monkeypatch):
    """Through ``ContinuousBatcher`` (admission, the pipelined tick,
    retirement): every token three lanes serve for three prompts of
    20-48 is, under the reference's full forward over prompt and
    answer, within the tolerance of the reference's best logit
    there; with the experts' products through every held expert (what
    a toy's few rows take) and through the sorted rows' grouped
    product (what a real prompt takes)."""
    from pbs_tpu.models import moe

    monkeypatch.setattr(moe, "DENSE_PAIRS", dense_pairs)
    tokens, _ = tokens_and_reference()
    prompts = [list(map(int, tokens[b][:p]))
               for b, p in enumerate((20, 33, BUCKET))]
    answers = serve(engine(3), prompts, 8)
    c = toy()
    rows = np.zeros((3, ROW), np.int32)
    at = []
    for b, (p, a) in enumerate(zip(prompts, answers)):
        seq = p + a[:-1]
        rows[b, :len(seq)] = seq
        at += [(b, len(p) - 1 + i, tok) for i, tok in enumerate(a)]
    r, cpos, tok = (np.asarray(x, np.int32) for x in zip(*at))
    best, _, picked = ref.score_tokens(
        c, SEED, LAYERS, jnp.float32, rows, r, cpos, tok[None, :])
    assert float((best - picked[0]).max()) < TOL


def test_sharded_serve_backend_serves_the_tree():
    cfg, params = program()[:2]
    backend = ShardedServeBackend("engine", cfg, params, tp=1, dp=1,
                                  n_slots=2, prompt_bucket=BUCKET,
                                  max_len=MAX_LEN)
    # embed, final norm, head; 3 Mamba-2 blocks of 9 leaves, 1 attention
    # block of 5, 3 expert blocks of 7 (two matrices an expert)
    assert backend.stats()["param_leaves"] == 3 + 3 * 9 + 5 + 3 * 7
    assert serve(backend.engine, PROMPTS[:3], 8) == serve(
        engine(3), PROMPTS[:3], 8)


# -- serve/: rules; what this plan does not do ---------------------------------

MAMBA2_LEAVES = ("attn_norm", "w_in", "conv_w", "conv_b", "dt_bias", "a_log",
                 "d_skip", "g_norm", "w_out")
SPECS = {"w_in": (None, -1), "conv_w": (None, -1), "w_out": (-1, None),
         "conv_b": (-1,), "d_skip": (-1,)}


@pytest.mark.parametrize("leaf", MAMBA2_LEAVES)
def test_every_leaf_of_the_mixer_meets_exactly_one_rule(leaf):
    cfg, params = program()[:2]
    found = [(p, x) for p, x in iter_leaf_paths(params)
             if p.rsplit("/", 1)[-1] == leaf
             and p.split("/")[1] in MAMBA_BLOCKS]
    assert len(found) == 3
    specs = match_partition_rules(PARTITION_RULES, params)
    for path, x in found:
        hits = [pat for pat, _ in PARTITION_RULES if re.search(pat, path)]
        assert len(hits) == 1, (path, hits)
        spec = specs
        for part in path.split("/"):
            spec = spec[part]
        assert spec == SPECS.get(leaf, ()), (path, spec)
        assert not spec or len(spec) == x.ndim


def test_the_whole_tree_is_the_plans_and_every_leaf_has_one_rule():
    cfg, params = program()[:2]
    for path, _ in iter_leaf_paths(params):
        hits = [pat for pat, _ in PARTITION_RULES if re.search(pat, path)]
        assert len(hits) == 1, (path, hits)
    shapes = jax.tree.map(lambda x: tuple(x.shape), params)
    assert shapes == P.plan_shapes(cfg)
    for name in MAMBA_BLOCKS + ATTN_BLOCKS:
        assert set(params["blocks"][name]) == {"attn"}
    for name in EXPERT_BLOCKS:
        assert set(params["blocks"][name]) == {"mlp"}
        assert set(params["blocks"][name]["mlp"]) == {
            "mlp_norm", "router", "router_bias", "we1", "we2", "ws1", "ws2"}
    made = P.init_plan_params(cfg, jax.random.PRNGKey(0))
    assert jax.tree.map(lambda x: tuple(x.shape), made) == shapes
    # Mamba-2's own start: a = -uniform(1, 16) a head, D = 1, steps in
    # [0.001, 0.1]; the reference's tree starts alike
    for m in (made["blocks"]["00"]["attn"], params["blocks"]["00"]["attn"]):
        a = np.exp(np.asarray(m["a_log"]))
        assert a.shape == (4,) and 1 <= a.min() and a.max() <= 16
        assert bool((m["d_skip"] == 1).all()) and bool(
            (m["g_norm"] == 1).all())
        step = jax.nn.softplus(m["dt_bias"])
        assert 1e-3 <= float(step.min()) + 1e-7
        assert float(step.max()) <= 1e-1 + 1e-6
        assert float(jnp.abs(m["conv_w"]).max()) <= 0.5
        assert float(jnp.abs(m["conv_b"]).max()) > 0


REFUSALS = {
    "prefix": (ValueError, "matrix-state layer keeps one float32",
               lambda cfg, params: ContinuousBatcher(
                   cfg, params, n_slots=2, prompt_bucket=12, max_len=40,
                   prefix_cache_size=2)),
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
    "grouped-router": (NotImplementedError, "group limit",
                       lambda cfg, params: FAMILY.layer_plan(
                           dict(toy(), n_group=2), LAYERS)),
    "verify-window": (NotImplementedError, "one position a tick",
                      lambda cfg, params: _plan_forward(
                          cfg, params, jnp.zeros((1, 2), jnp.int32),
                          slot_program(cfg).init_cache(1, MAX_LEN),
                          jnp.zeros((1,), jnp.int32),
                          jnp.ones((1, 2), bool))),
}


@pytest.mark.parametrize("what", sorted(REFUSALS))
def test_what_this_plan_does_not_do_raises_with_the_reason(what):
    cfg, params = program()[:2]
    error, reason, call = REFUSALS[what]
    with pytest.raises(error, match=reason):
        call(cfg, params)


def test_the_plan_names_kinds_not_models():
    """The rehearsal's seven blocks and the cell's 26 are the pattern's
    first letters, each block one half; the published plan has the
    published widths."""
    plan = program()[0].layer_plan
    halves = [tuple(type(k).__name__ for k in plan.kinds(l))
              for l in range(LAYERS)]
    M, E, A = (("Mamba2Kind", "NoneType"), ("NoneType", "MlpKind"),
               ("AttnKind", "NoneType"))
    assert halves == [M, E, M, E, M, A, E]
    assert plan.recurrent and plan.routed and plan.select_topk is None
    full = SPEC.config(CONFIG)
    assert len(full["hybrid_override_pattern"]) == 52
    published = FAMILY.layer_plan(full, full["serve"]["num_hidden_layers"])
    letters = "".join(
        "E" if a is None else "M" if isinstance(a, P.Mamba2Kind) else "*"
        for a, _ in map(published.kinds, range(26)))
    assert letters == "MEMEM*EMEMEM*EMEMEM*EMEMEM"
    m2, attn = published.attn
    assert (m2.n_heads, m2.head_dim, m2.n_groups, m2.d_state, m2.conv,
            m2.d_inner, m2.d_conv) == (64, 64, 8, 128, 4, 4096, 6144)
    assert attn.rope is None and attn.gate is None and attn.window is None
    assert attn.n_heads == 32
    experts, = published.mlp
    assert (experts.d_ff, experts.n_experts, experts.top_k, experts.held,
            experts.shared_d_ff, experts.routed_scale, experts.scoring,
            experts.form) == (1856, 128, 6, (0, 32), 3712, 2.5, "sigmoid",
                              "relu2")
    cfg = FAMILY.program_config(full, 26, 3072)
    assert (cfg.d_model, cfg.n_kv_heads, cfg.head_dim, cfg.vocab,
            cfg.tie_embeddings) == (2688, 2, 128, 32768, False)
    cache = jax.eval_shape(lambda: slot_program(cfg).init_cache(128, 3072))
    assert cache["ssm"]["00"].shape == (128, 64, 64, 128)
    assert cache["ssm"]["00"].dtype == jnp.float32
    assert cache["conv"]["00"].shape == (128, 3, 6144)
    assert sorted(cache["k"]) == ["05", "12", "19"]
    assert len(cache["ssm"]) == 12


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
    assert re.search(rf'"[^"]*{re.escape(scope)}/[^"]*"',
                     lowered_text()[match]), (metric, scope)


def test_the_state_update_lies_inside_the_mixers_scope():
    text = lowered_text()
    for inner in ("mamba2.conv", "mamba2.step", "mamba2.norm"):
        assert f"/attn.mamba2/{inner}/" in text["jit__decode"]
    for inner in ("mamba2.conv", "mamba2.scan", "mamba2.norm"):
        assert f"/attn.mamba2/{inner}/" in text["jit__prefill"]
    assert "/attn.mamba2/mamba2.scan/" not in text["jit__decode"]
    assert "/attn.mamba2/mamba2.step/" not in text["jit__prefill"]
    for t in text.values():
        assert all(f"/{s}/" in t for s in (
            "attn.full", "moe.route", "moe.experts", "moe.shared"))
        assert "/mlp.dense/" not in t
