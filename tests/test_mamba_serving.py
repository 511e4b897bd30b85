"""Selective state-space layers (Mamba-1) through the slot engine: a
float32 state and a convolution tail a slot beside a multi-query
attention layer's keys and values in one cache, a tied head, and the
plain float32 reference they are held to
(``benchmarks/reference/dense_mamba_mqa.py``, which imports nothing of
the program and runs the recurrence a token at a time). Toy sizes, CPU,
float32, seeded weights: the configuration file's rehearsal widths
(hidden 32, 4 query heads of 8 over one KV head, d_inner 64, d_state 8,
dt_rank 6, kernel 4, vocabulary 384) over four layers, attention second
(the period cut to 4 so that a compile is four layers, not fourteen;
the rehearsal's own fourteen are read below).

Tolerances: program and reference both compute in float32, in another
order (chunks run side by side and a cached state against a
token-by-token scan over the whole row), so logits of magnitude ~3
agree to a few float32 roundings (5e-6 read; the limit is 2e-4
absolute, the other families'). A state held in bfloat16 between tokens
misses the limit by 118 x, bfloat16 weights and activations by 711 x,
int8 products by 2,366 x (asserted below at 30 x, 100 x and 100 x).
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
from pbs_tpu.models.mamba import MAMBA_CHUNK, mamba_scan
from pbs_tpu.models.serving import ContinuousBatcher
from pbs_tpu.models.slot_programs import _grouped_attention, slot_program
from pbs_tpu.models.spec_serving import SpeculativeBatcher
from pbs_tpu.serve import ShardedServeBackend
from pbs_tpu.serve.partition import (
    PARTITION_RULES, iter_leaf_paths, match_partition_rules)

SEED = 13
TOL = 2e-4
SPEC = Spec()
FAMILY = SPEC.family("dense-mamba-mqa")
ref = FAMILY.reference
CELL = "serve-ssm-docqa-surge"
CONFIG = "ai21-jamba2-3b"
LAYERS = 4


def toy(dtype: str = "float32") -> dict:
    full = SPEC.config(CONFIG)
    c = copy.deepcopy(overlay(full, full["rehearsal"]))
    c["attn_layer_period"], c["attn_layer_offset"] = 4, 1
    c["compute_dtype"] = c["serve"]["weights_dtype"] = dtype
    c["serve"]["num_hidden_layers"] = LAYERS
    return c


MAX_LEN, BUCKET, SLOTS, ROW = 48, 24, 3, 40


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


@pytest.mark.parametrize("admit_at", [(0, 0, 0), (0, 3, 7), (5, 0, 2)])
@pytest.mark.parametrize("plens", [(1, 2, 3), (3, 17, BUCKET), (4, 5, 23)])
def test_prefill_then_decode_agrees_with_the_full_forward(plens, admit_at):
    """Prompts shorter than the convolution's kernel and of the whole
    bucket; lanes admitted together and one after another, so that a
    lane's first ticks run beside idle ones."""
    tokens, want = tokens_and_reference()
    assert float(np.abs(want).max()) > 1.0      # logits of order one
    got = served_logits("float32", tokens, plens, admit_at, ROW)
    assert len(got) == sum(ROW - p + 1 for p in plens)
    assert worst_gap(got, want) < TOL


def test_a_bfloat16_state_fails_the_tolerance():
    """The reference with its state rounded to bfloat16 between tokens
    (everything else float32 at ``HIGHEST``) lies 30 x the tolerance
    from what the program serves: a program that held its state so
    would fail the test above by that much."""
    tokens, rounded = tokens_and_reference(quant="state")
    got = served_logits("float32", tokens, (3, 17, BUCKET), (0, 0, 0), ROW)
    assert worst_gap(got, rounded) > 30 * TOL


@pytest.mark.parametrize("against,holds", [("float32", True),
                                           ("bfloat16-state", False)])
def test_the_kernel_in_the_prompt_scan_holds_the_same_tolerance(
        monkeypatch, against, holds):
    """The one-pass kernel (``ops/mamba_scan.py``, interpreted: these 64
    channels are under its tiling on a chip) put where a TPU lowering
    has it: prefill-then-decode agrees with the full forward as it does
    through the ``jax.numpy`` scan, lanes admitted one after another,
    and the reference whose state is rounded to bfloat16 between tokens
    still lies 30 x the tolerance away."""
    from pbs_tpu.models import mamba
    from pbs_tpu.ops.mamba_scan import mamba_prompt_scan

    scanned = []

    def scan(x, *rest):
        scanned.append(x.shape)
        return mamba_prompt_scan(x, *rest, interpret=True)

    monkeypatch.setattr(mamba, "_scan", scan)
    monkeypatch.setitem(globals(), "program", functools.lru_cache(
        maxsize=None)(program.__wrapped__))
    tokens, want = tokens_and_reference(quant=False if holds else "state")
    got = served_logits("float32", tokens, (3, 17, BUCKET), (0, 3, 7), ROW)
    if holds:
        assert worst_gap(got, want) < TOL
    else:
        assert worst_gap(got, want) > 30 * TOL
    assert scanned == [(BUCKET, 64)] * 3            # a trace a layer


def test_bfloat16_in_place_of_float32_fails_the_tolerance():
    tokens, want = tokens_and_reference()
    got = served_logits("bfloat16", tokens, [3, 7, 11], (0, 0, 0), 18)
    assert worst_gap(got, want) > 100 * TOL


def test_int8_products_fail_the_tolerance():
    tokens, want = tokens_and_reference()
    _, int8 = tokens_and_reference(quant=True)
    assert float(np.abs(int8 - want).max()) > 100 * TOL


# -- the scan against the recurrence ------------------------------------------


def recurrence(x, dt, bm, cm, a_log):
    """Steps 5 and 6 a token at a time, float64 on the host: ``(y (S,
    C), state after the last token (N, C))``."""
    x, dt, bm, cm, a_log = (np.asarray(t, np.float64)
                            for t in (x, dt, bm, cm, a_log))
    A = -np.exp(a_log)
    h, out = np.zeros(a_log.shape), np.zeros(x.shape)
    for t in range(len(x)):
        h = np.exp(dt[t][None, :] * A) * h \
            + (dt[t] * x[t])[None, :] * bm[t][:, None]
        out[t] = (h * cm[t][:, None]).sum(0)
    return out, h


def scan_inputs(n: int, fast: bool = False):
    """Seeded inputs of ``n`` positions, 8 states of 16 channels: steps
    log-uniform in [0.001, 0.1] against A = -(1 .. 8) (``fast``: steps
    up to 30, decays down to e^-240 a token)."""
    keys = jax.random.split(jax.random.PRNGKey(n), 4)
    C, N = 16, 8
    x = jax.random.normal(keys[0], (n, C), jnp.float32)
    bm, cm = (jax.random.normal(k, (n, N), jnp.float32) for k in keys[1:3])
    dt = jnp.exp(jax.random.uniform(
        keys[3], (n, C), jnp.float32, np.log(1e-3),
        np.log(30.0 if fast else 0.1)))
    a_log = jnp.log(jnp.broadcast_to(jnp.arange(
        1, N + 1, dtype=jnp.float32)[:, None], (N, C)))
    return x, dt, bm, cm, a_log


#: prompt lengths 1, one under, at and over a chunk, and a non-multiple
#: of the chunk under each of two rungs (2 and 4 chunks)
LENGTHS = [(1, 128), (MAMBA_CHUNK - 1, 128), (MAMBA_CHUNK, 128),
           (MAMBA_CHUNK + 1, 128), (100, 128), (100, 256), (200, 256)]


@pytest.mark.parametrize("plen,rung", LENGTHS)
def test_the_scan_equals_the_recurrence(plen, rung):
    """Padded to a rung with no-op positions (``dt`` 0) that hold
    garbage in everything else, the chunked form gives the outputs and
    the final state the token-by-token recurrence gives over the exact
    length."""
    x, dt, bm, cm, a_log = scan_inputs(plen)
    want_y, want_h = recurrence(x, dt, bm, cm, a_log)
    pad = lambda t: jnp.pad(t, ((0, rung - plen), (0, 0)))  # noqa: E731
    junk = lambda t: pad(t).at[plen:].set(7.0)  # noqa: E731
    y, h = jax.jit(mamba_scan)(junk(x), pad(dt), junk(bm), junk(cm), a_log)
    assert y.shape == (rung, 16) and h.shape == (8, 16)
    assert float(np.abs(np.asarray(y[:plen]) - want_y).max()) < 2e-5
    assert float(np.abs(np.asarray(h) - want_h).max()) < 2e-5


def test_the_same_positions_at_two_rungs_leave_the_same_bits():
    """A chunk's arithmetic does not know how many chunks follow."""
    x, dt, bm, cm, a_log = scan_inputs(100)
    outs = []
    for rung in (128, 256):
        pad = lambda t: jnp.pad(t, ((0, rung - 100), (0, 0)))  # noqa: E731
        y, h = jax.jit(mamba_scan)(pad(x), pad(dt), pad(bm), pad(cm), a_log)
        outs.append((np.asarray(y[:100]), np.asarray(h)))
    assert np.array_equal(outs[0][0], outs[1][0])
    assert np.array_equal(outs[0][1], outs[1][1])


def test_a_decay_of_e_to_the_minus_thirty_a_token_neither_overflows_nor_nans():
    """Every exponent the scan takes is ``<= 0``: channels that forget
    at e^-30 .. e^-240 a token underflow to zero and the rest agree
    with float64 (sums of a few hundred terms of magnitude ~30: 1e-3
    absolute is a few float32 roundings of those)."""
    x, dt, bm, cm, a_log = scan_inputs(2 * MAMBA_CHUNK, fast=True)
    assert float((dt * jnp.exp(a_log)[-1]).max()) > 200
    want_y, want_h = recurrence(x, dt, bm, cm, a_log)
    y, h = jax.jit(mamba_scan)(x, dt, bm, cm, a_log)
    assert np.isfinite(np.asarray(y)).all() and np.isfinite(
        np.asarray(h)).all()
    scale = float(np.abs(want_y).max())
    assert float(np.abs(np.asarray(y) - want_y).max()) < 2e-5 * scale
    assert float(np.abs(np.asarray(h) - want_h).max()) < 2e-5 * scale


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
    for name in ("00", "02", "03"):
        a, b = short[1]["ssm"][name][1], full[1]["ssm"][name][1]
        assert float(jnp.abs(a).max()) > 1e-4
        assert float(jnp.abs(a - b).max()) < 1e-5  # chunks of 24 and 48
        a, b = short[1]["conv"][name][1], full[1]["conv"][name][1]
        assert float(jnp.abs(a - b).max()) < 1e-5
        assert bool((a[:max(0, 3 - plen)] == 0).all())
        assert float(jnp.abs(a[max(0, 3 - plen):]).min()) > 0
        # and nothing of it reached the other slot
        assert float(jnp.abs(short[1]["ssm"][name][0]).max()) == 0.0
    assert set(short[1]["k"]) == {"01"} and set(short[1]["ssm"]) == {
        "00", "02", "03"} == set(short[1]["conv"])
    assert "state" not in short[1]


def test_ingestion_starts_from_zero_whatever_the_slot_held():
    tokens, _ = tokens_and_reference()
    _cfg, params, prog, ingest, _ = program()
    clean = ingest(params, prog.init_cache(2, MAX_LEN), 1,
                   padded(tokens[0], 9), 9)
    dirty = prog.init_cache(2, MAX_LEN)
    for key in ("ssm", "conv"):
        dirty[key] = {n: jnp.full_like(x, 3.0)
                      for n, x in dirty[key].items()}
    used = ingest(params, dirty, 1, padded(tokens[0], 9), 9)
    assert bool((used[0] == clean[0]).all())
    for name in ("00", "02", "03"):
        for key in ("ssm", "conv"):
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
    for key in ("ssm", "conv"):
        for name, new in after[key].items():
            old = before[key][name]
            assert np.array_equal(np.asarray(new[1]), old[1])
            assert not np.array_equal(np.asarray(new[0]), old[0])
            assert not np.array_equal(np.asarray(new[2]), old[2])
    assert [int(p) for p in after["pos"]] == [7, 6, 7]


# -- multi-query attention and the tied head ----------------------------------


def test_every_query_head_reads_the_one_kv_head():
    """``_grouped_attention`` at ``nkv`` 1 against a loop over heads."""
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(keys[0], (2, 6, 4, 8), jnp.float32)
    k, v = (jax.random.normal(kk, (2, 6, 1, 8), jnp.float32)
            for kk in keys[1:])
    seen = jnp.tril(jnp.ones((6, 6), bool))[None]
    got = _grouped_attention(q, k, v, seen, jnp.float32)
    for head in range(4):
        s = jnp.einsum("bqd,bkd->bqk", q[:, :, head], k[:, :, 0]) / 8 ** 0.5
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
        want = jnp.einsum("bqk,bkd->bqd", p, v[:, :, 0])
        assert float(jnp.abs(got[:, :, head] - want).max()) < 1e-6


def test_the_head_is_the_embedding():
    """No ``head`` leaf; the logits of the prompt's last position are
    the normed stream against the embedding's rows, and a tied
    configuration whose layers are all alike is served by the planned
    program too (the scan's tree has a head)."""
    cfg, params, prog, ingest, _ = program()
    assert cfg.tie_embeddings and "head" not in params
    assert "head" not in P.plan_shapes(cfg)
    tokens, want = tokens_and_reference()
    last, _, _, _ = ingest(params, prog.init_cache(1, MAX_LEN), 0,
                           padded(tokens[0], 9), 9)
    assert last.shape == (toy()["vocab_size"],)
    assert float(np.abs(np.asarray(last) - want[0, 8]).max()) < TOL
    import dataclasses
    alike = dataclasses.replace(cfg, layer_plan=None)
    assert type(slot_program(alike)).__name__ == "_PlannedProgram"
    assert type(slot_program(dataclasses.replace(
        alike, tie_embeddings=False))).__name__ == "_ScanProgram"


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
    assert backend.stats()["param_leaves"] == 2 + 3 * 13 + 5 + 4 * 4
    assert serve(backend.engine, PROMPTS[:3], 8) == serve(
        engine(3), PROMPTS[:3], 8)


# -- serve/: rules; what this plan does not do ---------------------------------

NEW_LEAVES = ("w_in", "conv_w", "conv_b", "w_x", "dt_norm", "b_norm",
              "c_norm", "w_dt", "dt_bias", "a_log", "d_skip", "w_out")
ALONG_D_INNER = {"w_in": (None, -1), "conv_w": (None, -1),
                 "w_dt": (None, -1), "w_x": (-1, None),
                 "w_out": (-1, None), "conv_b": (-1,), "d_skip": (-1,)}


@pytest.mark.parametrize("leaf", NEW_LEAVES + ("attn_norm",))
def test_every_new_leaf_meets_exactly_one_rule(leaf):
    cfg, params = program()[:2]
    found = [(p, x) for p, x in iter_leaf_paths(params)
             if p.rsplit("/", 1)[-1] == leaf]
    assert len(found) == (4 if leaf == "attn_norm" else 3)
    specs = match_partition_rules(PARTITION_RULES, params)
    for path, x in found:
        hits = [pat for pat, _ in PARTITION_RULES if re.search(pat, path)]
        assert len(hits) == 1, (path, hits)
        spec = specs
        for part in path.split("/"):
            spec = spec[part]
        # what feeds a channel's state lies along d_inner; the small
        # norms, and the two names a delta-rule layer has too, replicated
        assert spec == ALONG_D_INNER.get(leaf, ()), (path, spec)
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
    made = P.init_plan_params(cfg, jax.random.PRNGKey(0))
    assert jax.tree.map(lambda x: tuple(x.shape), made) == shapes
    # Mamba's own start: A = -(1 .. d_state) a channel, D = 1, steps in
    # [0.001, 0.1]
    m = made["blocks"]["00"]["attn"]
    assert np.allclose(np.exp(np.asarray(m["a_log"]))[:, 3],
                       np.arange(1, 9))
    assert bool((m["d_skip"] == 1).all())
    step = jax.nn.softplus(m["dt_bias"])
    assert 1e-3 <= float(step.min()) and float(step.max()) <= 1e-1 + 1e-6
    assert float(jnp.abs(m["conv_w"]).max()) <= 0.5
    assert float(jnp.abs(m["conv_b"]).max()) > 0


REFUSALS = {
    "prefix": (ValueError, "state-space layer keeps one recurrent state",
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
    "training": (NotImplementedError, "no backward", lambda cfg, params:
                 FAMILY.train_step(cfg, 1e-3)),
    "experts": (NotImplementedError, "every MLP is dense",
                lambda cfg, params: FAMILY.layer_plan(
                    dict(toy(), num_experts=16), LAYERS)),
}


@pytest.mark.parametrize("what", sorted(REFUSALS))
def test_what_this_plan_does_not_do_raises_with_the_reason(what):
    cfg, params = program()[:2]
    error, reason, call = REFUSALS[what]
    with pytest.raises(error, match=reason):
        call(cfg, params)


def test_the_plan_names_kinds_not_models():
    """The rehearsal's fourteen layers are one whole period of the
    model's pattern (thirteen state-space layers, attention eighth);
    the published plan has the published widths."""
    full = SPEC.config(CONFIG)
    c = overlay(full, full["rehearsal"])
    plan = FAMILY.layer_plan(c, c["serve"]["num_hidden_layers"])
    kinds = [type(plan.kinds(l)[0]).__name__ for l in range(14)]
    assert kinds == ["MambaKind"] * 7 + ["AttnKind"] + ["MambaKind"] * 6
    assert plan.recurrent and not plan.routed
    published = FAMILY.layer_plan(full, 28)
    assert [l for l in range(28) if isinstance(
        published.kinds(l)[0], P.AttnKind)] == [7, 21]
    mamba, attn = published.attn
    assert (mamba.d_inner, mamba.d_state, mamba.dt_rank, mamba.conv) == (
        5120, 16, 160, 4)
    assert attn.rope is None and attn.gate is None and attn.window is None
    assert attn.n_heads == 20 and published.mlp[0].d_ff == 8192
    cfg = FAMILY.program_config(full, 28, 2560)
    assert (cfg.n_kv_heads, cfg.head_dim, cfg.tie_embeddings) == (
        1, 128, True)
    assert not P.uniform_plan(program()[0]).recurrent


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
    for inner in ("mamba.conv", "mamba.state"):
        assert f"/attn.mamba/{inner}/" in text["jit__decode"]
    for inner in ("mamba.conv", "mamba.scan"):
        assert f"/attn.mamba/{inner}/" in text["jit__prefill"]
    assert "/attn.mamba/mamba.scan/" not in text["jit__decode"]
    for t in text.values():
        assert "/attn.full/" in t and "/mlp.dense/" in t
