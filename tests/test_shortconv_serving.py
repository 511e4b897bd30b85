"""A mixer that is a gated short convolution alone (two rows of a
lane's memory and nothing else), grouped-query layers whose 64-wide
heads are RMS-normed before the rotary and lie two to a row of the
cache, and an expert layer held whole behind a sigmoid router that
renormalises by ``sum + 1e-6``, through the slot engine, and the plain
float32 reference they are held to
(``benchmarks/reference/moe_conv_gqa.py``, which imports nothing of the
program and forms the convolution as an explicit sum over the
zero-padded sequence). Toy sizes, CPU, float32, seeded weights: the
configuration file's rehearsal widths (hidden 256, 4 query heads of 64
over 2 KV heads, so that the cache lies packed as the published widths'
does; a dense width of 96, 8 experts of 32, top-2; vocabulary 384) over
``layer_types``' first ten layers, ``conv conv attn conv conv conv attn
conv conv conv``, the first two with a dense MLP.

Tolerances: program and reference both compute in float32, in another
order (a cached tail and cached keys against one pass over the whole
row; the experts' sorted rows against every expert under a zero
weight), so logits of magnitude ~3 agree to a few float32 roundings
(the limit is 2e-4 absolute, the other families'). A mixer without its
tail, heads without their norms, a router without its selection bias,
bfloat16 weights and activations and int8 products all miss it by 100 x
or more (asserted below).
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
from pbs_tpu.models import plan as P
from pbs_tpu.models import shortconv, slot_programs
from pbs_tpu.models.moe import held_expert_ffn, route_top_k
from pbs_tpu.models.serving import ContinuousBatcher
from pbs_tpu.models.slot_programs import (
    _from_packed, _grouped_attention, _plan_forward, _to_packed, kv_pack,
    slot_program)
from pbs_tpu.models.spec_serving import SpeculativeBatcher
from pbs_tpu.models.transformer import TransformerConfig
from pbs_tpu.ops.kv_attend import kv_attend, kv_attend_tiles
from pbs_tpu.serve import ShardedServeBackend
from pbs_tpu.serve.partition import (
    PARTITION_RULES, TEMPLATE_PATHS, audit_rules, iter_leaf_paths,
    match_partition_rules)

SEED = 47
TOL = 2e-4
SPEC = Spec()
FAMILY = SPEC.family("moe-conv-gqa")
ref = FAMILY.reference
CELL = "serve-conv-writing-surge"
CONFIG = "lfm2-24b-a2b"
LAYERS = 10
MAX_LEN, BUCKET, SLOTS, ROW = 64, 48, 3, 56
CONV_BLOCKS = ("00", "01", "03", "04", "05", "07", "08", "09")
ATTN_BLOCKS = ("02", "06")


def toy(dtype: str = "float32", total: int = 8, held: int = 8) -> dict:
    full = SPEC.config(CONFIG)
    c = copy.deepcopy(overlay(full, full["rehearsal"]))
    c["compute_dtype"] = c["serve"]["weights_dtype"] = dtype
    c["deployment"]["experts_total"] = total
    c["num_experts"] = held
    return c


@functools.lru_cache(maxsize=None)
def program(dtype: str = "float32", qk_norm: bool = True):
    """The toy model's configuration, weights and the two programs the
    engine makes of it (jitted once for the module). ``qk_norm`` False
    is the same weights under a plan whose heads are not normed."""
    c = toy(dtype)
    cfg = FAMILY.program_config(c, LAYERS, MAX_LEN)
    params = jax.jit(lambda s: ref.init_tree(
        c, s, LAYERS, jnp.dtype(dtype)))(ref.seed_word(SEED))
    if not qk_norm:
        plan = cfg.layer_plan
        cfg = dataclasses.replace(cfg, layer_plan=dataclasses.replace(
            plan, attn=tuple(
                dataclasses.replace(a, qk_norm=False)
                if isinstance(a, P.AttnKind) else a for a in plan.attn)))
        params = dict(params, blocks={
            name: {"attn": {k: v for k, v in b["attn"].items()
                            if k not in ("q_norm", "k_norm")},
                   "mlp": b["mlp"]}
            for name, b in params["blocks"].items()})
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


# -- tail and cache against the full forward -----------------------------------


def served_logits(tokens, plens, admit_at, length, dtype="float32",
                  edit=None, **which):
    """Teacher-forced serving of the first ``length`` tokens of each row
    of ``tokens``: slot b is given its prompt (``plens[b]`` tokens) at
    tick ``admit_at[b]`` and then decodes the rest, one position a tick,
    beside whatever else is in the cache (lanes not yet admitted ride
    along inactive). ``edit(params)`` changes the weights first.
    Returns ``{(b, position): logits}`` for the prompt's last position
    and every decoded one."""
    _cfg, params, prog, ingest, decode = program(dtype, **which)
    if edit is not None:
        params = edit(params)
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
    """Prompts shorter than the filter (the tail's zeros are read), of
    a few positions and of the whole bucket; lanes admitted together
    and one after another, so that a lane's first ticks run beside idle
    ones."""
    tokens, want = tokens_and_reference()
    assert float(np.abs(want).max()) > 1.0      # logits of order one
    got = served_logits(tokens, plens, admit_at, ROW)
    assert len(got) == sum(ROW - p + 1 for p in plens)
    assert worst_gap(got, want) < TOL


def _without(*leaves, scale=0.0):
    """The weights with every leaf of those names times ``scale``."""
    def edit(params):
        return dict(params, blocks={
            name: {half: {k: v * scale if k in leaves else v
                          for k, v in part.items()}
                   for half, part in block.items()}
            for name, block in params["blocks"].items()})
    return edit


def test_a_mixer_that_forgets_its_tail_fails_the_tolerance(monkeypatch):
    """The decode step with the tail it is given zeroed (a mixer that
    sees its newest position alone) lies 100 x the tolerance from the
    reference."""
    step = shortconv.conv_decode
    monkeypatch.setitem(
        slot_programs._RECURRENT, P.ConvKind,
        ("attn.conv", ("conv",),
         lambda a, ap, h, tail, *rest: step(a, ap, h, jnp.zeros_like(tail),
                                            *rest),
         shortconv.conv_ingest))
    program.cache_clear()
    try:
        tokens, want = tokens_and_reference()
        got = served_logits(tokens, (20, 33, BUCKET), (0, 0, 0), ROW)
        assert worst_gap(got, want) > 100 * TOL
    finally:
        program.cache_clear()


def test_heads_without_their_norms_fail_the_tolerance():
    tokens, want = tokens_and_reference()
    got = served_logits(tokens, (20, 33, BUCKET), (0, 0, 0), ROW,
                        qk_norm=False)
    assert worst_gap(got, want) > 100 * TOL


def test_a_router_without_its_selection_bias_fails_the_tolerance():
    """The bias is 0.005 x normal and takes part in the choice alone:
    without it some token of 168 chooses another expert in some of
    eight layers."""
    tokens, want = tokens_and_reference()
    got = served_logits(tokens, (20, 33, BUCKET), (0, 0, 0), ROW,
                        edit=_without("router_bias"))
    assert worst_gap(got, want) > 100 * TOL


def test_bfloat16_in_place_of_float32_fails_the_tolerance():
    tokens, want = tokens_and_reference()
    got = served_logits(tokens, [20, 24, 29], (0, 0, 0), 34, "bfloat16")
    assert worst_gap(got, want) > 100 * TOL


def test_int8_products_fail_the_tolerance():
    tokens, want = tokens_and_reference()
    _, int8 = tokens_and_reference(quant=True)
    assert float(np.abs(int8 - want).max()) > 100 * TOL


# -- the tail -----------------------------------------------------------------


@pytest.mark.parametrize("plen", [1, 2, 3, 20, 24])
def test_a_padded_prompt_leaves_what_the_exact_length_leaves(plen):
    """Every padded length gives the last logits, the tail and the keys
    the shorter one gives: padding is a no-op and the tail is ``g`` at
    the prompt's last two *real* positions (zeros before a prompt
    shorter than that). The cache holds a tail for the convolution
    layers, keys and values for the attention layers, and no state."""
    tokens, _ = tokens_and_reference()
    _cfg, params, prog, ingest, _ = program()
    short, full = (ingest(
        params, prog.init_cache(2, MAX_LEN), 1,
        padded(tokens[0], plen, rows), plen) for rows in (24, BUCKET))
    assert float(jnp.abs(short[0] - full[0]).max()) < 1e-5  # last logits
    for name in CONV_BLOCKS:
        a, b = short[1]["conv"][name][1], full[1]["conv"][name][1]
        assert a.shape == (2, 256) and a.dtype == jnp.float32
        assert float(jnp.abs(a - b).max()) < 1e-5
        assert bool((a[:max(0, 2 - plen)] == 0).all())
        assert float(jnp.abs(a[max(0, 2 - plen):]).min()) > 0
        # and nothing of it reached the other slot
        assert float(jnp.abs(short[1]["conv"][name][0]).max()) == 0.0
    for name in ATTN_BLOCKS:
        a, b = short[1]["k"][name][1], full[1]["k"][name][1]
        assert float(jnp.abs(a[:plen] - b[:plen]).max()) < 1e-5
        assert float(jnp.abs(a[:plen]).min()) > 0
    assert set(short[1]["k"]) == set(ATTN_BLOCKS) == set(short[1]["v"])
    assert set(short[1]["conv"]) == set(CONV_BLOCKS)
    assert set(short[1]) == {"k", "v", "conv", "pos"}


@pytest.mark.parametrize("plen", [1, 2, 5, 17])
def test_the_tail_after_ingestion_is_the_tail_after_stepping(plen):
    """One layer's mixer alone: the whole prompt at once against the
    same rows one position a step from a zero tail: the same outputs
    and the same tail (the same ``g``, the same three-term sum; the
    in-projection of 24 rows at once and of one row a call round
    apart in float32)."""
    _cfg, params, _prog, _, _ = program()
    a, ap = P.ConvKind("conv", 256, conv=3), params["blocks"]["03"]["attn"]
    h = jax.random.normal(jax.random.PRNGKey(plen), (1, 24, 256),
                          jnp.float32)
    valid = (jnp.arange(24) < plen)[None]
    y, tail = shortconv.conv_ingest(a, ap, h, valid, 1e-5, jnp.float32)
    stepped, ys = jnp.zeros((1, 2, 256), jnp.float32), []
    for t in range(plen):
        out, stepped = shortconv.conv_decode(
            a, ap, h[:, t:t + 1], stepped, jnp.ones((1,), bool), 1e-5,
            jnp.float32)
        ys.append(out)
    assert float(jnp.abs(tail - stepped).max()) < 1e-5
    assert float(jnp.abs(y[:, :plen] - jnp.concatenate(ys, 1)).max()) < 1e-5
    assert float(jnp.abs(tail[0, -1]).min()) > 0


def test_the_filter_is_the_three_term_sum_over_the_padded_sequence():
    """The mixer by hand in numpy: ``c_t = sum_j w[j] g_{t-2+j}`` with
    zeros before the first position, ``y = (C * c) W_out``, the
    in-projection's columns ``B | C | u``."""
    _cfg, params, _prog, _, _ = program()
    a, ap = P.ConvKind("conv", 256, conv=3), params["blocks"]["00"]["attn"]
    h = np.asarray(jax.random.normal(jax.random.PRNGKey(2), (1, 9, 256),
                                     jnp.float32))
    y, _ = shortconv.conv_ingest(a, ap, jnp.asarray(h), jnp.ones((1, 9), bool),
                                 1e-5, jnp.float32)
    w_in, w, w_out = (np.asarray(ap[k], np.float64)
                      for k in ("w_in", "conv_w", "w_out"))
    bcu = h[0].astype(np.float64) @ w_in
    g = np.concatenate([np.zeros((2, 256)), bcu[:, :256] * bcu[:, 512:]])
    conved = sum(w[j] * g[j:j + 9] for j in range(3))
    want = (bcu[:, 256:512] * conved) @ w_out
    assert float(np.abs(np.asarray(y[0]) - want).max()) < 1e-4
    assert float(np.abs(want).max()) > 0.1


def test_ingestion_starts_from_zero_whatever_the_slot_held():
    tokens, _ = tokens_and_reference()
    _cfg, params, prog, ingest, _ = program()
    clean = ingest(params, prog.init_cache(2, MAX_LEN), 1,
                   padded(tokens[0], 21), 21)
    dirty = prog.init_cache(2, MAX_LEN)
    dirty["conv"] = {n: jnp.full_like(x, 3.0)
                     for n, x in dirty["conv"].items()}
    used = ingest(params, dirty, 1, padded(tokens[0], 21), 21)
    assert bool((used[0] == clean[0]).all())
    for name in CONV_BLOCKS:
        assert bool((used[1]["conv"][name][1]
                     == clean[1]["conv"][name][1]).all())
        assert bool((used[1]["conv"][name][0] == 3.0).all())


def test_an_inactive_lanes_tail_and_keys_are_bit_identical_after_a_tick():
    tokens, _ = tokens_and_reference()
    _cfg, params, prog, ingest, decode = program()
    cache = prog.init_cache(SLOTS, MAX_LEN)
    for b in range(SLOTS):
        _, cache, _, _ = ingest(params, cache, b, padded(tokens[b], 20), 20)
    before = jax.tree.map(np.asarray, cache)
    active = np.array([True, False, True])
    _, after, route = decode(params, cache, jnp.asarray(tokens[:, 20]),
                             jnp.asarray(active))
    for name, new in after["conv"].items():
        old = before["conv"][name]
        assert np.array_equal(np.asarray(new[1]), old[1])
        assert not np.array_equal(np.asarray(new[0]), old[0])
        # shifted by one: the older row is the one that was newer
        assert np.array_equal(np.asarray(new[0][0]), old[0][1])
    for kv in ("k", "v"):
        for name, new in after[kv].items():
            old = before[kv][name]
            # the idle lane's 20 positions stay; what a lane that holds
            # nothing writes at its cursor is never read (the next
            # prompt or the next tick overwrites it)
            assert np.array_equal(np.asarray(new[1][:20]), old[1][:20])
            assert np.array_equal(np.asarray(new[0][:20]), old[0][:20])
            assert np.asarray(new[0][20]).any()
    assert [int(p) for p in after["pos"]] == [21, 20, 21]
    # two tokens routed; 2 choices each in each of the 8 expert layers
    assert int(route[0]) == 2 and int(route[1]) == 2 * 2 * 8
    assert int(route[2]) == 0           # every expert is held


# -- heads two to a row -----------------------------------------------------------


@pytest.mark.parametrize("nkv,hd,pack", [
    (8, 64, 2), (2, 64, 2), (8, 128, 1), (2, 128, 1), (1, 128, 1),
    (1, 64, 1), (3, 64, 1), (8, 32, 4), (2, 16, 1), (8, 16, 8),
    (8, 256, 1), (8, 96, 1)])
def test_heads_narrower_than_a_row_lie_several_to_a_row(nkv, hd, pack):
    assert kv_pack(nkv, hd) == pack


def test_the_cache_of_the_published_widths_costs_what_it_holds():
    """256 slots x 3,072 positions: an attention layer's keys are
    ``(256, 3072, 4, 128)`` bfloat16, 1 KiB a position (with the values
    2 KiB a layer, not the 4 a row of 128 lanes a 64-wide head would
    cost), and shapes ``ops/kv_attend.py`` takes; a convolution layer's
    tail 8 KiB a lane whatever the length."""
    full = SPEC.config(CONFIG)
    sv = full["serve"]
    cfg = FAMILY.program_config(full, sv["num_hidden_layers"], sv["max_len"])
    prog = slot_program(cfg)
    cache = jax.eval_shape(lambda: prog.init_cache(sv["slots"],
                                                   sv["max_len"]))
    assert sorted(cache["k"]) == ["02", "06"] == sorted(cache["v"])
    k = cache["k"]["02"]
    assert k.shape == (256, 3072, 4, 128) and k.dtype == jnp.bfloat16
    assert k.size * 2 // (256 * 3072) == 1024
    assert kv_attend_tiles(k.shape[2], k.shape[3], k.shape[1])
    assert len(cache["conv"]) == 8 and "state" not in cache
    tail = cache["conv"]["00"]
    assert tail.shape == (256, 2, 2048) and tail.dtype == jnp.bfloat16
    lane = sum(x.size * x.dtype.itemsize // 256
               for key in ("k", "v", "conv") for x in cache[key].values())
    assert lane == 2 * 3072 * 2048 + 8 * 8192
    assert full["sizing"]["bytes_a_lane"] == lane


@pytest.mark.parametrize("nkv,g", [(8, 4), (2, 2), (4, 1)])
def test_a_query_in_its_half_reads_its_own_heads_scores(nkv, g):
    """Attention over the packed rows with each query head zero but for
    its KV head's part equals attention a head at a time, in both
    lowerings: the ``jax.numpy`` form over the packed cache, and the
    kernel (interpret mode) as it stands, told the head's scale."""
    B, T, hd, pack = 3, 64, 64, 2
    H = nkv * g
    ks = jax.random.split(jax.random.PRNGKey(nkv), 3)
    q = jax.random.normal(ks[0], (B, 1, H, hd), jnp.float32)
    k, v = (jax.random.normal(kk, (B, T, nkv, hd), jnp.float32)
            for kk in ks[1:])
    at = jnp.asarray([0, 37, T - 1])
    seen = (jnp.arange(T)[None, :] <= at[:, None])[:, None, :]
    want = _grouped_attention(q, k, v, seen, jnp.float32)
    rows = (B, T, nkv // pack, pack * hd)
    qp = _to_packed(q, nkv, pack)
    assert qp.shape == (B, 1, H, 128)
    assert int((np.asarray(qp) != 0).sum()) == B * H * hd
    got = _from_packed(_grouped_attention(
        qp, k.reshape(rows), v.reshape(rows), seen, jnp.float32, hd),
        nkv, pack)
    assert float(jnp.abs(got - want).max()) < 2e-6
    if nkv // pack > 1:         # the kernel takes two rows a position up
        out = kv_attend(qp[:, 0], k.reshape(rows), v.reshape(rows), at,
                        scale=1.0 / 8, block=16, interpret=True)
        got = _from_packed(out[:, None], nkv, pack)
        assert float(jnp.abs(got - want).max()) < 2e-6


def test_the_packed_cache_serves_what_a_head_a_row_serves(monkeypatch):
    """The same toy model with the cache laid a head a row (``kv_pack``
    held to 1): the same logits to a rounding, through ingestion and
    ticks."""
    tokens, want = tokens_and_reference()
    packed = served_logits(tokens, (20, 33, BUCKET), (0, 2, 5), ROW)
    cache = program()[2].init_cache(2, MAX_LEN)
    assert cache["k"]["02"].shape == (2, MAX_LEN, 1, 128)
    monkeypatch.setattr(slot_programs, "kv_pack", lambda nkv, hd: 1)
    program.cache_clear()
    try:
        cache = program()[2].init_cache(2, MAX_LEN)
        assert cache["k"]["02"].shape == (2, MAX_LEN, 2, 64)
        plain = served_logits(tokens, (20, 33, BUCKET), (0, 2, 5), ROW)
    finally:
        program.cache_clear()
    assert worst_gap(plain, want) < TOL
    assert max(float(np.abs(packed[key] - plain[key]).max())
               for key in packed) < 1e-5


# -- the router and the layer held whole ----------------------------------------


def expert_layer(c, at: int = 2, rows: int = 40):
    """Normed rows and the outer weights of expert layer ``at``."""
    h = jax.random.normal(jax.random.PRNGKey(7), (rows, c["hidden_size"]),
                          jnp.float32)
    return h, ref.sparse_outer_weights(c, ref.seed_word(SEED), at,
                                       jnp.float32)


def experts_kind(c):
    return FAMILY.layer_plan(c, LAYERS).mlp[1]


def held_part(c, h, outer, first: int, count: int, at: int = 2):
    kind = dataclasses.replace(experts_kind(c), held=(first, count))
    lp = dict(outer, **ref.expert_block(c, ref.seed_word(SEED), at, first,
                                        count, jnp.float32))
    return held_expert_ffn(h, lp, kind, jnp.ones((h.shape[0],), bool),
                           jnp.float32)


def spread(w, idx, n: int):
    """(T, k) weights on (T, k) expert ids as (T, n)."""
    return np.asarray(jnp.sum(
        jax.nn.one_hot(idx, n, dtype=jnp.float32) * w[..., None], axis=-2))


def test_the_router_is_the_references():
    c = toy()
    h, outer = expert_layer(c)
    kind = experts_kind(c)
    assert (kind.scoring, kind.renorm_eps, kind.routed_scale, kind.top_k,
            kind.shared_d_ff) == ("sigmoid", 1e-6, 1.0, 2, 0)
    w, idx = route_top_k(h, outer["router"], kind, outer["router_bias"])
    want = np.asarray(ref.routing(c, h, outer["router"],
                                  outer["router_bias"], False))
    assert float(np.abs(spread(w, idx, 8) - want).max()) < 1e-6
    assert ((want > 0).sum(-1) == 2).all()


def test_the_selection_bias_chooses_and_does_not_weigh():
    c = toy()
    h, outer = expert_layer(c, rows=400)
    kind = experts_kind(c)
    bias = outer["router_bias"]
    w, idx = route_top_k(h, outer["router"], kind, bias)
    w0, idx0 = route_top_k(h, outer["router"], kind, jnp.zeros_like(bias))
    flipped = np.asarray(jnp.sort(idx, -1) != jnp.sort(idx0, -1)).any(-1)
    assert 0 < flipped.sum() < 100      # a near tie here and there
    # where the choice is the same so are the weights: the bias is in
    # neither the numerator nor the sum
    same = ~flipped
    assert np.array_equal(spread(w, idx, 8)[same], spread(w0, idx0, 8)[same])
    # and a large bias on one expert puts it in every token's choice
    # with the weight its own score gives
    big = jnp.zeros_like(bias).at[5].set(10.0)
    w5, idx5 = route_top_k(h, outer["router"], kind, big)
    assert bool((idx5 == 5).any(-1).all())
    assert float(jnp.max(w5)) < 1.0


def test_the_renormalising_sum_has_its_epsilon():
    """Scores small enough for 1e-6 to show: every logit -12, so every
    score is sigmoid(-12) = 6.1e-6 and a chosen pair's weights are
    ``s / (2 s + 1e-6)`` = 0.462 each, not a half; the reference's the
    same; a kind without the epsilon gives the half."""
    c = toy()
    kind = experts_kind(c)
    h = jnp.ones((4, 256), jnp.float32)
    router = jnp.full((256, 8), -12.0 / 256, jnp.float32)
    bias = jnp.zeros((8,), jnp.float32)
    s = float(jax.nn.sigmoid(-12.0))
    w, _ = route_top_k(h, router, kind, bias)
    assert np.allclose(np.asarray(w), s / (2 * s + 1e-6), rtol=1e-5)
    assert abs(float(w[0, 0]) - 0.4622) < 1e-3
    want = np.asarray(ref.routing(c, h, router, bias, False))
    assert np.allclose(want[want > 0], s / (2 * s + 1e-6), rtol=1e-5)
    plain, _ = route_top_k(h, router, dataclasses.replace(
        kind, renorm_eps=0.0), bias)
    assert np.allclose(np.asarray(plain), 0.5, rtol=1e-6)
    assert P.MlpKind("experts", 8, n_experts=4, top_k=2).renorm_eps == 0.0


@pytest.mark.parametrize("dense_pairs", [4096, 0])
def test_the_layer_held_whole_is_the_references_uncut_layer(dense_pairs,
                                                            monkeypatch):
    """``held = (0, all)`` against the reference's sum over all eight
    experts, no expert absent and no token dropped; and four shares of
    two add up to the same, so the layer told it holds everything is
    the layer a share is a part of. Through every held expert (a tick's
    few rows) and through the sorted rows' grouped product."""
    from pbs_tpu.models import moe

    monkeypatch.setattr(moe, "DENSE_PAIRS", dense_pairs)
    c = toy()
    h, outer = expert_layer(c)
    gate = ref.routing(c, h, outer["router"], outer["router_bias"], False)
    want = ref.block_of_experts(h, gate, ref.expert_block(
        c, ref.seed_word(SEED), 2, 0, 8, jnp.float32), False)
    whole, counts = held_part(c, h, outer, 0, 8)
    assert float(jnp.abs(whole - want).max()) < 1e-4
    assert float(jnp.abs(want).max()) > 0.1
    chosen = np.asarray(gate > 0)
    assert [int(x) for x in counts] == [
        40 * 2, 0, chosen.any(0).sum(), chosen.sum(0).max()]
    parts, _ = zip(*(held_part(c, h, outer, first, 2)
                     for first in range(0, 8, 2)))
    assert float(jnp.abs(sum(parts) - want).max()) < 1e-4


# -- the engine: a lane reused ------------------------------------------------


def serve(engine, prompts, max_new):
    done = {}
    for p in prompts:
        engine.submit(p, max_new)
    while engine.has_work():
        done.update({c.request_id: list(c.tokens) for c in engine.step()})
    return [done[i] for i in range(len(prompts))]


PROMPTS = [[5, 9, 2], [7] * 21, [3, 1, 4, 1, 5, 9, 2, 6], [11],
           list(range(20, 20 + BUCKET))]


def engine(slots: int) -> ContinuousBatcher:
    cfg, params = program()[:2]
    return ContinuousBatcher(cfg, params, n_slots=slots,
                             prompt_bucket=BUCKET, max_len=MAX_LEN)


@pytest.mark.parametrize("slots", [1, 2])
def test_a_lane_retired_and_readmitted_serves_what_a_fresh_engine_serves(
        slots):
    """One or two lanes for five requests: each later request is
    ingested into a lane whose tail and keys the last tenant left (a
    one-token prompt among them: its tail is a zero row and one of its
    own, whatever the lane held), beside a lane in mid-answer, and
    reads what it reads alone in a new engine."""
    alone = [serve(engine(1), [p], 12)[0] for p in PROMPTS]
    assert serve(engine(slots), PROMPTS, 12) == alone
    assert all(len(t) == 12 for t in alone)


@pytest.mark.parametrize("dense_pairs", [4096, 0])
def test_the_engine_serves_the_references_best_tokens(dense_pairs,
                                                      monkeypatch):
    """Through ``ContinuousBatcher`` (admission, the pipelined tick,
    retirement): every token three lanes serve for three prompts of
    20-48 is, under the reference's full forward over prompt and
    answer, within the tolerance of the reference's best logit there."""
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
    # embed, final norm (no head: tied); 8 convolution mixers of 4
    # leaves, 2 attention mixers of 7; 2 dense MLPs of 4, 8 expert
    # layers of 6
    assert backend.stats()["param_leaves"] == 2 + 8 * 4 + 2 * 7 + 2 * 4 \
        + 8 * 6
    assert serve(backend.engine, PROMPTS[:3], 8) == serve(
        engine(3), PROMPTS[:3], 8)


# -- serve/: rules; what this plan does not do ---------------------------------

SPECS = {"w_in": (None, -1), "conv_w": (None, -1), "w_out": (-1, None),
         "wq": (None, -1), "wk": (None, -1), "wv": (None, -1),
         "wo": (-1, None)}
MIXER_LEAVES = {"conv": ("attn_norm", "w_in", "conv_w", "w_out"),
                "attn": ("attn_norm", "wq", "wk", "wv", "wo", "q_norm",
                         "k_norm")}


@pytest.mark.parametrize("kind,leaf", [
    (k, leaf) for k, leaves in MIXER_LEAVES.items() for leaf in leaves])
def test_every_leaf_of_the_mixers_meets_exactly_one_rule(kind, leaf):
    cfg, params = program()[:2]
    blocks = CONV_BLOCKS if kind == "conv" else ATTN_BLOCKS
    found = [(p, x) for p, x in iter_leaf_paths(params)
             if p.rsplit("/", 1)[-1] == leaf and p.split("/")[1] in blocks
             and p.split("/")[2] == "attn"]
    assert len(found) == len(blocks)
    specs = match_partition_rules(PARTITION_RULES, params)
    for path, x in found:
        hits = [pat for pat, _ in PARTITION_RULES if re.search(pat, path)]
        assert len(hits) == 1, (path, hits)
        spec = specs
        for part in path.split("/"):
            spec = spec[part]
        assert spec == SPECS.get(leaf, ()), (path, spec)
        assert not spec or len(spec) == x.ndim


def test_no_rule_is_dead_or_shadowed_and_no_path_uncovered():
    audit = audit_rules(PARTITION_RULES)
    assert audit == {"dead": [], "shadowed": [], "uncovered": []}
    for leaf in ("w_in", "conv_w", "w_out", "q_norm", "k_norm"):
        assert f"blocks/N/attn/{leaf}" in TEMPLATE_PATHS


def test_the_whole_tree_is_the_plans_and_every_leaf_has_one_rule():
    cfg, params = program()[:2]
    for path, _ in iter_leaf_paths(params):
        hits = [pat for pat, _ in PARTITION_RULES if re.search(pat, path)]
        assert len(hits) == 1, (path, hits)
    shapes = jax.tree.map(lambda x: tuple(x.shape), params)
    assert shapes == P.plan_shapes(cfg)
    assert "head" not in params
    for name in CONV_BLOCKS:
        assert set(params["blocks"][name]["attn"]) == set(
            MIXER_LEAVES["conv"])
    for name in ATTN_BLOCKS:
        assert set(params["blocks"][name]["attn"]) == set(
            MIXER_LEAVES["attn"])
    for name in ("00", "01"):
        assert set(params["blocks"][name]["mlp"]) == {
            "mlp_norm", "w1", "w3", "w2"}
    for name in ("02", "09"):
        assert set(params["blocks"][name]["mlp"]) == {
            "mlp_norm", "router", "router_bias", "we1", "we3", "we2"}
    made = P.init_plan_params(cfg, jax.random.PRNGKey(0))
    assert jax.tree.map(lambda x: tuple(x.shape), made) == shapes
    # a Conv1d's start at fan-in 3, in the program's own start and in
    # the reference's tree; the head norms start at one
    for m in (made["blocks"]["00"]["attn"], params["blocks"]["00"]["attn"]):
        top = float(jnp.abs(m["conv_w"]).max())
        assert 0.5 < top <= 3 ** -0.5 + 1e-6
    for m in (made["blocks"]["02"]["attn"], params["blocks"]["02"]["attn"]):
        assert bool((m["q_norm"] == 1).all() and (m["k_norm"] == 1).all())
        assert m["q_norm"].shape == (64,)


REFUSALS = {
    "prefix": (ValueError, "gated convolution keeps the last rows",
               lambda cfg, params: ContinuousBatcher(
                   cfg, params, n_slots=2, prompt_bucket=12, max_len=40,
                   prefix_cache_size=2)),
    "speculation": (NotImplementedError, "tail as it stood",
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
    "no-bias-router": (NotImplementedError, "use_expert_bias",
                       lambda cfg, params: FAMILY.layer_plan(
                           dict(toy(), use_expert_bias=False), LAYERS)),
    "conv-bias": (NotImplementedError, "conv_bias",
                  lambda cfg, params: FAMILY.layer_plan(
                      dict(toy(), conv_bias=True), LAYERS)),
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
    """The rehearsal's ten layers and the cell's are ``layer_types``'
    first ten; the published plan has the published widths."""
    plan = program()[0].layer_plan
    kinds = [tuple(type(k).__name__ for k in plan.kinds(l))
             for l in range(LAYERS)]
    C, A = "ConvKind", "AttnKind"
    assert [a for a, _ in kinds] == [C, C, A, C, C, C, A, C, C, C]
    assert all(m == "MlpKind" for _, m in kinds)
    assert [plan.kinds(l)[1].n_experts for l in range(LAYERS)] \
        == [0, 0] + [8] * 8
    assert plan.recurrent and plan.routed and plan.select_topk is None
    full = SPEC.config(CONFIG)
    assert len(full["layer_types"]) == 40
    assert full["layer_types"].count("full_attention") == 10
    published = FAMILY.layer_plan(full, full["serve"]["num_hidden_layers"])
    conv, attn = published.attn
    assert (conv.channels, conv.conv) == (2048, 3)
    assert (attn.n_heads, attn.window, attn.gate, attn.qk_norm,
            attn.rope.theta, attn.rope.rotary_dim, attn.rope.factor) \
        == (32, None, None, True, 1e6, None, 1.0)
    dense, experts = published.mlp
    assert (dense.d_ff, dense.n_experts) == (11776, 0)
    assert (experts.d_ff, experts.n_experts, experts.top_k, experts.held,
            experts.shared_d_ff, experts.routed_scale, experts.scoring,
            experts.form, experts.renorm_eps) == (
                1536, 64, 4, (0, 64), 0, 1.0, "sigmoid", "silu", 1e-6)
    cfg = FAMILY.program_config(full, 10, 3072)
    assert (cfg.d_model, cfg.n_kv_heads, cfg.head_dim, cfg.vocab,
            cfg.tie_embeddings, cfg.norm_eps) == (
                2048, 8, 64, 65536, True, 1e-5)
    # a stack of convolution layers alone is recurrent too
    alone = P.LayerPlan((conv,), (dense,), ((0, 0),))
    assert alone.recurrent


def test_a_decode_says_which_form_its_attention_runs_in():
    """On a CPU the two attention layers run the ``jax.numpy`` form and
    say so; for a described TPU the packed cache of the published
    widths goes through the live-block kernel, 512 positions a block."""
    full = SPEC.config(CONFIG)
    cfg = FAMILY.program_config(full, 10, 3072)
    prog = slot_program(cfg)
    cache = jax.eval_shape(lambda: prog.init_cache(256, 3072))
    assert prog.live_layers(cache) == {
        2: ("kv", 3072, 512), 6: ("kv", 3072, 512)}
    assert prog.live_layers(cache, lowered=True) == {}     # a CPU
    toy_prog = program()[2]
    # the toy's one packed row a position is no shape the kernel takes
    assert toy_prog.live_layers(toy_prog.init_cache(2, MAX_LEN)) == {}


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
                    if s != "attn.window"]      # no layer here keeps a ring
    return out


@pytest.mark.parametrize("metric,match,scope", _cell_scopes())
def test_a_metrics_scope_names_ops_of_its_program(metric, match, scope):
    """A renamed ``jax.named_scope`` breaks this test, not a metric that
    would silently find no op in the trace."""
    assert re.search(rf'"[^"]*{re.escape(scope)}/[^"]*"',
                     lowered_text()[match]), (metric, scope)


def test_the_step_and_the_filter_lie_inside_the_mixers_scope():
    text = lowered_text()
    assert "/attn.conv/conv.step/" in text["jit__decode"]
    assert "/attn.conv/conv.filter/" in text["jit__prefill"]
    assert "/attn.conv/conv.filter/" not in text["jit__decode"]
    assert "/attn.conv/conv.step/" not in text["jit__prefill"]
    for t in text.values():
        assert all(f"/{s}/" in t for s in (
            "attn.conv", "attn.full", "mlp.dense", "moe.route",
            "moe.experts"))
        assert "moe.shared" not in t
