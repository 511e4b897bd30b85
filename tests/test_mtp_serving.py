"""A plan that drafts for itself (DeepSeek-V3's multi-token-prediction
module) through the slot engine: latent attention without an indexer
under YaRN, a group-limited sigmoid router, a tick that verifies two
positions a lane and advances it by one token or two, and the plain
float32 reference they are held to
(``benchmarks/reference/moe_mla_mtp.py``, which imports nothing of the
program, reads every head's keys and values off the latent rows and
knows no verify window). Toy sizes, CPU, float32, seeded weights: the
configuration file's own rehearsal preset (hidden 48, 4 heads of 12 + 4
over latents of 24 and 16, 8 experts in 4 groups of which 2 are kept,
top-3 with 4 held, one dense layer and two expert layers, the drafting
block behind them), at a vocabulary of 8-12 where both branches of the
acceptance occur every few ticks.

Tolerances: program and reference both compute in float32, in another
order (the absorbed form over cached rows and a window of two queries
against the per-head form over the whole row, sorted grouped products
or every held expert against every expert for every token), so logits
of magnitude ~4 agree to a few float32 roundings: 2e-4 absolute. A
bfloat16 computation of the same stack misses that by two orders, and
the softmax's scale without YaRN's ``m^2`` by three (both asserted
below).
"""

import copy
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness.spec import Spec
from benchmarks.run import overlay
from pbs_tpu.models import mla
from pbs_tpu.models import plan as P
from pbs_tpu.models.moe import (
    held_expert_ffn, route_top_k, shared_expert_ffn)
from pbs_tpu.models.serving import ContinuousBatcher
from pbs_tpu.models.slot_programs import _plan_forward, slot_program
from pbs_tpu.models.transformer import rms_norm
from pbs_tpu.obs.trace import Ev, TraceBuffer
from pbs_tpu.serve import ShardedServeBackend

SEED = 7
TOL = 2e-4
SPEC = Spec()
FAMILY = SPEC.family("moe-mla-mtp")
ref = FAMILY.reference
MAX_LEN, BUCKET, N_LAYERS = 96, 48, 3


def toy(vocab: int = 12, first: int = 0, held: int = 4, total: int = 8,
        groups: int = 4) -> dict:
    full = SPEC.config("deepseek-v3")
    c = copy.deepcopy(overlay(full, full["rehearsal"]))
    c["vocab_size"] = vocab
    c["n_routed_experts"] = c["num_experts"] = held
    c["n_group"] = groups
    c["deployment"].update(experts_first=first, experts_total=total)
    return c


@functools.lru_cache(maxsize=None)
def model(vocab: int = 12, dtype=jnp.float32):
    """The toy model's configuration, its weights, and the same stack
    without its drafting block (the same leaves less ``blocks/mtp``)."""
    c = toy(vocab)
    c["compute_dtype"] = jnp.dtype(dtype).name
    cfg = FAMILY.program_config(c, N_LAYERS, MAX_LEN)
    params = jax.jit(lambda s: ref.init_tree(
        c, s, N_LAYERS, dtype))(ref.seed_word(SEED))
    plain = dataclasses.replace(cfg, layer_plan=dataclasses.replace(
        cfg.layer_plan, draft=None))
    bare = dict(params, blocks={k: v for k, v in params["blocks"].items()
                                if k != P.DRAFT_BLOCK})
    return c, cfg, params, plain, bare


def padded(tokens, rows: int = BUCKET):
    out = np.zeros(rows, np.int32)
    out[:len(tokens)] = tokens
    return jnp.asarray(out)


def rows_of(seqs, length: int = MAX_LEN):
    out = np.zeros((len(seqs), length), np.int32)
    for b, seq in enumerate(seqs):
        out[b, :len(seq)] = seq
    return out


# -- the tick against the full forward -----------------------------------------


@functools.lru_cache(maxsize=None)
def ticked(vocab: int = 12, ticks: int = 24, dtype=jnp.float32):
    """Eight prompts ingested (the last two while the others decode) and
    ``ticks`` drafting ticks through the program's own ``ingest`` and
    ``draft_tick``: per lane the served sequence, and per tick what it
    verified (cursor, the window's two tokens and logits, whether the
    draft was accepted, the drafting block's logits)."""
    c, cfg, params, _, _ = model(vocab, dtype)
    prog = slot_program(cfg)
    ingest, tick = jax.jit(prog.ingest_drafts), jax.jit(prog.draft_tick)
    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(0, vocab, n).tolist()
               for n in (9, 30, 17, 4, 22, 41, 13, 35)]
    lanes = len(prompts)
    cache = prog.init_cache(lanes, MAX_LEN)
    seqs = [None] * lanes
    active = np.zeros(lanes, bool)
    seen, prompt_drafts = [], {}

    def admit(b):
        nonlocal cache
        _, cache, _, drafts = ingest(
            params, cache, b, padded(prompts[b]), len(prompts[b]))
        seqs[b] = prompts[b] + [int(cache["cur"][b])]
        prompt_drafts[b] = np.asarray(drafts)[:len(prompts[b])]
        active[b] = True

    for b in range(lanes - 2):
        admit(b)
    for t in range(ticks):
        if t in (4, 9):
            admit(lanes - 2 + (t == 9))
        before = {k: np.asarray(cache[k]) for k in ("pos", "cur", "dr")}
        toks, cache, _, logits, drafts = tick(
            params, cache, jnp.asarray(active))
        toks = np.asarray(toks)
        for b in np.flatnonzero(active):
            p = int(before["pos"][b])
            assert seqs[b][p] == before["cur"][b] and len(seqs[b]) == p + 1
            took = int((toks[b] >= 0).sum())
            seen.append(dict(
                lane=b, p=p, window=(int(before["cur"][b]),
                                     int(before["dr"][b])),
                accepted=took == 2, logits=np.asarray(logits[b]),
                drafts=np.asarray(drafts[b]), prefix=list(seqs[b])))
            seqs[b] += toks[b, :took].tolist()
            assert int(cache["pos"][b]) == p + took
            assert int(cache["cur"][b]) == seqs[b][-1]
        # a lane at the end of its room sits the rest out
        active &= np.asarray(cache["pos"]) < MAX_LEN - 3
    return c, seqs, seen, prompt_drafts, prompts


def test_both_branches_of_the_acceptance_occur():
    _, _, seen, _, _ = ticked()
    took = [s["accepted"] for s in seen]
    assert sum(took) >= 5 and len(took) - sum(took) >= 20, (
        sum(took), len(took))


def test_a_ticks_two_logits_are_the_references_full_forward():
    """The window's first logits at the cursor, and its second at the
    position behind (over the drafted token, accepted or not), against
    the reference's full forward of the same tokens."""
    c, seqs, seen, _, _ = ticked()
    served = np.asarray(ref.full_logits(
        c, SEED, N_LAYERS, jnp.float32, rows_of(seqs)))
    # a window whose draft was rejected read a token that was never
    # served: its second logits are held to the forward over that row
    rejected = [s for s in seen if not s["accepted"]]
    other = np.asarray(ref.full_logits(
        c, SEED, N_LAYERS, jnp.float32,
        rows_of([s["prefix"] + [s["window"][1]] for s in rejected])))
    worst = 0.0
    for s in seen:
        worst = max(worst, np.abs(
            s["logits"][0] - served[s["lane"], s["p"]]).max())
        if s["accepted"]:
            worst = max(worst, np.abs(
                s["logits"][1] - served[s["lane"], s["p"] + 1]).max())
    for s, want in zip(rejected, other):
        worst = max(worst, np.abs(s["logits"][1] - want[s["p"] + 1]).max())
    assert worst < TOL, worst
    # and the tokens served are the reference's best at every position
    for b, seq in enumerate(seqs):
        n = len(ticked()[4][b])
        for i in range(n - 1, len(seq) - 1):
            assert served[b, i].max() - served[b, i, seq[i + 1]] < TOL


def test_the_drafts_logits_are_the_references_at_every_tick():
    """The drafting block's logits, in the prompt forward (every pair
    of the prompt) and in every tick (the pair at the cursor, and where
    the draft was accepted the pair behind it), against
    ``draft_logits`` over the served sequence."""
    c, seqs, seen, prompt_drafts, prompts = ticked()
    want = np.asarray(ref.draft_logits(
        c, SEED, N_LAYERS, jnp.float32, rows_of(seqs)))
    worst = 0.0
    for b, got in prompt_drafts.items():
        worst = max(worst, np.abs(got - want[b, :len(prompts[b])]).max())
    for s in seen:
        if s["p"] + 1 >= len(seqs[s["lane"]]) - 1:
            continue  # the last served token has nothing behind it
        worst = max(worst, np.abs(
            s["drafts"][0] - want[s["lane"], s["p"]]).max())
        if s["accepted"] and s["p"] + 2 < len(seqs[s["lane"]]) - 1:
            worst = max(worst, np.abs(
                s["drafts"][1] - want[s["lane"], s["p"] + 1]).max())
    assert worst < TOL, worst


def test_a_bfloat16_stack_fails_the_tolerance():
    """The same ticks with weights and activations in bfloat16 miss the
    float32 reference by far more than the tolerance: it can tell."""
    c, seqs, seen, _, _ = ticked(dtype=jnp.bfloat16)
    served = np.asarray(ref.full_logits(
        c, SEED, N_LAYERS, jnp.bfloat16, rows_of(seqs)))
    worst = max(np.abs(s["logits"][0].astype(np.float32)
                       - served[s["lane"], s["p"]]).max() for s in seen)
    assert worst > 20 * TOL, worst


# -- the accept branch, through the engine --------------------------------------


def serve_all(cfg, params, requests, slots, eos=None, every=2):
    """Requests through ``ContinuousBatcher``, one submitted every
    ``every`` ticks (so that prompts are admitted while a decode is in
    flight and lanes are retired and readmitted): tokens a request, and
    the engine."""
    eng = ContinuousBatcher(cfg, params, n_slots=slots, prompt_bucket=BUCKET,
                            max_len=MAX_LEN, eos_id=eos)
    eng.bind_trace(TraceBuffer(1 << 15))   # every record of the run
    done, pending, tick = {}, list(requests), 0
    while pending or eng.has_work():
        if pending and tick % every == 0:
            eng.submit(*pending.pop(0))
        done.update({c.request_id: list(c.tokens) for c in eng.step()})
        tick += 1
    return [done[i] for i in range(len(requests))], eng


def requests_of(vocab, n, budgets):
    rng = np.random.default_rng(SEED + 2)
    return [(rng.integers(0, vocab, rng.integers(2, 40)).tolist(),
             int(rng.integers(*budgets))) for _ in range(n)]


def records(eng, event):
    return [r for r in eng.trace.peek(1 << 15).tolist()
            if r[1] == int(event)]


@pytest.mark.parametrize("vocab,slots", [(12, 3), (8, 2)])
def test_served_tokens_equal_the_same_stack_without_its_drafting_block(
        vocab, slots):
    """Token for token, over budgets of every size (so that some end on
    the first of a tick's two tokens and the second is dropped), lanes
    retired and readmitted with a rejected row behind the cursor, and
    prompts admitted while a decode is in flight; both branches taken
    tens of times."""
    _, cfg, params, plain, bare = model(vocab)
    requests = requests_of(vocab, 60, (1, 30))
    drafted, eng = serve_all(cfg, params, requests, slots)
    want, base = serve_all(plain, bare, requests, slots)
    assert drafted == want
    assert [len(t) for t in drafted] == [n for _, n in requests]
    st = eng.stats()
    assert st["drafts_accepted"] >= 20
    assert st["drafts_proposed"] - st["drafts_accepted"] >= 20
    assert st["draft_tokens_dropped"] >= 1        # a budget's, no EOS here
    assert base.stats()["drafts_proposed"] == 0
    # the record of every decode read: sums are the counters
    drafts = records(eng, Ev.ENG_DRAFT)
    assert sum(r[4] for r in drafts) == st["drafts_proposed"]
    assert sum(r[5] for r in drafts) == st["drafts_accepted"]
    assert sum(r[7] for r in drafts) == st["draft_tokens_dropped"]
    # booked: every token served but each request's first (the prefill's)
    assert sum(r[6] for r in drafts) == sum(map(len, drafted)) - len(drafted)
    assert eng.ticks_overlapped > 0               # the tick stayed pipelined


def test_an_eos_as_the_first_of_two_tokens_ends_the_request():
    """With an EOS that the model emits often and budgets it never
    reaches: the served tokens end at the EOS, equal the plain stack's,
    and a token computed behind an EOS is dropped."""
    vocab = 12
    _, cfg, params, plain, bare = model(vocab)
    requests = requests_of(vocab, 24, (45, 50))
    free, _ = serve_all(plain, bare, requests, 3)
    counts = np.bincount(np.concatenate(free), minlength=vocab)
    eos = int(np.argsort(counts)[-3])   # common, not the commonest
    drafted, eng = serve_all(cfg, params, requests, 3, eos=eos)
    want, _ = serve_all(plain, bare, requests, 3, eos=eos)
    assert drafted == want
    ended = [t for t in drafted if t[-1] == eos]
    assert len(ended) >= 10 and all(eos not in t[:-1] for t in drafted)
    assert eng.stats()["draft_tokens_dropped"] >= 1
    assert eng.stats()["drafts_accepted"] >= 10


def test_submit_keeps_the_windows_rows_under_max_len():
    _, cfg, params, _, _ = model()
    eng = ContinuousBatcher(cfg, params, n_slots=1, prompt_bucket=BUCKET,
                            max_len=MAX_LEN)
    eng.submit([1] * 40, MAX_LEN - 42)
    with pytest.raises(ValueError, match="less the 2 rows"):
        eng.submit([1] * 40, MAX_LEN - 41)


def test_sampling_with_a_drafting_block_raises():
    _, cfg, params, _, _ = model()
    with pytest.raises(ValueError, match="greedy"):
        ContinuousBatcher(cfg, params, n_slots=1, prompt_bucket=BUCKET,
                          max_len=MAX_LEN, temperature=0.7)


def test_eng_select_counts_two_queries_a_busy_lane():
    """A latent layer that chooses nothing writes ``ENG_SELECT`` too:
    chosen = seen, ``topk`` the cache's length, two rows a busy lane
    (the second sees one position more), so the accepted readers size
    this program's costs."""
    _, cfg, params, _, _ = model()
    eng = ContinuousBatcher(cfg, params, n_slots=2, prompt_bucket=BUCKET,
                            max_len=MAX_LEN)
    eng.submit(list(range(10)), 6)
    while eng.has_work():
        eng.step_settled()
    pre, *dec = records(eng, Ev.ENG_SELECT)
    assert pre[3:8] == [10, 55, 55, MAX_LEN, 0]
    booked = 1
    for r in dec:   # settled ticks: the host knows every cursor
        assert r[3:7] == [2, 2 * (10 + booked) + 1, 2 * (10 + booked) + 1,
                          MAX_LEN]
        booked = next(d[6] for d in records(eng, Ev.ENG_DRAFT)
                      if d[0] >= r[0]) + booked
    assert booked == 6 or booked == 7   # 7: a dropped second token


# -- what does not take a window -------------------------------------------------


def test_a_plan_with_a_ring_or_a_state_raises_for_a_window_with_its_reason():
    c, cfg, params, _, _ = model()
    mla_kind = cfg.layer_plan.attn[0]
    ring = P.AttnKind("window", 4, 8, P.Rope())
    for other, word in ((ring, "ring"), (P.ConvKind("conv", 48), "ring"),
                        (dataclasses.replace(mla_kind, index_heads=2,
                                             index_dim=8, topk=16),
                         "indexer")):
        plan = P.LayerPlan((mla_kind, other), cfg.layer_plan.mlp,
                           ((0, 0), (1, 1)), draft=(0, 1))
        bad = dataclasses.replace(cfg, n_layers=2, head_size=16,
                                  layer_plan=plan)
        with pytest.raises(NotImplementedError, match=word):
            slot_program(bad)
    # and without a drafting block, a window handed to the forward
    plan = P.LayerPlan((ring,), cfg.layer_plan.mlp, ((0, 0),))
    ringed = dataclasses.replace(cfg, n_layers=1, layer_plan=plan)
    prog = slot_program(ringed)
    with pytest.raises(NotImplementedError, match="one position a tick"):
        _plan_forward(ringed, jax.eval_shape(
            lambda: prog.init_params(jax.random.PRNGKey(0))),
            jnp.zeros((2, 2), jnp.int32), prog.init_cache(2, 16),
            jnp.zeros((2,), jnp.int32), jnp.ones((2, 2), bool))
    assert "ring" in prog.no_windows


def test_a_latent_program_without_an_indexer_has_no_prompt_windows():
    _, cfg, params, plain, bare = model()
    assert not slot_program(plain).windows
    with pytest.raises(ValueError, match="latent row and a rotary key"):
        ContinuousBatcher(plain, bare, n_slots=1, prompt_bucket=BUCKET,
                          max_len=MAX_LEN, prefix_cache_size=2)


# -- latent attention without an indexer ------------------------------------------


def test_without_an_indexer_equals_the_selecting_kind_that_selects_all():
    """The same weights under glm-5's kind with ``topk`` at the cache's
    length (an indexer that scores every position and rules none out):
    the same logits, prompt forward and ticks, and no ``ik`` rows or
    ``wi_*`` leaves here."""
    _, _, _, plain, bare = model()
    kind = plain.layer_plan.attn[0]
    picking = dataclasses.replace(kind, index_heads=2, index_dim=8,
                                  topk=MAX_LEN)
    chosen = dataclasses.replace(plain, layer_plan=dataclasses.replace(
        plain.layer_plan, attn=(picking,)))
    full = P.init_plan_params(chosen, jax.random.PRNGKey(1))
    merged = dict(bare, blocks={
        name: dict(block, attn={**full["blocks"][name]["attn"],
                                **block["attn"]})
        for name, block in bare["blocks"].items()})
    assert "wi_q" not in bare["blocks"]["00"]["attn"]
    tokens = np.random.default_rng(3).integers(0, 12, 20).tolist()
    outs = []
    for cfg, params in ((plain, bare), (chosen, merged)):
        prog = slot_program(cfg)
        cache = prog.init_cache(2, MAX_LEN)
        assert ("ik" in cache) == (cfg is chosen)
        last, cache, _, _ = jax.jit(prog.ingest)(
            params, cache, 1, padded(tokens), len(tokens))
        got = [np.asarray(last)]
        decode = jax.jit(prog.decode)
        for tok in (3, 7, 1):
            logits, cache, _, _ = decode(
                params, cache, jnp.asarray([0, tok], jnp.int32),
                jnp.asarray([False, True]))
            cache["pos"] = cache["pos"] + jnp.asarray([0, 1])
            got.append(np.asarray(logits[1, 0]))
        outs.append(np.stack(got))
    assert np.abs(outs[0] - outs[1]).max() < 1e-5


def test_the_softmax_scale_carries_yarns_mscale_squared():
    """The mixer against the reference's, and against the reference
    computed with the scale that forgets ``m^2``: the first within the
    tolerance, the second far outside it."""
    c, cfg, params, _, _ = model()
    a = cfg.layer_plan.attn[0]
    assert abs(a.scale - ref.softmax_scale(c)) < 1e-12
    assert a.scale / ref.softmax_scale(c, squared=False) == pytest.approx(
        (0.1 * np.log(40.0) + 1.0) ** 2)
    S = 32
    x = jax.random.normal(jax.random.PRNGKey(4), (S, c["hidden_size"]))
    ap = params["blocks"]["01"]["attn"]
    cos, sin = (t[None, :S] for t in P.rope_table(a.rope, cfg.head_dim, S))
    heads, *_ = mla.mla_ingest(
        a, ap, rms_norm(x, ap["attn_norm"], cfg.norm_eps)[None],
        jnp.ones((1, S), bool), cos, sin, cfg.norm_eps, jnp.float32)
    got = np.asarray(heads[0] @ ap["wo"])
    want = np.asarray(ref.mixer_row(c, x, ap))
    unscaled = np.asarray(ref.mixer_row(
        c, x, ap, scale=ref.softmax_scale(c, squared=False)))
    assert np.abs(got - want).max() < TOL
    assert np.abs(got - unscaled).max() > 100 * TOL


def test_the_window_kernel_is_window_rows_in_interpret_mode():
    """``ops/mla_attend.mla_attend_window`` (the Pallas pipeline,
    interpreted) against ``mla.window_rows``: two queries a lane over
    one read of the lane's rows, cursors in different blocks, an idle
    lane at 0."""
    from pbs_tpu.ops.mla_attend import mla_attend_window

    B, S, H, R, E, T = 3, 2, 8, 128, 16, 64
    ks = jax.random.split(jax.random.PRNGKey(6), 4)
    q_lat = jax.random.normal(ks[0], (B, S, H, R), jnp.float32)
    q_r = jax.random.normal(ks[1], (B, S, H, E), jnp.float32)
    ckv = jax.random.normal(ks[2], (B, T, R), jnp.float32)
    kr = jax.random.normal(ks[3], (B, T, E), jnp.float32)
    pos = jnp.asarray([0, 15, 37], jnp.int32)   # 15: the window straddles
    got = mla_attend_window(q_lat, q_r, ckv, kr, pos, scale=0.11, block=16,
                            interpret=True)
    want = mla.window_rows(q_lat, q_r, ckv, kr, pos, scale=0.11)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5


# -- the group-limited router ---------------------------------------------------


def dense_weights_of(w, idx, n):
    return np.asarray(jnp.sum(jax.nn.one_hot(idx, n) * w[..., None], axis=-2))


def test_the_group_limited_router_is_the_references():
    """Rows built to tie-break and to sit on a group's edge, and random
    ones: the same experts under the same weights as the reference's
    ``routing``. 8 experts in 4 groups of 2, 2 groups kept, 3 chosen."""
    c = toy()
    E = 8
    kind = FAMILY.layer_plan(c, N_LAYERS).mlp[1]
    assert (kind.n_group, kind.topk_group, kind.top_k) == (4, 2, 3)
    rng = np.random.default_rng(5)
    logits = rng.normal(size=(64, E)).astype(np.float32)
    # a tie inside a group and across groups: lower numbers win
    logits[0] = [1, 1, 1, 1, 0, 0, -1, -1]
    # groups 1 and 2 tie for the second kept place: group 1 is kept
    logits[1] = [2, 2, 0.5, 0.25, 0.25, 0.5, -3, -3]
    # the best single expert sits in a group that is not kept
    logits[2] = [1, 1, 1.2, 1.2, 5, -9, 0, 0]
    # a kept group offers one good expert: the third comes from it
    logits[3] = [5, 0.5, 2, 1.5, 0, 0, -1, -1]
    router = jnp.eye(E, dtype=jnp.float32)
    for bias in (np.zeros(E, np.float32),
                 (0.3 * rng.normal(size=E)).astype(np.float32)):
        w, idx = route_top_k(jnp.asarray(logits), router, kind,
                             jnp.asarray(bias))
        want = np.asarray(ref.routing(c, jnp.asarray(logits), router,
                                      jnp.asarray(bias), False))
        assert np.abs(dense_weights_of(w, idx, E) - want).max() < 1e-6
        assert (np.sort(np.asarray(idx), -1)
                == np.stack([np.flatnonzero(r)[:3] for r in want > 0])).all()
    w, idx = route_top_k(jnp.asarray(logits[:4]), router, kind,
                         jnp.zeros(E))
    assert np.asarray(idx)[0].tolist() == [0, 1, 2]
    assert sorted(np.asarray(idx)[1].tolist()) == [0, 1, 2]
    assert 4 not in np.asarray(idx)[2]
    assert sorted(np.asarray(idx)[3].tolist()) == [0, 2, 3]


def test_one_group_gives_the_unlimited_choice_bit_for_bit():
    """``n_group`` 1 (glm-5's router) is the code it always was, and a
    limit that keeps every group changes no bit of it either."""
    c = toy()
    kind = FAMILY.layer_plan(c, N_LAYERS).mlp[1]
    one = dataclasses.replace(kind, n_group=1, topk_group=1)
    every = dataclasses.replace(kind, n_group=4, topk_group=4)
    h = jax.random.normal(jax.random.PRNGKey(8), (50, c["hidden_size"]))
    outer = ref.sparse_outer_weights(c, ref.seed_word(SEED), 1, jnp.float32)
    bias = outer["router_bias"]
    scores = jax.nn.sigmoid(h @ outer["router"])
    _, want_i = jax.lax.top_k(scores + bias, kind.top_k)
    want_v = jnp.take_along_axis(scores, want_i, -1)
    want_w = want_v / (jnp.sum(want_v, -1, keepdims=True) + 1e-20) * 2.5
    for k in (one, every):
        w, idx = route_top_k(h, outer["router"], k, bias)
        assert (np.asarray(idx) == np.asarray(want_i)).all()
        assert (np.asarray(w) == np.asarray(want_w)).all()
    limited = route_top_k(h, outer["router"], kind, bias)[1]
    assert (np.asarray(limited) != np.asarray(want_i)).any()


def test_the_sixteen_shares_of_an_expert_layer_sum_to_the_uncut_layer():
    """32 experts in 4 groups, 2 held a share: the sixteen shares'
    routed parts and the shared expert once are the reference's layer
    with every expert held."""
    total, held = 32, 2
    whole = toy(first=0, held=total, total=total)
    h = jax.random.normal(jax.random.PRNGKey(9), (40, whole["hidden_size"]))
    seed, at = ref.seed_word(SEED), 2
    outer = ref.sparse_outer_weights(whole, seed, at, jnp.float32)
    gate = ref.routing(whole, h, outer["router"], outer["router_bias"],
                       False)
    want = ref.swiglu(h, outer["ws1"], outer["ws3"], outer["ws2"], False)
    for start in range(0, total, 4):
        want = want + ref.block_of_experts(
            h, gate[:, start:start + 4],
            ref.expert_block(whole, seed, at, start, 4, jnp.float32), False)
    got = shared_expert_ffn(h, outer, jnp.float32)
    touched = 0
    for first in range(0, total, held):
        c = toy(first=first, held=held, total=total)
        kind = FAMILY.layer_plan(c, N_LAYERS).mlp[1]
        assert kind.held == (first, held)
        lp = {**outer, **ref.expert_block(c, seed, at, first, held,
                                          jnp.float32)}
        y, counts = held_expert_ffn(h, lp, kind, jnp.ones(40, bool),
                                    jnp.float32)
        got, touched = got + y, touched + int(counts[0])
    assert touched == 40 * whole["num_experts_per_tok"]
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < TOL


# -- the serving path --------------------------------------------------------------


def test_sharded_serve_backend_serves_the_tree_and_places_every_leaf():
    _, cfg, params, _, _ = model()
    backend = ShardedServeBackend("engine", cfg, params, tp=1, dp=1,
                                  n_slots=2, prompt_bucket=BUCKET,
                                  max_len=MAX_LEN)
    # outer 3; a mixer 8 leaves; the dense MLP 4; an expert layer 9; the
    # drafting block its mixer, its expert layer and 4 of its own
    assert backend.stats()["param_leaves"] == \
        3 + 3 * 8 + 4 + 2 * 9 + (8 + 9 + 4)
    requests = requests_of(12, 5, (3, 12))
    got = []
    for prompt, budget in requests:
        backend.engine.submit(prompt, budget)
    done = {}
    while backend.engine.has_work():
        done.update({c.request_id: c.tokens for c in backend.engine.step()})
    got = [done[i] for i in range(len(requests))]
    assert got == serve_all(cfg, params, requests, 2, every=1)[0]
