"""A planned layer stack (``models/plan.py``) through the slot engine:
window and full attention layers with their own head counts in one
cache, a grouped expert layer that holds a share of the experts, and
the plain float32 reference they are held to
(``benchmarks/reference/moe_mixed_attn.py``, which imports nothing of
the program). Toy sizes, CPU, float32, seeded weights: the
configuration file's own rehearsal preset (window 8, 4 full / 6 sliding
query heads over 2 KV heads of 16 where d_model / heads is 12 or 8,
8 experts top-3 with 4 held).

Tolerances: program and reference both compute in float32, in another
order (cache and ring against a full forward, sorted grouped products
against every expert for every token), so logits of magnitude ~3 agree
to a few float32 roundings: 2e-4 absolute. bfloat16 in place of float32
misses that by two orders (asserted below).
"""

import copy
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness.spec import Spec
from benchmarks.reference import moe_mixed_attn as ref
from benchmarks.run import overlay
from pbs_tpu.gateway import Gateway, TenantQuota
from pbs_tpu.models import plan as P
from pbs_tpu.models.moe import held_expert_ffn, shared_expert_ffn
from pbs_tpu.models.serving import ContinuousBatcher
from pbs_tpu.models.slot_programs import (
    _rope_leading, _ScanProgram, _slot_forward, ingest_slot_prompt,
    init_slot_cache, slot_program)
from pbs_tpu.models.spec_serving import SpeculativeBatcher
from pbs_tpu.models.transformer import TransformerConfig, init_params
from pbs_tpu.obs.trace import Ev
from pbs_tpu.serve import ShardedServeBackend
from pbs_tpu.serve.partition import (
    PARTITION_RULES, iter_leaf_paths, match_partition_rules)
from pbs_tpu.utils.clock import MonotonicClock

SEED = 11
TOL = 2e-4
SPEC = Spec()
FAMILY = SPEC.family("moe-mixed-gqa")


def toy(first: int = 0, held: int = 4, dtype: str = "float32") -> dict:
    full = SPEC.config("laguna-s-2.1")
    c = copy.deepcopy(overlay(full, full["rehearsal"]))
    c["num_experts"], c["deployment"]["experts_first"] = held, first
    c["compute_dtype"] = c["serve"]["weights_dtype"] = dtype
    return c


MAX_LEN, BUCKET, SLOTS = 40, 12, 3
WINDOW = 8
ROW = 3 * WINDOW + 2  # three windows: the ring wraps twice


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

    @functools.partial(jax.jit, donate_argnums=(1,))
    def decode(params, cache, last_tok, active):
        logits, new, _, route = prog.decode(params, cache, last_tok, active)
        new["pos"] = cache["pos"] + active.astype(jnp.int32)
        return logits[:, 0], new, route

    return cfg, params, prog, jax.jit(prog.ingest, donate_argnums=(1,)), \
        decode


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


# -- the two-kind cache against the full forward -----------------------------


def served_logits(dtype, tokens, plens, admit_at, length):
    """Teacher-forced serving of the first ``length`` tokens of each row
    of ``tokens``: slot b is given its prompt (``plens[b]`` tokens) at
    tick ``admit_at[b]`` and then decodes the rest, one position a tick,
    beside whatever else is in the cache. Returns ``{(b, position):
    logits}`` for the prompt's last position and every decoded one."""
    _cfg, params, prog, ingest, decode = program(dtype)
    B = len(tokens)
    cache = prog.init_cache(B, MAX_LEN)
    out, pos, active = {}, np.zeros(B, np.int64), np.zeros(B, bool)
    for tick in range(max(admit_at) + length):
        for b in range(B):
            if admit_at[b] == tick:
                prompt = np.zeros(BUCKET, np.int32)
                prompt[:plens[b]] = tokens[b, :plens[b]]
                last, cache, _, _ = ingest(params, cache, b,
                                           jnp.asarray(prompt), plens[b])
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
@pytest.mark.parametrize("windows", [1, 2, 3])
def test_prefill_then_decode_agrees_with_the_full_forward(windows, admit_at):
    tokens, want = tokens_and_reference()
    length = windows * WINDOW + 2
    plens = [3, WINDOW - 1, min(length - 2, WINDOW + 3)]  # in, at, past
    got = served_logits("float32", tokens, plens, admit_at, length)
    assert len(got) == sum(length - p + 1 for p in plens)
    assert worst_gap(got, want) < TOL


def test_bfloat16_in_place_of_float32_fails_the_tolerance():
    tokens, want = tokens_and_reference()
    got = served_logits("bfloat16", tokens, [3, 7, 11], (0, 0, 0), 18)
    assert worst_gap(got, want) > 50 * TOL


# -- the expert layer ---------------------------------------------------------


def expert_layer_inputs(c, n_tokens=40, at=2):
    d = c["hidden_size"]
    h = jax.random.normal(jax.random.PRNGKey(3), (n_tokens, d), jnp.float32)
    outer = ref.sparse_outer_weights(c, ref.seed_word(SEED), at, jnp.float32)
    return h, outer


def held_part(c, h, outer, first, count, at=2, valid=None):
    kind = P.MlpKind("experts", c["moe_intermediate_size"],
                     n_experts=c["deployment"]["experts_total"],
                     top_k=c["num_experts_per_tok"], held=(first, count),
                     shared_d_ff=c["shared_expert_intermediate_size"],
                     routed_scale=c["moe_routed_scaling_factor"])
    lp = {**outer, **ref.expert_block(c, ref.seed_word(SEED), at, first,
                                      count, jnp.float32)}
    valid = jnp.ones(h.shape[0], bool) if valid is None else valid
    return held_expert_ffn(h, lp, kind, valid, jnp.float32)


def uncut_layer(c, h, outer, at=2):
    total = c["deployment"]["experts_total"]
    gate = ref.routing(c, h, outer["router"], False)
    routed = ref.block_of_experts(h, gate, ref.expert_block(
        c, ref.seed_word(SEED), at, 0, total, jnp.float32), False)
    return routed + ref.swiglu(h, outer["ws1"], outer["ws3"], outer["ws2"],
                               False), gate


def test_the_shares_add_up_to_the_uncut_layer():
    c = toy()
    h, outer = expert_layer_inputs(c)
    want, gate = uncut_layer(c, h, outer)
    parts, counts = zip(*(held_part(c, h, outer, 4 * s, 4)
                          for s in (0, 1)))
    got = parts[0] + parts[1] + shared_expert_ffn(h, outer, jnp.float32)
    assert float(jnp.abs(got - want).max()) < 1e-5
    chosen = np.asarray(gate > 0)
    for s, cnt in enumerate(counts):
        mine = chosen[:, 4 * s:4 * s + 4]
        assert [int(x) for x in cnt] == [
            mine.sum(), chosen.sum() - mine.sum(),
            mine.any(0).sum(), mine.sum(0).max()]
    assert int(counts[0][0] + counts[1][0]) == 40 * 3  # no token dropped


def test_every_token_on_one_expert_loses_none():
    c = toy()
    h, outer = expert_layer_inputs(c)
    # Every token chooses expert 2 first, whatever else it chooses:
    # dim 0 of every token is 5, and only expert 2's router column
    # reads dim 0.
    h = h.at[:, 0].set(5.0)
    outer = dict(outer, router=outer["router"].at[0, :].set(0.0)
                 .at[0, 2].set(10.0))
    want, gate = uncut_layer(c, h, outer)
    assert bool((gate[:, 2] > 0).all())
    y, cnt = held_part(c, h, outer, 0, 8)
    assert int(cnt[3]) == h.shape[0] and int(cnt[0]) == 3 * h.shape[0]
    got = y + shared_expert_ffn(h, outer, jnp.float32)
    assert float(jnp.abs(got - want).max()) < 1e-5


def test_rows_that_are_not_tokens_route_nowhere():
    c = toy()
    h, outer = expert_layer_inputs(c)
    valid = jnp.arange(h.shape[0]) < 7
    y, cnt = held_part(c, h, outer, 0, 8, valid=valid)
    assert int(cnt[0]) == 7 * 3 and int(cnt[1]) == 0
    assert float(jnp.abs(y[7:]).max()) == 0.0


# -- rotary: YaRN and partial rotary against hand values ----------------------


def test_yarn_frequencies_against_hand_values():
    rp = SPEC.config("laguna-s-2.1")["rope_parameters"]["full_attention"]
    rope = FAMILY.layer_plan(SPEC.config("laguna-s-2.1"), 1).attn[0].rope
    assert (rope.rotary_dim, rope.factor, rope.theta) == (64, 128.0, 5e5)
    f = P.inv_freq(rope, 128)
    # D = 64, base 5e5, original length 8192. The correction dims:
    # 64 ln(8192 / (32 * 2 pi)) / (2 ln 5e5) = 9.04 -> 9 (floor) and
    # 64 ln(8192 / (2 pi)) / (2 ln 5e5) = 17.49 -> 18 (ceil): pairs up
    # to 9 keep theta^(-2i/D), pairs from 18 on are divided by 128,
    # pair 13 is 4/9 of the way.
    assert f.shape == (32,)
    assert f[0] == 1.0
    assert abs(f[9] / 5e5 ** (-18 / 64) - 1) < 1e-12
    assert abs(f[13] / (5e5 ** (-26 / 64) * (5 / 9 + 4 / 9 / 128)) - 1) < 1e-12
    assert abs(f[13] - 0.00270537) < 1e-8
    assert abs(f[18] / (5e5 ** (-36 / 64) / 128) - 1) < 1e-12
    assert abs(f[31] - 2.35458e-08) < 1e-13
    assert np.allclose(f, ref.inv_freq(rp, 128), rtol=1e-12)
    cos, sin = P.rope_table(rope, 128, 4)
    assert abs(float(cos[0, 0]) - 1.4852030263919618) < 1e-6   # x factor
    assert abs(float(sin[1, 0]) - 1.4852030263919618 * np.sin(1.0)) < 1e-6
    plain = P.inv_freq(P.Rope(theta=10000.0), 128)
    assert plain.shape == (64,) and abs(plain[1] - 0.86596432) < 1e-8


def test_partial_rotary_turns_the_leading_dims_only():
    x = jnp.arange(16, dtype=jnp.float32).reshape(1, 1, 1, 16) + 1.0
    ang = jnp.array([[[0.5, 0.25, 0.0, 1.0]]])          # rotary_dim 8
    y = np.asarray(_rope_leading(x, jnp.cos(ang), jnp.sin(ang)))[0, 0, 0]
    # pair (0, 4) by 0.5: (1, 5) -> (cos - 5 sin, 5 cos + sin)
    assert abs(y[0] - (np.cos(0.5) - 5 * np.sin(0.5))) < 1e-6
    assert abs(y[4] - (5 * np.cos(0.5) + np.sin(0.5))) < 1e-6
    assert abs(y[2] - 3.0) < 1e-6 and abs(y[6] - 7.0) < 1e-6   # angle 0
    assert (y[8:] == np.arange(9, 17)).all()                    # passed


# -- serve/: rules, backend ---------------------------------------------------


def test_every_leaf_of_a_planned_tree_meets_exactly_one_rule():
    cfg, params = program()[:2]
    paths = [p for p, _ in iter_leaf_paths(params)]
    assert len(paths) == 3 + 5 * 6 + 4 + 4 * 8
    for path in paths:
        hits = [pat for pat, _ in PARTITION_RULES if re.search(pat, path)]
        assert len(hits) == 1, (path, hits)
    specs = match_partition_rules(PARTITION_RULES, params)
    assert specs["blocks"]["01"]["mlp"]["we2"] == (-1, None, None)
    assert specs["blocks"]["01"]["attn"]["wo"] == (-1, None)
    assert specs["blocks"]["01"]["attn"]["wg"] == ()
    assert jax.tree.structure(specs, is_leaf=lambda x: isinstance(x, tuple)) \
        == jax.tree.structure(P.plan_shapes(cfg),
                              is_leaf=lambda x: isinstance(x, tuple))


def serve(engine, prompts, max_new, gateway=None):
    """Tokens of each prompt's completion, in order: through the
    gateway where there is one (the engine's own completions are read
    as they leave ``step``), else straight into the engine."""
    done, step = {}, engine.step

    def spy():
        out = step()
        done.update({c.request_id: list(c.tokens) for c in out})
        return out

    engine.step = spy
    if gateway is None:
        for p in prompts:
            engine.submit(p, max_new)
        while engine.has_work():
            engine.step()
    else:
        for p in prompts:
            assert gateway.submit(
                "t", {"prompt": p, "max_new": max_new}).admitted
        for _ in range(400):
            if not gateway.busy():
                break
            gateway.tick()
    return [done[i] for i in range(len(prompts))]


PROMPTS = [[5, 9, 2], [7] * 10, [3, 1, 4, 1, 5, 9, 2, 6], [11, 12]]


def test_sharded_serve_backend_serves_the_planned_tree():
    cfg, params = program()[:2]
    backend = ShardedServeBackend("engine", cfg, params, tp=1, dp=1,
                                  n_slots=2, prompt_bucket=12, max_len=40)
    assert backend.stats()["param_leaves"] == 69
    gw = Gateway([backend], clock=MonotonicClock(), quotas={
        "t": TenantQuota(rate=1e9, burst=1e9, slo="interactive",
                         max_queued=64)})
    got = serve(backend.engine, PROMPTS, 20, gateway=gw)
    plain = serve(ContinuousBatcher(cfg, params, n_slots=3, prompt_bucket=12,
                                    max_len=40), PROMPTS, 20)
    assert got == plain and all(len(t) == 20 for t in got)
    with pytest.raises(NotImplementedError, match="one device"):
        ShardedServeBackend("engine", cfg, params, tp=2, dp=1)


def test_what_a_planned_stack_does_not_do_raises_with_the_reason():
    cfg, params = program()[:2]
    with pytest.raises(ValueError, match="prefix"):
        ContinuousBatcher(cfg, params, n_slots=2, prompt_bucket=12,
                          max_len=40, prefix_cache_size=2)
    with pytest.raises(ValueError, match="mlp_fn"):
        slot_program(cfg, mlp_fn=lambda lp, h: (h, 0.0))
    with pytest.raises(NotImplementedError, match="uniform"):
        SpeculativeBatcher(cfg, params, cfg, params, n_slots=2,
                           prompt_bucket=12, max_len=40)
    with pytest.raises(NotImplementedError, match="uniform"):
        FAMILY.train_step(cfg, 1e-3)


# -- ENG_ROUTE ----------------------------------------------------------------


def reference_route_counts(c, prompt):
    """The routing of one prompt by the reference's own pieces: per
    expert layer the (tokens, experts_total) matrix of chosen experts."""
    seed, dt = ref.seed_word(SEED), jnp.float32
    f32 = lambda w: {k: v.astype(dt) for k, v in w.items()}  # noqa: E731
    x = ref.outer_weights(c, seed, dt)["embed"][np.asarray(prompt)[None]]
    first, held = ref.held_range(c)
    chosen = []
    for layer in range(c["serve"]["num_hidden_layers"]):
        x = ref.attention(c, x, f32(ref.attn_weights(c, seed, layer, layer,
                                                     dt)), layer)
        if c["mlp_layer_types"][layer] == "dense":
            w = ref.dense_weights(c, seed, layer, dt)
            h = ref.rms_norm(x, w["mlp_norm"], c["rms_norm_eps"])
            x = x + ref.swiglu(h, w["w1"], w["w3"], w["w2"], False)
            continue
        w = ref.sparse_outer_weights(c, seed, layer, dt)
        h = ref.rms_norm(x, w["mlp_norm"], c["rms_norm_eps"])[0]
        gate = ref.routing(c, h, w["router"], False)
        chosen.append(np.asarray(gate > 0))
        wb = ref.expert_block(c, seed, layer, first, held, dt)
        y = ref.block_of_experts(h, gate[:, first:first + held], wb, False)
        x = x + (y + ref.swiglu(h, w["ws1"], w["ws3"], w["ws2"], False))[None]
    return chosen


def test_eng_route_counts_what_the_reference_routes():
    c = toy()
    cfg, params = program()[:2]
    eng = ContinuousBatcher(cfg, params, n_slots=2, prompt_bucket=12,
                            max_len=40)
    prompt = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3]
    eng.submit(prompt, 6)
    eng.submit([2, 7], 3)
    while eng.has_work():
        eng.step()
    recs = eng.trace.peek(eng.trace.capacity).astype(np.int64)
    stamp = lambda ev: {int(r[0]) for r in recs if r[1] == ev}  # noqa: E731
    routes = [r for r in recs if r[1] == Ev.ENG_ROUTE]
    pre = [r for r in routes if int(r[0]) in stamp(Ev.ENG_PREFILL)]
    dec = [r for r in routes if int(r[0]) not in stamp(Ev.ENG_PREFILL)]
    assert len(pre) == 2 and len(dec) == 5 and len(routes) == 7
    # A decode's route is booked by the step() after the one that
    # enqueued it and stamped like that call's ENG_DECODE; the last is
    # booked by the call that drains the pipeline and enqueues nothing.
    assert sum(int(r[0]) in stamp(Ev.ENG_DECODE) for r in dec) == 4
    chosen = reference_route_counts(c, prompt)
    mine = [ch[:, :4] for ch in chosen]
    assert [int(v) for v in pre[0][3:]] == [
        len(prompt),
        sum(m.sum() for m in mine),
        sum(ch.sum() - m.sum() for ch, m in zip(chosen, mine)),
        sum(m.any(0).sum() for m in mine),
        max(m.sum(0).max() for m in mine)]
    k, sparse = c["num_experts_per_tok"], len(chosen)
    for r in dec:  # tokens routed = lanes decoding in that tick
        assert r[4] + r[5] == r[3] * k * sparse and 1 <= r[3] <= 2
        assert r[7] <= r[3] and r[6] <= r[4]
    assert [int(r[3]) for r in dec] == [2, 2, 1, 1, 1]


# -- a dense plan is what it was ----------------------------------------------


def test_a_dense_configuration_runs_the_scan_it_ran_before():
    cfg = TransformerConfig(vocab=97, d_model=48, n_layers=3, n_heads=4,
                            n_kv_heads=2, d_ff=96, max_seq=40,
                            dtype=jnp.float32)
    assert cfg.head_dim == 12
    params = init_params(cfg, jax.random.PRNGKey(0))
    import dataclasses

    explicit = dataclasses.replace(cfg, layer_plan=P.uniform_plan(cfg))
    assert isinstance(slot_program(cfg), _ScanProgram)
    assert isinstance(slot_program(explicit), _ScanProgram)
    got = serve(ContinuousBatcher(cfg, params, n_slots=2, prompt_bucket=12,
                                  max_len=40), PROMPTS, 9)

    # The same tokens from the two functions as they were before a
    # configuration had a plan: one slot, greedy.
    def before(prompt):
        cache = init_slot_cache(cfg, 1, 40)
        padded = np.zeros(12, np.int32)
        padded[:len(prompt)] = prompt
        last, cache, _ = ingest_slot_prompt(cfg, params, cache, 0,
                                            jnp.asarray(padded), len(prompt))
        toks = [int(jnp.argmax(last))]
        for _ in range(8):
            logits, new, _ = _slot_forward(
                cfg, params, jnp.asarray([[toks[-1]]], jnp.int32), cache,
                cache["pos"])
            cache = dict(new, pos=cache["pos"] + 1)
            toks.append(int(jnp.argmax(logits[0, 0])))
        return toks

    assert got == [before(p) for p in PROMPTS]
