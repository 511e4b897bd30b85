"""Speculative continuous batching: the two serving accelerations
composed. Exactness contract: greedy spec serving is bit-identical to
the plain engine; efficiency contract: engine ticks shrink by the
acceptance rate."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pbs_tpu.models import (
    ContinuousBatcher,
    SpeculativeBatcher,
    TransformerConfig,
    init_params,
)

CFG = TransformerConfig(vocab=128, d_model=64, n_layers=2, n_heads=4,
                        n_kv_heads=2, d_ff=128, max_seq=128,
                        dtype=jnp.float32)
PROMPTS = [[1, 2, 3], [9, 8, 7, 6], [4, 4], [11, 12, 13]]


@pytest.fixture(scope="module")
def models():
    params = init_params(CFG, jax.random.PRNGKey(0))
    noise = jax.random.normal(jax.random.PRNGKey(7),
                              params["head"].shape)
    dparams = dict(params, head=params["head"] + 0.01 * noise)
    return params, dparams


def drain(eng, max_ticks=300):
    got = {}
    for _ in range(max_ticks):
        for c in eng.step():
            got[c.request_id] = c.tokens
        if not eng.has_work():
            break
    assert not eng.has_work(), "engine did not drain"
    return got


@pytest.mark.slow  # ~11 s token-exact mesh property sweep
def test_spec_serving_on_tp_mesh_token_exact(models):
    """r5: speculative serving composes with the tp mesh — target AND
    draft trees Megatron-sharded, both slot caches kv-head-sharded.
    Outputs stay bit-identical to the single-device spec engine."""
    from pbs_tpu.parallel import make_mesh
    from pbs_tpu.serve.partition import place

    params, dparams = models
    gold_eng = SpeculativeBatcher(CFG, params, CFG, dparams, k=3,
                                  n_slots=2, prompt_bucket=8,
                                  max_len=64)
    mesh = make_mesh({"tp": 2}, devices=jax.devices()[:2])
    mesh_eng = SpeculativeBatcher(CFG, place(params, mesh), CFG,
                                  place(dparams, mesh), k=3,
                                  n_slots=2, prompt_bucket=8,
                                  max_len=64, mesh=mesh)
    for eng in (gold_eng, mesh_eng):
        for p in PROMPTS[:2]:
            eng.submit(p, max_new_tokens=8)
    assert drain(gold_eng) == drain(mesh_eng)


def test_spec_serving_with_prefix_cache_token_exact(models):
    """r5: speculative serving composes with the prefix cache — a hit
    installs the TARGET window while the draft still prefills (the
    _admitted hook covers hits and misses), so the pos invariant holds
    and outputs stay bit-identical with zero second target prefill."""
    params, dparams = models
    eng = SpeculativeBatcher(CFG, params, CFG, dparams, k=3, n_slots=2,
                             prompt_bucket=8, max_len=64,
                             prefix_cache_size=4)
    prompt = [1, 2, 3]

    def run_one():
        rid = eng.submit(prompt, max_new_tokens=8)
        out = []
        while not out:
            out = [c for c in eng.step() if c.request_id == rid]
        return out[0].tokens

    t1 = run_one()
    assert eng.prefill_count == 1 and eng.prefix_hits == 0
    t2 = run_one()
    assert t2 == t1
    assert eng.prefill_count == 1  # hit: no second target prefill
    assert eng.prefix_hits == 1


def test_spec_serving_token_exact_and_fewer_ticks(models):
    params, dparams = models
    plain = ContinuousBatcher(CFG, params, n_slots=2, prompt_bucket=8,
                              max_len=64)
    spec = SpeculativeBatcher(CFG, params, CFG, dparams, k=3, n_slots=2,
                              prompt_bucket=8, max_len=64)
    for eng in (plain, spec):
        for p in PROMPTS:
            eng.submit(p, max_new_tokens=10)
    out_p = drain(plain)
    out_s = drain(spec)
    assert out_p == out_s  # bit-identical, request by request
    st = spec.stats()
    # A 0.01-noise draft accepts most proposals: far fewer ticks.
    assert st["steps"] < plain.stats()["steps"]
    assert st["spec_acceptance"] > 0.5
    assert 0 <= st["spec_accepted"] <= st["spec_proposed"]


def test_spec_serving_eos_truncation_matches_plain(models):
    params, dparams = models
    # Discover a token that appears mid-stream, then make it EOS.
    probe = ContinuousBatcher(CFG, params, n_slots=2, prompt_bucket=8,
                              max_len=64)
    for p in PROMPTS:
        probe.submit(p, max_new_tokens=10)
    streams = drain(probe)
    eos = None
    for toks in streams.values():
        if len(toks) > 2:
            eos = toks[2]  # mid-stream token -> early stop for that req
            break
    assert eos is not None
    plain = ContinuousBatcher(CFG, params, n_slots=2, prompt_bucket=8,
                              max_len=64, eos_id=eos)
    spec = SpeculativeBatcher(CFG, params, CFG, dparams, k=3, n_slots=2,
                              prompt_bucket=8, max_len=64, eos_id=eos)
    for eng in (plain, spec):
        for p in PROMPTS:
            eng.submit(p, max_new_tokens=10)
    assert drain(plain) == drain(spec)


def test_spec_serving_self_draft_max_speedup(models):
    """Draft == target: every window fully accepted; an R-token
    request finishes in ceil((R-1)/(k+1)) decode ticks + admission."""
    params, _ = models
    spec = SpeculativeBatcher(CFG, params, CFG, params, k=3, n_slots=1,
                              prompt_bucket=8, max_len=64)
    spec.submit([1, 2, 3], max_new_tokens=9)
    drain(spec)
    st = spec.stats()
    assert st["spec_acceptance"] == 1.0
    # 1 admit tick samples token 1; 8 more tokens / (k+1)=4 -> 2 ticks;
    # +1 final retire-check tick.
    assert st["steps"] <= 4


def test_spec_serving_guards(models):
    params, dparams = models
    with pytest.raises(ValueError, match="greedy-only"):
        SpeculativeBatcher(CFG, params, CFG, dparams, temperature=0.7,
                           prompt_bucket=8, max_len=64)
    with pytest.raises(ValueError, match="k must be"):
        SpeculativeBatcher(CFG, params, CFG, dparams, k=0,
                           prompt_bucket=8, max_len=64)
    spec = SpeculativeBatcher(CFG, params, CFG, dparams, k=3,
                              prompt_bucket=8, max_len=32)
    with pytest.raises(ValueError, match="overshoot"):
        spec.submit([1, 2, 3], max_new_tokens=29)  # 3+29+4 > 32


@pytest.mark.slow  # ~8 s int8-target sweep (tier-1 wall rescue)
def test_spec_serving_int8_target(models):
    """The deployment shape: big int8-quantized target + small fp
    draft. Exactness holds vs the plain engine on the SAME quantized
    target (acceptance compares the quantized target's own argmax)."""
    from pbs_tpu.models.quant import quantize_weights

    params, dparams = models
    qparams = quantize_weights(params)
    plain = ContinuousBatcher(CFG, qparams, n_slots=2, prompt_bucket=8,
                              max_len=64)
    spec = SpeculativeBatcher(CFG, qparams, CFG, dparams, k=3,
                              n_slots=2, prompt_bucket=8, max_len=64)
    for eng in (plain, spec):
        for p in PROMPTS:
            eng.submit(p, max_new_tokens=8)
    assert drain(plain) == drain(spec)


@pytest.mark.parametrize("seed", [11, 23])
def test_spec_serving_randomized_exactness(models, seed):
    """Seeded fuzz: random prompts, budgets, submission timing, and an
    EOS drawn from the vocab — spec and plain engines must agree
    request-for-request under any interleaving."""
    params, dparams = models
    rng = np.random.default_rng(seed)
    reqs = [(list(rng.integers(1, CFG.vocab, rng.integers(1, 8))),
             int(rng.integers(1, 12))) for _ in range(7)]
    eos = int(rng.integers(1, CFG.vocab))
    outs = []
    for make in (
        lambda: ContinuousBatcher(CFG, params, n_slots=2,
                                  prompt_bucket=8, max_len=64,
                                  eos_id=eos),
        lambda: SpeculativeBatcher(CFG, params, CFG, dparams, k=3,
                                   n_slots=2, prompt_bucket=8,
                                   max_len=64, eos_id=eos),
    ):
        eng = make()
        got = {}
        pending = list(reqs)
        ticks = 0
        while (pending or eng.has_work()) and ticks < 400:
            # staggered arrivals: a request lands every other tick
            if pending and ticks % 2 == 0:
                p, n = pending.pop(0)
                eng.submit(p, max_new_tokens=n)
            for c in eng.step():
                got[c.request_id] = c.tokens
            ticks += 1
        assert not pending and not eng.has_work()
        outs.append(got)
    assert outs[0] == outs[1]


def test_moe_continuous_serving_token_exact():
    """The MoE family serves through the same slot engine (mlp_fn
    seam): engine outputs match the lockstep MoE generate loop
    token-for-token under dropless capacity."""
    from pbs_tpu.models import MoEConfig, init_moe_params, make_moe_generate
    from pbs_tpu.models.moe import moe_slot_mlp

    mcfg = MoEConfig(vocab=128, d_model=64, n_layers=2, n_heads=4,
                     n_kv_heads=2, d_ff=96, max_seq=128,
                     dtype=jnp.float32, n_experts=4, top_k=2,
                     dropless=True)  # provably dropless routing
    mparams = init_moe_params(mcfg, jax.random.PRNGKey(0))
    prompt = jnp.asarray([[5, 6, 7, 8]], jnp.int32)
    ref, _drop = jax.jit(make_moe_generate(mcfg, 8, temperature=0.0))(
        mparams, prompt, jax.random.PRNGKey(9))
    ref = [int(t) for t in np.asarray(ref)[0]]

    eng = ContinuousBatcher(mcfg, mparams, n_slots=2, prompt_bucket=4,
                            max_len=64, mlp_fn=moe_slot_mlp(mcfg))
    eng.submit([5, 6, 7, 8], max_new_tokens=8)
    got = drain(eng)
    assert got[0] == ref, (got[0], ref)


@pytest.mark.slow  # ~10 s token-exact MoE property sweep
def test_moe_speculative_serving_token_exact():
    """And the composition: MoE target + dense draft in the
    speculative engine, exact vs the plain MoE engine."""
    from pbs_tpu.models import MoEConfig, init_moe_params
    from pbs_tpu.models.moe import moe_slot_mlp

    mcfg = MoEConfig(vocab=128, d_model=64, n_layers=2, n_heads=4,
                     n_kv_heads=2, d_ff=96, max_seq=128,
                     dtype=jnp.float32, n_experts=4, top_k=2,
                     capacity_factor=4.0)
    mparams = init_moe_params(mcfg, jax.random.PRNGKey(0))
    dparams = init_params(CFG, jax.random.PRNGKey(1))  # dense draft
    plain = ContinuousBatcher(mcfg, mparams, n_slots=2, prompt_bucket=8,
                              max_len=64, mlp_fn=moe_slot_mlp(mcfg))
    spec = SpeculativeBatcher(mcfg, mparams, CFG, dparams, k=3,
                              n_slots=2, prompt_bucket=8, max_len=64,
                              mlp_fn=moe_slot_mlp(mcfg))
    for eng in (plain, spec):
        for p in PROMPTS:
            eng.submit(p, max_new_tokens=8)
    assert drain(plain) == drain(spec)


def test_moe_drop_telemetry_surfaces(models):
    """A capacity-starved MoE draft silently collapses acceptance —
    the engine's draft drop telemetry is its alarm (and the target's
    own mlp_extra_mean stays clean)."""
    from pbs_tpu.models import MoEConfig, init_moe_params
    from pbs_tpu.models.moe import moe_slot_mlp

    params, _ = models
    starved = MoEConfig(vocab=128, d_model=64, n_layers=2, n_heads=4,
                        n_kv_heads=2, d_ff=96, max_seq=128,
                        dtype=jnp.float32, n_experts=4, top_k=2,
                        capacity_factor=0.3)
    dparams = init_moe_params(starved, jax.random.PRNGKey(1))
    spec = SpeculativeBatcher(CFG, params, starved, dparams, k=3,
                              n_slots=2, prompt_bucket=8, max_len=64,
                              draft_mlp_fn=moe_slot_mlp(starved))
    for p in PROMPTS[:2]:
        spec.submit(p, max_new_tokens=8)
    drain(spec)
    st = spec.stats()
    assert st["draft_mlp_extra_mean"] > 0.1, st
    assert st["mlp_extra_mean"] == 0.0  # dense target: no drops


@pytest.mark.parametrize("program", ["draft_prefill", "spec_decode"])
def test_spec_programs_donate_their_caches(models, program, donating):
    """The speculative engine owns BOTH slot caches the way the plain
    engine owns one: each program donates every cache it returns."""
    params, dparams = models
    eng = SpeculativeBatcher(CFG, params, CFG, dparams, k=3, n_slots=2,
                             prompt_bucket=8, max_len=64)
    tc, dc = eng.cache, eng.dcache
    if program == "draft_prefill":
        out = donating(
            lambda: eng._draft_prefill_fn(
                eng.draft_params, dc, 1, jnp.arange(8, dtype=jnp.int32),
                5)[0],
            dc["k"], dc["v"])
        assert not tc["k"].is_deleted()  # the target's is not its to take
        assert np.asarray(out["pos"]).tolist() == [0, 5]
    else:
        tout, dout = donating(
            lambda: eng._spec_decode_fn(
                eng.params, eng.draft_params, tc, dc,
                jnp.zeros((2,), jnp.int32), jnp.array([False, True]))[2:4],
            tc["k"], tc["v"], dc["k"], dc["v"])
        # The active lane advanced by its accepted prefix + 1, the
        # same in both caches (the pos invariant); the idle one held.
        assert np.asarray(tout["pos"]).tolist() == \
            np.asarray(dout["pos"]).tolist()
        assert int(tout["pos"][0]) == 0 and 1 <= int(tout["pos"][1]) <= 4


def test_spec_warm_up_leaves_cursors_zero_and_first_request_exact(models):
    """Both caches come out of the constructor's (donating, rebinding)
    warm-up alive with every cursor at 0; the first tenant's tokens
    are the plain engine's."""
    params, dparams = models
    eng = SpeculativeBatcher(CFG, params, CFG, dparams, k=3, n_slots=2,
                             prompt_bucket=8, max_len=64)
    for cache in (eng.cache, eng.dcache):
        assert not cache["k"].is_deleted()
        assert not np.asarray(cache["pos"]).any()
    plain = ContinuousBatcher(CFG, params, n_slots=2, prompt_bucket=8,
                              max_len=64)
    for e in (eng, plain):
        e.submit(PROMPTS[1], max_new_tokens=12)
    assert drain(eng) == drain(plain)
