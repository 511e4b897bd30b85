"""Continuous batching: slot engine correctness and the latency
property it exists for.

Gold standard: ``make_generate`` (the lockstep path, already
parity-tested against the model). Greedy decoding through the slot
engine must produce EXACTLY the same tokens — per request, regardless
of admission order, slot assignment, or co-resident traffic — and a
late request must start decoding while earlier ones are still running
(the whole point vs batch-lockstep serving)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pbs_tpu.models import (
    ContinuousBatcher,
    TransformerConfig,
    init_params,
    make_continuous_serve_step,
    make_generate,
)

TINY = dict(vocab=64, d_model=32, n_layers=2, n_heads=2, n_kv_heads=2,
            d_ff=64, max_seq=128, dtype=jnp.float32)


@pytest.fixture(scope="module")
def model():
    cfg = TransformerConfig(**TINY)
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _gold(cfg, params, prompt, n_new):
    gen = jax.jit(make_generate(cfg, n_new, temperature=0.0))
    out = gen(params, jnp.asarray(prompt, jnp.int32)[None, :],
              jax.random.PRNGKey(1))
    return [int(t) for t in np.asarray(out)[0]]


def _drain(eng):
    out = []
    for _ in range(500):
        out += eng.step()
        if not eng.has_work():
            break
    return {c.request_id: c for c in out}


def test_single_request_matches_lockstep_generate(model):
    cfg, params = model
    prompt = [5, 9, 2, 31, 7]
    eng = ContinuousBatcher(cfg, params, n_slots=3, prompt_bucket=16)
    rid = eng.submit(prompt, max_new_tokens=8)
    done = _drain(eng)
    assert done[rid].tokens == _gold(cfg, params, prompt, 8)
    assert done[rid].prompt_len == 5


def test_concurrent_requests_isolated(model):
    """Different prompts in different slots: each output equals its
    SOLO lockstep generation — no cross-slot contamination."""
    cfg, params = model
    prompts = {0: [3, 1, 4], 1: [15, 9, 2, 6], 2: [53, 5]}
    eng = ContinuousBatcher(cfg, params, n_slots=3, prompt_bucket=16)
    rids = {i: eng.submit(p, max_new_tokens=6)
            for i, p in prompts.items()}
    done = _drain(eng)
    for i, p in prompts.items():
        assert done[rids[i]].tokens == _gold(cfg, params, p, 6), i


def test_staggered_admission_still_exact(model):
    """A request admitted mid-flight (different slot cursor positions)
    decodes exactly as it would alone."""
    cfg, params = model
    eng = ContinuousBatcher(cfg, params, n_slots=2, prompt_bucket=16)
    r0 = eng.submit([7, 7, 7, 7], max_new_tokens=12)
    for _ in range(5):
        eng.step()  # r0 mid-generation
    r1 = eng.submit([2, 30], max_new_tokens=4)
    done = _drain(eng)
    assert done[r0].tokens == _gold(cfg, params, [7, 7, 7, 7], 12)
    assert done[r1].tokens == _gold(cfg, params, [2, 30], 4)


def test_late_request_overlaps_earlier_one(model):
    """THE continuous-batching property: with a free slot, a late
    request starts immediately instead of waiting for the running
    batch to finish."""
    cfg, params = model
    eng = ContinuousBatcher(cfg, params, n_slots=2, prompt_bucket=16)
    r_long = eng.submit([1, 2, 3], max_new_tokens=30)
    for _ in range(3):
        eng.step()
    r_short = eng.submit([4, 5], max_new_tokens=3)
    done = _drain(eng)
    # the short request finished long before the long one
    assert done[r_short].steps_waited == 0  # admitted without queueing
    assert len(done[r_long].tokens) == 30
    assert len(done[r_short].tokens) == 3


def test_queueing_when_slots_full(model):
    cfg, params = model
    eng = ContinuousBatcher(cfg, params, n_slots=1, prompt_bucket=16)
    r0 = eng.submit([9], max_new_tokens=4)
    r1 = eng.submit([8], max_new_tokens=4)
    done = _drain(eng)
    assert done[r1].steps_waited > 0  # had to wait for the slot
    assert done[r0].tokens == _gold(cfg, params, [9], 4)
    assert done[r1].tokens == _gold(cfg, params, [8], 4)


def test_eos_retires_early(model):
    cfg, params = model
    prompt = [5, 9, 2]
    gold = _gold(cfg, params, prompt, 10)
    eos = gold[3]  # force an early stop at a token we know arrives
    eng = ContinuousBatcher(cfg, params, n_slots=2, prompt_bucket=16,
                            eos_id=eos)
    rid = eng.submit(prompt, max_new_tokens=10)
    done = _drain(eng)
    assert done[rid].tokens == gold[:4]  # stopped AT the eos token


def test_submit_validation(model):
    cfg, params = model
    eng = ContinuousBatcher(cfg, params, n_slots=1, prompt_bucket=8,
                            max_len=32)
    with pytest.raises(ValueError, match="not in"):
        eng.submit(list(range(9)), max_new_tokens=2)  # over bucket
    with pytest.raises(ValueError, match="exceeds max_len"):
        eng.submit([1, 2], max_new_tokens=31)
    with pytest.raises(ValueError, match=">= 1"):
        eng.submit([1, 2], max_new_tokens=0)  # prefill would emit 1


def test_tensor_parallel_serving_token_parity(model):
    """TP serving by placement (the GSPMD recipe): the SAME two jitted
    programs run with Megatron-sharded params and kv-head-sharded
    cache slabs on a tp mesh — outputs must be token-exact against the
    single-device engine."""
    from pbs_tpu.parallel import make_mesh
    from pbs_tpu.serve.partition import place

    cfg, params = model
    mesh = make_mesh({"tp": 2}, devices=jax.devices()[:2])
    prompts = {0: [3, 1, 4], 1: [15, 9, 2, 6]}

    eng_tp = ContinuousBatcher(cfg, place(params, mesh), n_slots=2,
                               prompt_bucket=16, mesh=mesh)
    rids = {i: eng_tp.submit(p, max_new_tokens=6)
            for i, p in prompts.items()}
    done = _drain(eng_tp)
    for i, p in prompts.items():
        assert done[rids[i]].tokens == _gold(cfg, params, p, 6), i


def test_tp_mesh_validation(model):
    from pbs_tpu.parallel import make_mesh

    cfg, params = model
    with pytest.raises(ValueError, match="'tp' axis"):
        ContinuousBatcher(cfg, params, n_slots=1, prompt_bucket=8,
                          mesh=make_mesh({"dp": 2},
                                         devices=jax.devices()[:2]))


def test_slo_stats_populate(model):
    cfg, params = model
    eng = ContinuousBatcher(cfg, params, n_slots=2, prompt_bucket=16)
    hooked = []  # gateway seam: every submit reports (rid, plen, max_new)
    eng.submit_hook = lambda rid, plen, mn: hooked.append((rid, plen, mn))
    for i in range(3):
        eng.submit([1 + i], max_new_tokens=3)
    done = _drain(eng)
    st = eng.stats()
    assert st["completed"] == 3
    assert 0 < st["ttft_p50_s"] <= st["latency_p99_s"]
    for c in done.values():
        assert 0 < c.ttft_s <= c.latency_s
    assert hooked == [(rid, 1, 3) for rid in sorted(done)]
    # Both SLO windows are bounded to the same 1024-sample cap.
    assert eng._ttfts.maxlen == eng._latencies.maxlen == 1024


def test_pct_is_nearest_rank():
    """Satellite pin: the old int(q*n) indexed one rank high — p50 of
    two samples returned the max. Nearest-rank returns an observed
    sample at the ceil(q*n)-th rank."""
    pct = ContinuousBatcher._pct
    assert pct([], 0.99) == 0.0
    assert pct([7.0], 0.50) == 7.0
    assert pct([2.0, 1.0], 0.50) == 1.0  # was 2.0 before the fix
    assert pct(list(range(1, 101)), 0.50) == 50
    assert pct(list(range(1, 101)), 0.99) == 99


def test_job_shaped_serve_step(model):
    """The engine as a schedulable tenant: one token per quantum."""
    cfg, params = model
    eng = ContinuousBatcher(cfg, params, n_slots=2, prompt_bucket=16)

    def feed(step):
        return [([3, 1], 3)] if step == 0 else []

    serve = make_continuous_serve_step(eng, next_requests=feed)
    state = {"step": 0, "completed": 0}
    metric_total = 0
    for _ in range(8):
        state, metrics = serve(state)
        metric_total += int(metrics["tokens"])
    assert state["completed"] == 1
    assert eng.stats()["tokens_emitted"] == 3
    # the TOKENS metric is exact goodput: no double count on
    # completion, no undercount on admission (review finding)
    assert metric_total == 3


def test_prefix_cache_token_exact_and_skips_prefill():
    """Exact-prompt prefix cache: a repeated prompt produces the
    identical greedy completion while dispatching zero prefill
    forwards (the KV window installs from host RAM)."""
    cfg = TransformerConfig(**TINY)
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = ContinuousBatcher(cfg, params, n_slots=2, prompt_bucket=8,
                            max_len=32, prefix_cache_size=4)
    prompt = [5, 7, 11]

    def run_one():
        rid = eng.submit(prompt, max_new_tokens=4)
        out = []
        while not out:
            out = [c for c in eng.step() if c.request_id == rid]
        return out[0].tokens

    t1 = run_one()
    assert eng.prefill_count == 1 and eng.prefix_hits == 0
    t2 = run_one()
    assert t2 == t1  # token-exact from the cached window
    assert eng.prefill_count == 1  # no second prefill dispatch
    assert eng.prefix_hits == 1
    assert eng.stats()["prefix_hits"] == 1


def test_moe_serving_on_tp_mesh_token_exact():
    """r5: the mlp_fn x mesh rejection is lifted — an MoE engine on a
    tp mesh (Megatron attention + expert d_ff column/row shards, the
    serve rule table's ``layers/we*`` rules) must produce token-exact
    greedy output vs the single-device MoE engine, with zero drops
    (dropless)."""
    from pbs_tpu.models import MoEConfig
    from pbs_tpu.models.moe import init_moe_params, moe_slot_mlp
    from pbs_tpu.parallel import make_mesh
    from pbs_tpu.serve.partition import place

    mcfg = MoEConfig(
        vocab=64, d_model=32, n_layers=2, n_heads=2, n_kv_heads=2,
        d_ff=64, max_seq=128, dtype=jnp.float32, n_experts=4, top_k=2,
        dropless=True, router_group_size=8,
    )
    params = init_moe_params(mcfg, jax.random.PRNGKey(0))
    prompt = [5, 9, 2, 31, 7]

    def run(mesh):
        eng = ContinuousBatcher(
            mcfg, params if mesh is None else place(params, mesh),
            n_slots=2, prompt_bucket=16,
            mlp_fn=moe_slot_mlp(mcfg), mesh=mesh)
        rid = eng.submit(prompt, max_new_tokens=8)
        done = _drain(eng)
        return done[rid].tokens

    gold = run(None)
    mesh = make_mesh({"tp": 2}, devices=jax.devices()[:2])
    assert run(mesh) == gold


def test_prefix_cache_on_tp_mesh_token_exact(model):
    """r5: prefix cache composes with tp serving (the restriction is
    lifted). The cached window slices stay tp-sharded on device; a hit
    installs with zero prefill dispatches and the greedy completion is
    token-exact against the single-device gold."""
    from pbs_tpu.parallel import make_mesh
    from pbs_tpu.serve.partition import place

    cfg, params = model
    mesh = make_mesh({"tp": 2}, devices=jax.devices()[:2])
    eng = ContinuousBatcher(cfg, place(params, mesh), n_slots=2,
                            prompt_bucket=16, mesh=mesh,
                            prefix_cache_size=4)
    prompt = [3, 1, 4]
    gold = _gold(cfg, params, prompt, 6)

    def run_one():
        rid = eng.submit(prompt, max_new_tokens=6)
        out = []
        while not out:
            out = [c for c in eng.step() if c.request_id == rid]
        return out[0].tokens

    t1 = run_one()
    assert t1 == gold
    assert eng.prefill_count == 1 and eng.prefix_hits == 0
    t2 = run_one()
    assert t2 == gold  # token-exact from the sharded cached window
    assert eng.prefill_count == 1  # hit: no second prefill dispatch
    assert eng.prefix_hits == 1


def test_prefix_cache_lru_eviction():
    cfg = TransformerConfig(**TINY)
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = ContinuousBatcher(cfg, params, n_slots=1, prompt_bucket=8,
                            max_len=32, prefix_cache_size=1)

    def run(prompt):
        rid = eng.submit(prompt, max_new_tokens=2)
        while eng.has_work():
            eng.step()

    run([1, 2])
    run([3, 4])      # evicts [1, 2]
    run([1, 2])      # miss again
    assert eng.prefix_hits == 0 and eng.prefill_count == 3
    run([1, 2])      # now a hit
    assert eng.prefix_hits == 1 and eng.prefill_count == 3


def test_prefix_cache_off_by_default():
    cfg = TransformerConfig(**TINY)
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = ContinuousBatcher(cfg, params, n_slots=1, prompt_bucket=8,
                            max_len=32)
    for _ in range(2):
        eng.submit([1, 2], max_new_tokens=2)
        while eng.has_work():
            eng.step()
    assert eng.prefix_hits == 0 and eng.prefill_count == 2


# -- the engine owns its cache: donated, written in place ---------------------


@pytest.mark.parametrize("program", ["prefill", "install", "decode"])
def test_programs_donate_the_cache(model, program, donating):
    """Every program that takes the cache and returns one donates it:
    the handle that went in is dead, the one that came out is whole."""
    cfg, params = model
    eng = ContinuousBatcher(cfg, params, n_slots=2, prompt_bucket=8,
                            max_len=32, prefix_cache_size=2)
    old = eng.cache
    key = jax.random.PRNGKey(0)
    win = jnp.ones((cfg.n_layers, 1, 8, cfg.n_kv_heads, cfg.head_dim),
                   cfg.dtype)
    call = {
        "prefill": lambda: eng._prefill_fn(
            eng.params, old, eng._dev_tok, 1,
            jnp.arange(8, dtype=jnp.int32), 5, key)[3],
        "install": lambda: eng._install_fn(old, 1, win, win, 5),
        "decode": lambda: eng._decode_fn(  # lane 0 off, lane 1 on token 0
            eng.params, old, jnp.zeros((2,), jnp.int32),
            jnp.array([-2, 0], jnp.int32), key)[2],
    }[program]
    before = np.asarray(old["k"]).copy()
    out = donating(call, old["k"], old["v"])
    assert out["k"].shape == old["k"].shape
    want_pos = {"prefill": [0, 5], "install": [0, 5], "decode": [0, 1]}
    assert np.asarray(out["pos"]).tolist() == want_pos[program]
    if program == "install":  # the window landed in slot 1, nowhere else
        k = np.array(out["k"])  # a copy: a device array's view is read-only
        assert (k[:, 1, :8] == 1).all()
        k[:, 1, :8] = before[:, 1, :8]
        np.testing.assert_array_equal(k, before)


@pytest.mark.parametrize("prefix_cache_size", [0, 4])
def test_warm_up_leaves_cursors_zero_and_first_request_exact(
        model, prefix_cache_size):
    """The constructor's warm-up runs the donating programs and rebinds
    the cache: every cursor is still 0 and the first tenant is exact."""
    cfg, params = model
    eng = ContinuousBatcher(cfg, params, n_slots=3, prompt_bucket=16,
                            prefix_cache_size=prefix_cache_size)
    assert not eng.cache["k"].is_deleted()
    assert not np.asarray(eng.cache["pos"]).any()
    prompt = [5, 9, 2, 31, 7]
    rid = eng.submit(prompt, max_new_tokens=8)
    assert _drain(eng)[rid].tokens == _gold(cfg, params, prompt, 8)


def test_prefix_window_outlives_the_cache_it_was_sliced_from(model):
    """A window saved at tick n is an array of its own: it installs
    token-exact at tick n + 40, after the cache it was sliced from has
    been donated on every tick in between."""
    cfg, params = model
    eng = ContinuousBatcher(cfg, params, n_slots=2, prompt_bucket=8,
                            max_len=64, prefix_cache_size=4)
    prompt = [5, 7, 11]
    gold = _gold(cfg, params, prompt, 6)
    rid = eng.submit(prompt, max_new_tokens=6)
    assert _drain(eng)[rid].tokens == gold
    saved_at = eng.steps
    ent = eng._prefix_cache[np.asarray(prompt, np.int32).tobytes()]
    snap = np.asarray(ent["k"]).copy()
    other = eng.submit([3, 1, 4, 1, 5], max_new_tokens=45)
    assert len(_drain(eng)[other].tokens) == 45
    assert eng.steps >= saved_at + 40
    assert not ent["k"].is_deleted() and not ent["v"].is_deleted()
    np.testing.assert_array_equal(np.asarray(ent["k"]), snap)
    rid = eng.submit(prompt, max_new_tokens=6)
    assert _drain(eng)[rid].tokens == gold
    assert eng.prefix_hits == 1 and eng.prefill_count == 2


# -- the layer scan against a plain loop over unstacked weights ---------------


GQA = dict(vocab=61, d_model=64, n_layers=3, n_heads=8, n_kv_heads=2,
           d_ff=96, max_seq=24, dtype=jnp.float32)


def _tanh_mlp(lp, h):
    """A stand-in for the FFN seam: its own product and a constant
    auxiliary scalar, so the sum over layers can be told."""
    from pbs_tpu.models.quant import wload

    y = jnp.tanh(h @ wload(lp["w1"], h.dtype)) @ wload(lp["w2"], h.dtype)
    return y, jnp.float32(0.25)


def _layer_loop(cfg, params, tokens, cache, row_pos, mlp_fn=None):
    """``_slot_forward``'s contract the plain way: a Python loop over
    layers, each layer's leaves taken out of the stack first (an int8
    leaf dequantised whole), one row and one position at a time."""
    from pbs_tpu.models.quant import embed_rows, wload
    from pbs_tpu.models.transformer import rms_norm, rope_tables

    dt = cfg.dtype
    B, S = tokens.shape
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    T = cache["k"].shape[2]
    cos, sin = (np.asarray(t) for t in rope_tables(cfg, T))
    ks, vs = np.array(cache["k"]), np.array(cache["v"])
    row_pos = np.asarray(row_pos)

    def rope(x, p):  # x (H, hd) at position p
        x1, x2 = x[:, :hd // 2], x[:, hd // 2:]
        return np.concatenate([x1 * cos[p] - x2 * sin[p],
                               x2 * cos[p] + x1 * sin[p]], axis=-1)

    x = embed_rows(params["embed"], tokens, dt)
    extra = 0.0
    for layer in range(cfg.n_layers):
        lp = jax.tree.map(lambda w: w[layer], params["layers"])
        h = np.asarray(rms_norm(x, lp["attn_norm"], cfg.norm_eps))
        wq, wk, wv, wo = (np.asarray(wload(lp[n], dt))
                          for n in ("wq", "wk", "wv", "wo"))
        attn = np.zeros((B, S, nh * hd), np.float32)
        for b in range(B):
            for s in range(S):
                p = int(row_pos[b]) + s
                ks[layer, b, p] = rope((h[b, s] @ wk).reshape(nkv, hd), p)
                vs[layer, b, p] = (h[b, s] @ wv).reshape(nkv, hd)
            for s in range(S):
                p = int(row_pos[b]) + s
                q = rope((h[b, s] @ wq).reshape(nh, hd), p)
                for head in range(nh):
                    g = head // (nh // nkv)
                    sc = ks[layer, b, :p + 1, g] @ q[head] / np.sqrt(hd)
                    pr = np.exp(sc - sc.max())
                    attn[b, s, head * hd:(head + 1) * hd] = \
                        (pr / pr.sum()) @ vs[layer, b, :p + 1, g]
        x = x + jnp.asarray(attn @ wo)
        h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
        if mlp_fn is None:
            y = (jax.nn.silu(h @ wload(lp["w1"], dt))
                 * (h @ wload(lp["w3"], dt))) @ wload(lp["w2"], dt)
        else:
            y, e = mlp_fn(lp, h)
            extra += float(e)
        x = x + y
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return np.asarray(x @ wload(params["head"], dt)), ks, vs, extra


@pytest.mark.parametrize("seam", ["dense", "mlp_fn"])
@pytest.mark.parametrize("leaf", ["plain", "int8"])
@pytest.mark.parametrize("S", [1, 5], ids=["decode", "window"])
def test_layer_scan_equals_a_plain_loop_over_unstacked_weights(S, leaf, seam):
    """The scan body's products read their operands in the stack; what
    comes out is what a loop over each layer's own weights gives: the
    logits, and a cache that differs from the one that went in at the
    B x S written positions of each layer and nowhere else. Eight query
    heads on two kv heads, every row at its own cursor, the cache full
    of another tenant's rows beforehand."""
    from pbs_tpu.models.quant import quantize_weights
    from pbs_tpu.models.slot_programs import _slot_forward

    cfg = TransformerConfig(**GQA)
    params = init_params(cfg, jax.random.PRNGKey(3))
    if leaf == "int8":
        params = quantize_weights(params)
        assert set(params["layers"]["wq"]) == {"q", "s"}
    mlp_fn = _tanh_mlp if seam == "mlp_fn" else None
    B, T = 3, cfg.max_seq
    kk, kv, kt = jax.random.split(jax.random.PRNGKey(S), 3)
    shape = (cfg.n_layers, B, T, cfg.n_kv_heads, cfg.head_dim)
    cache = {"k": jax.random.normal(kk, shape, cfg.dtype),
             "v": jax.random.normal(kv, shape, cfg.dtype),
             "pos": jnp.zeros((B,), jnp.int32)}
    row_pos = jnp.asarray([0, 7, 13], jnp.int32)
    tokens = jax.random.randint(kt, (B, S), 0, cfg.vocab)

    logits, new, extra = jax.jit(
        lambda p, t, c, r: _slot_forward(cfg, p, t, c, r, mlp_fn=mlp_fn))(
            params, tokens, cache, row_pos)
    want, ks, vs, want_extra = _layer_loop(cfg, params, tokens, cache,
                                           row_pos, mlp_fn)

    assert logits.shape == (B, S, cfg.vocab) and logits.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(logits), want, atol=2e-4)
    np.testing.assert_allclose(np.asarray(new["k"]), ks, atol=2e-5)
    np.testing.assert_allclose(np.asarray(new["v"]), vs, atol=2e-5)
    written = np.zeros(shape[:3], bool)
    for b, p in enumerate(np.asarray(row_pos)):
        written[:, b, p:p + S] = True
    for name in ("k", "v"):  # untouched rows are the very bits that went in
        np.testing.assert_array_equal(np.asarray(new[name])[~written],
                                      np.asarray(cache[name])[~written])
    assert float(extra) == pytest.approx(want_extra)
    assert want_extra == (0.25 * cfg.n_layers if mlp_fn else 0.0)


@pytest.mark.parametrize("leaf", ["plain", "int8"])
def test_grouped_heads_served_tokens_equal_lockstep_generate(leaf):
    """Eight query heads on two kv heads, through the engine (prefill,
    then ticks beside another tenant): the lockstep path's tokens."""
    from pbs_tpu.models.quant import quantize_weights

    cfg = TransformerConfig(**GQA)
    params = init_params(cfg, jax.random.PRNGKey(3))
    if leaf == "int8":
        params = quantize_weights(params)
    eng = ContinuousBatcher(cfg, params, n_slots=2, prompt_bucket=8)
    prompts = [[5, 9, 2, 31, 7], [11, 3, 40]]
    rids = [eng.submit(p, max_new_tokens=9) for p in prompts]
    done = _drain(eng)
    for rid, prompt in zip(rids, prompts):
        assert done[rid].tokens == _gold(cfg, params, prompt, 9)
