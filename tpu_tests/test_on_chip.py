"""On-chip validation suite: compiled Pallas kernels + measured paths.

Lives OUTSIDE tests/ on purpose: tests/conftest.py pins the process to
a virtual CPU platform (the right thing for CI), while this suite
needs the chip — and a chip belongs to one process, so it is never run
as a child of a pytest parent that has touched JAX. Run it through the
chip tool, from the repo root:

    chiprun -- python -m pytest tpu_tests -q

It proves what interpret-mode CI cannot: the kernels compile through
the Mosaic TPU lowering and agree with the XLA reference numerically.
Off a TPU the module fails to import — an error, not a skip, so that on
the chip a skip can never pass for a pass.
"""

import numpy as np

import jax
import jax.numpy as jnp

_dev = jax.devices()[0]
if _dev.platform != "tpu":
    raise RuntimeError(
        f"tpu_tests needs a TPU; JAX's default device is platform="
        f"{_dev.platform} ({_dev.device_kind}). Run it through the chip "
        "tool: chiprun -- python -m pytest tpu_tests -q")


def dense_attention(q, k, v, causal=True):
    B, S, H, hd = q.shape
    group = H // k.shape[2]
    kr = jnp.repeat(k, group, axis=2)
    vr = jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   kr.astype(jnp.float32)) / np.sqrt(hd)
    if causal:
        rows = jax.lax.broadcasted_iota(jnp.int32, (S, S), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (S, S), 1)
        s = jnp.where((cols <= rows)[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, vr.astype(jnp.float32))


def dense_lse(q, k):
    """(B, S, H) causal logsumexp of the scaled scores."""
    S, hd = q.shape[1], q.shape[3]
    kr = jnp.repeat(k, q.shape[2] // k.shape[2], axis=2)
    s = jnp.einsum("bqhd,bkhd->bqhk", q.astype(jnp.float32),
                   kr.astype(jnp.float32)) / np.sqrt(hd)
    rows = jax.lax.broadcasted_iota(jnp.int32, (S, S), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (S, S), 1)
    s = jnp.where((cols <= rows)[None, :, None, :], s, -1e30)
    return jax.nn.logsumexp(s, axis=-1)


def qkv(B=2, S=512, H=8, Hkv=4, hd=128, seed=0, dtype=jnp.bfloat16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (B, S, H, hd), dtype),
            jax.random.normal(ks[1], (B, S, Hkv, hd), dtype),
            jax.random.normal(ks[2], (B, S, Hkv, hd), dtype))


def test_flash_forward_compiled():
    from pbs_tpu.ops.attention import flash_attention, flash_attention_lse

    q, k, v = qkv()
    out = flash_attention(q, k, v, causal=True)  # compiled: not the CPU
    ref = dense_attention(q, k, v)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref)))
    assert err < 0.05, err  # bf16 inputs
    # The lse variant (ring attention's combiner state) shares the
    # kernel; its fp32 o and its logsumexp must agree too.
    o32, lse = flash_attention_lse(q, k, v, causal=True)
    assert float(jnp.max(jnp.abs(o32 - ref))) < 0.05
    ref_lse = dense_lse(q, k)
    assert float(jnp.max(jnp.abs(lse[..., 0] - ref_lse))) < 0.05


def test_flash_forward_ragged_compiled():
    from pbs_tpu.ops.attention import flash_attention

    q, k, v = qkv(S=511)  # in-wrapper padding through the TPU lowering
    out = flash_attention(q, k, v, causal=True)
    ref = dense_attention(q, k, v)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref)))
    assert err < 0.05, err


def test_flash_backward_compiled():
    """The custom-VJP backward kernels (dq pass, GQA dk/dv pass)
    through the Mosaic lowering — the one thing CPU CI cannot prove."""
    from pbs_tpu.ops.attention import flash_attention

    q, k, v = qkv(B=1, S=512, H=4, Hkv=2)
    w = jax.random.normal(jax.random.PRNGKey(7), q.shape, q.dtype)

    def lf(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True).astype(
            jnp.float32) * w.astype(jnp.float32))

    def ld(q, k, v):
        return jnp.sum(dense_attention(q, k, v) * w.astype(jnp.float32))

    gf = jax.jit(jax.grad(lf, argnums=(0, 1, 2)))(q, k, v)
    gd = jax.jit(jax.grad(ld, argnums=(0, 1, 2)))(q, k, v)
    for name, a, b in zip("qkv", gf, gd):
        a32, b32 = a.astype(jnp.float32), b.astype(jnp.float32)
        rel = float(jnp.max(jnp.abs(a32 - b32))) / (
            float(jnp.max(jnp.abs(b32))) + 1e-9)
        assert rel < 0.05, (name, rel)


def test_instrumented_matmul_compiled():
    from pbs_tpu.ops.matmul import instrumented_matmul, scale_stats

    a = jax.random.normal(jax.random.PRNGKey(0), (512, 512), jnp.bfloat16)
    b = jax.random.normal(jax.random.PRNGKey(1), (512, 512), jnp.bfloat16)
    out, raw = instrumented_matmul(a, b, block_m=256, block_n=256,
                                   block_k=256)
    ref = (a.astype(jnp.float32) @ b.astype(jnp.float32))
    err = float(jnp.max(jnp.abs(out - ref))) / float(jnp.max(jnp.abs(ref)))
    assert err < 0.05, err
    st = scale_stats(np.asarray(raw), 256, 256, 256)
    assert st.mxu_tiles == 8  # (512/256)^3
    assert st.flops == 8 * 2 * 256 ** 3


def test_flash_long_context_numerics():
    """Flash at S=2048 (towards long context) against the dense
    reference, on the chip — online-softmax
    accumulation error must stay bounded as the number of folded
    k-blocks grows."""
    from pbs_tpu.ops.attention import flash_attention

    q, k, v = qkv(B=1, S=2048, H=8, Hkv=4, hd=128, seed=3)
    out = jax.jit(flash_attention)(q, k, v)
    ref = jax.jit(dense_attention)(q, k, v)
    a = out.astype(jnp.float32)
    rel = float(jnp.max(jnp.abs(a - ref))) / (
        float(jnp.max(jnp.abs(ref))) + 1e-9)
    assert rel < 0.05, rel


def test_flash_block_shape_knobs():
    """The env-tunable block shapes compile at non-default settings
    (the sweep's tuning surface)."""
    from pbs_tpu.ops.attention import flash_attention

    q, k, v = qkv(B=1, S=1024, H=8, Hkv=4, hd=128, seed=4)
    out = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, block_q=256, block_k=512))(q, k, v)
    ref = jax.jit(dense_attention)(q, k, v)
    rel = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref))) / (
        float(jnp.max(jnp.abs(ref))) + 1e-9)
    assert rel < 0.05, rel


def test_profiler_device_lane_parse_on_chip():
    """The measured-telemetry path against a REAL chip trace (verdict
    r2 weak #4: the parser was only ever validated on CPU thunk
    events). Asserts device lanes are found and the compute/memory
    phase signal separates an MXU-bound program from an HBM-bound one
    on real device-lane timing."""
    from pbs_tpu.telemetry.profiler import XlaQuantumProfiler

    n = 1024
    x = jnp.ones((n, n), jnp.bfloat16)

    @jax.jit
    def mm(a):
        for _ in range(8):
            a = (a @ a) / n
        return a

    @jax.jit
    def ew(a):
        for _ in range(60):
            a = jnp.tanh(a) + 0.1
        return a

    mm(x).block_until_ready()  # compile outside the trace
    ew(x).block_until_ready()
    prof = XlaQuantumProfiler()
    _, st_mm = prof.profile(lambda: mm(x).block_until_ready())
    _, st_ew = prof.profile(lambda: ew(x).block_until_ready())
    assert st_mm is not None and st_ew is not None, prof.last_error
    # Real-chip traces must surface device lanes, not host thunks.
    assert st_mm.source == "device", (st_mm.source, st_mm.top_ops)
    assert st_mm.n_ops > 0 and st_ew.n_ops > 0
    assert st_mm.compute_ns > 0, st_mm.top_ops
    assert st_ew.stall_frac > st_mm.stall_frac + 0.2, (
        st_mm.top_ops, st_ew.top_ops)


def test_pallas_train_step_compiled():
    """attn_impl='pallas' through a full fwd+bwd+AdamW train step on
    the chip (tiny model, one step): the lowered step carries the
    Mosaic custom call, and its loss matches the xla path's on the
    same params and batch. (chip_smoke.py repeats this at full width.)"""
    import dataclasses

    from pbs_tpu.models import flagship_config, init_params, make_train_step

    base = dataclasses.replace(flagship_config(tiny=True),
                               dtype=jnp.bfloat16)
    params = init_params(base, jax.random.PRNGKey(0))
    toks = jax.random.randint(
        jax.random.PRNGKey(1), (2, 128), 0, base.vocab, jnp.int32)
    losses = {}
    for attn in ("xla", "pallas"):
        cfg = dataclasses.replace(base, attn_impl=attn)
        init_opt, step = make_train_step(cfg, learning_rate=1e-3)
        state = (params, jax.jit(init_opt)(params), 0)
        jstep = jax.jit(step)
        if attn == "pallas":
            assert "tpu_custom_call" in jstep.lower(state, toks).as_text()
        _, m = jstep(state, toks)
        losses[attn] = float(m["loss"])
    assert np.isfinite(losses["pallas"])
    assert abs(losses["pallas"] - losses["xla"]) < 5e-3 * max(
        1.0, abs(losses["xla"]))


def test_bf16_moments_train_step_compiled():
    """mu_dtype=bf16 (the optimizer-HBM lever, models.default_optimizer)
    through a full train step on the chip: the moment cast-in/cast-out
    must survive the TPU lowering with donation, and the stored moments
    must stay bf16 on device."""
    import dataclasses

    import optax

    from pbs_tpu.models import flagship_config, init_params, make_train_step

    cfg = dataclasses.replace(flagship_config(tiny=True), dtype=jnp.bfloat16)
    params = init_params(cfg, jax.random.PRNGKey(0))
    init_opt, step = make_train_step(cfg, learning_rate=1e-3,
                                     mu_dtype=jnp.bfloat16)
    state = (params, jax.jit(init_opt)(params), 0)
    toks = jax.random.randint(
        jax.random.PRNGKey(1), (2, 128), 0, cfg.vocab, jnp.int32)
    jstep = jax.jit(step, donate_argnums=(0,))
    for _ in range(3):
        state, m = jstep(state, toks)
    assert np.isfinite(float(m["loss"]))
    adam = [s for s in jax.tree_util.tree_leaves(
                state[1], is_leaf=lambda x: isinstance(
                    x, optax.ScaleByAdamState))
            if isinstance(s, optax.ScaleByAdamState)][0]
    assert jax.tree_util.tree_leaves(adam.nu)[0].dtype == jnp.bfloat16


def test_dropless_moe_serving_on_chip():
    """The dropless router (capacity = group tokens) and the slot
    engine's MoE seam through the real TPU lowering: a small MoE
    target serves a prompt end to end with zero drops, token-identical
    to the lockstep MoE generate loop. (CI proves the parity in
    interpreter/CPU mode; this proves the dispatch einsums and the
    engine's jitted programs compile and agree ON CHIP.)"""
    from pbs_tpu.models import (
        ContinuousBatcher,
        MoEConfig,
        init_moe_params,
        make_moe_generate,
    )
    from pbs_tpu.models.moe import moe_slot_mlp

    mcfg = MoEConfig(
        vocab=256, d_model=256, n_layers=2, n_heads=8, n_kv_heads=4,
        d_ff=512, max_seq=128, dtype=jnp.bfloat16, n_experts=4,
        top_k=2, dropless=True)
    mparams = init_moe_params(mcfg, jax.random.PRNGKey(0))
    prompt = jnp.asarray([[5, 6, 7, 8]], jnp.int32)
    ref, _ = jax.jit(make_moe_generate(mcfg, 8, temperature=0.0))(
        mparams, prompt, jax.random.PRNGKey(9))
    ref_toks = [int(t) for t in np.asarray(ref)[0]]

    eng = ContinuousBatcher(mcfg, mparams, n_slots=2, prompt_bucket=4,
                            max_len=32, mlp_fn=moe_slot_mlp(mcfg))
    eng.submit([5, 6, 7, 8], max_new_tokens=8)
    got = None
    for _ in range(100):
        for c in eng.step():
            got = [int(t) for t in c.tokens]
        if not eng.has_work():
            break
    assert got == ref_toks, (got, ref_toks)
    assert eng.stats()["mlp_extra_mean"] == 0.0  # provably dropless


def test_chunked_ce_train_step_compiled():
    """loss_chunks (the logits-never-materialize loss tail) through the
    TPU lowering: scan-of-checkpoint over head chunks, one train step,
    loss matches the materialized path on chip."""
    import dataclasses

    from pbs_tpu.models import flagship_config, init_params, make_train_step

    base = dataclasses.replace(flagship_config(tiny=True), dtype=jnp.bfloat16)
    chunked = dataclasses.replace(base, loss_chunks=4)
    params = init_params(base, jax.random.PRNGKey(0))
    toks = jax.random.randint(
        jax.random.PRNGKey(1), (2, 128), 0, base.vocab, jnp.int32)
    losses = {}
    for name, cfg in (("mat", base), ("chunk", chunked)):
        init_opt, step = make_train_step(cfg, learning_rate=1e-3,
                                         full_seq=True)
        state = (params, jax.jit(init_opt)(params), 0)
        _, m = jax.jit(step)(state, toks)
        losses[name] = float(m["loss"])
    assert np.isfinite(losses["chunk"])
    assert abs(losses["chunk"] - losses["mat"]) < 5e-3 * max(
        1.0, abs(losses["mat"]))


def _bf16_params(cfg, seed=0):
    """``init_params``' distribution made in bf16 on the device, a layer
    at a time: the fp32 tree of a 7B cut does not fit beside its cast."""
    from pbs_tpu.models import init_params

    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def make(n, name, shape):
        if name.endswith("norm"):
            return jnp.ones(shape, jnp.bfloat16)
        scale = float((np.sqrt(cfg.d_model) if name == "embed" else 1.0)
                      / np.sqrt(shape[-2]))  # a Python float: stays bf16
        draw = lambda k: jax.random.normal(  # noqa: E731
            k, shape[-2:], jnp.bfloat16) * scale
        key = jax.random.fold_in(jax.random.PRNGKey(seed), n)
        if len(shape) == 2:
            return jax.jit(draw)(key)
        return jax.jit(lambda k: jax.lax.map(  # stacked over layers
            draw, jax.random.split(k, shape[0])))(key)

    return jax.tree_util.tree_unflatten(treedef, [
        make(n, path[-1].key, leaf.shape)
        for n, (path, leaf) in enumerate(leaves)])


def test_slot_cache_updated_in_place_at_the_mistral_cell():
    """The engine's programs at the benchmark's mistral cell (26
    layers, 16 slots x 1024, bucket 512): the compiler aliases the
    whole donated cache and what a call needs beside its arguments is
    under 0.3 GiB (a rebuilt cache is 1.6 GiB more). Then one request
    for 8 ticks: every served token is the best, to bf16's grain, of a
    plain full-sequence forward of the same weights that has no cache
    at all (the training forward, over prompt + served tokens)."""
    from pbs_tpu.models import ContinuousBatcher, TransformerConfig
    from pbs_tpu.models.transformer import forward

    cfg = TransformerConfig(
        vocab=32768, d_model=4096, n_layers=26, n_heads=32, n_kv_heads=8,
        d_ff=14336, max_seq=1024, rope_theta=1e6, dtype=jnp.bfloat16)
    slots, bucket = 16, 512
    params = _bf16_params(cfg)
    eng = ContinuousBatcher(cfg, params, n_slots=slots,
                            prompt_bucket=bucket, max_len=cfg.max_seq)
    cache_bytes = sum(eng.cache[x].nbytes for x in ("k", "v"))
    assert cache_bytes == 2 * 26 * 16 * 1024 * 8 * 128 * 2
    key = jax.random.PRNGKey(0)
    lowered = {
        "decode": eng._decode_fn.lower(
            eng.params, eng.cache, jnp.zeros((slots,), jnp.int32),
            jnp.zeros((slots,), jnp.int32), key),
        "prefill": eng._prefill_fn.lower(
            eng.params, eng.cache, jnp.zeros((slots,), jnp.int32), 0,
            jnp.zeros((bucket,), jnp.int32), 1, key),
    }
    for name, low in lowered.items():
        m = low.compile().memory_analysis()
        transient = (m.temp_size_in_bytes + m.output_size_in_bytes
                     - m.alias_size_in_bytes)
        print(f"{name}: alias {m.alias_size_in_bytes / 2**30:.3f} GiB, "
              f"temp + output - alias {transient / 2**30:.3f} GiB")
        assert m.alias_size_in_bytes >= cache_bytes, name
        assert transient < 0.3 * 2**30, name

    prompt = [int(t) for t in np.random.default_rng(0).integers(
        1, cfg.vocab, 12)]
    eng.submit(prompt, max_new_tokens=9)  # 1 at prefill + 8 decode ticks
    served = None
    while eng.has_work():
        for c in eng.step():
            served = [int(t) for t in c.tokens]
    assert len(served) == 9 and eng.steps >= 8
    seq = jnp.asarray([prompt + served], jnp.int32)
    logits = np.asarray(jax.jit(
        lambda p, t: forward(cfg, p, t))(eng.params, seq))[0]
    at = np.arange(len(prompt) - 1, len(prompt) + len(served) - 1)
    gap = logits[at].max(axis=-1) - logits[at, served]
    print("served-token gap to the full forward's best:", gap.round(4))
    # A wrong token reads ~4 here (random weights); bf16 rounding of
    # two unlike summation orders, under 0.1.
    assert gap.max() < 0.25, gap


def test_decode_reads_attention_projections_where_they_lie_in_the_stack():
    """The engine's decode at mistral's widths (4 layers of them): no
    instruction of the compiled program writes a tensor the size of one
    layer's wq, wk, wv or wo, in any arrangement of its axes. Given a
    reshape to heads straight behind the q, k and v products, XLA:TPU
    moves it onto the weight, slices that out of the (L, ...) stack and
    copies it transposed in every layer of every tick: 3.2 ms of
    mistral's 22.2 (PERF.md section 6, PR 32; the same compile without
    the chip is tests/test_tpu_compile.py). A CPU compile has no such
    copy to show."""
    from pbs_tpu.models import ContinuousBatcher, TransformerConfig
    from pbs_tpu.telemetry.hlo import materialised, written

    cfg = TransformerConfig(
        vocab=32768, d_model=4096, n_layers=4, n_heads=32, n_kv_heads=8,
        d_ff=14336, max_seq=256, rope_theta=1e6, dtype=jnp.bfloat16)
    slots = 16
    eng = ContinuousBatcher(cfg, _bf16_params(cfg), n_slots=slots,
                            prompt_bucket=64, max_len=cfg.max_seq)
    ops = materialised(eng._decode_fn.lower(
        eng.params, eng.cache, jnp.zeros((slots,), jnp.int32),
        jnp.zeros((slots,), jnp.int32),
        jax.random.PRNGKey(0)).compile().as_text())
    # The scan is there and the reading sees into it: the MLP's
    # products write (slots, d_ff) from inside the loop.
    assert any(shape == f"bf16[{slots},{cfg.d_ff}]" for _, _, shape in ops)
    d, hd = cfg.d_model, cfg.head_dim
    weightlike = set()  # (d, H * hd) and (d, H, hd), axes in any order
    for heads in (cfg.n_heads, cfg.n_kv_heads):
        weightlike |= {tuple(sorted((d, heads * hd))),
                       tuple(sorted((d, heads, hd)))}
    moved = written(ops, weightlike)
    assert not moved, moved


def test_kda_state_step_compiled_at_the_cell():
    """The one-pass recurrent step (``ops/kda_step.py``) compiled
    through Mosaic at the KDA cell's size, a layer's (256, 64, 128,
    128) float32 state: eight sampled tiles against the delta rule in
    float64 on the host (1e-5 of the tile's largest entry: float32
    products, whatever unit makes them), the idle lanes' state bit for
    bit; then three layers of it, donated, 250 of 256 lanes active,
    timed beside the ``jax.numpy`` step XLA makes two passes of (PERF.md
    section 6, PR 36)."""
    import time

    from pbs_tpu.models.kda import state_step
    from pbs_tpu.ops.kda_step import kda_state_step

    B, H, hd, layers = 256, 64, 128, 3
    f32 = jnp.float32

    def inputs(seed):
        ks = jax.random.split(jax.random.PRNGKey(seed), 5)
        q, k, v = (jax.random.normal(kk, (B, H, hd), f32) for kk in ks[:3])
        unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa
        g = -jnp.exp(jax.random.uniform(ks[3], (B, H, hd), f32,
                                        np.log(1e-3), np.log(30.0)))
        return (jnp.exp(g), unit(k), unit(q) * hd ** -0.5, v,
                jax.random.uniform(ks[4], (B, H), f32, 0.0, 2.0))

    def fresh(seed):
        return 0.5 * jax.random.normal(jax.random.PRNGKey(seed),
                                       (B, H, hd, hd), f32)

    active = jnp.arange(B) % 43 != 7                # 250 of 256
    idle = np.flatnonzero(~np.asarray(active))
    state, ins = fresh(1), inputs(2)
    o, new = jax.jit(kda_state_step)(state, *ins, active)
    assert bool(jnp.array_equal(
        jax.lax.bitcast_convert_type(new[idle], jnp.uint32),
        jax.lax.bitcast_convert_type(state[idle], jnp.uint32)))
    rng = np.random.default_rng(0)
    live = np.flatnonzero(np.asarray(active))
    worst = 0.0
    for b, h in zip(rng.choice(live, 8), rng.integers(0, H, 8)):
        alpha, k, q, v = (np.asarray(t[b, h], np.float64) for t in ins[:4])
        s = np.asarray(state[b, h], np.float64) * alpha[:, None]
        s = s + float(ins[4][b, h]) * np.outer(k, v - s.T @ k)
        for got, want in ((new[b, h], s), (o[b, h], s.T @ q)):
            gap = np.abs(np.asarray(got, np.float64) - want).max() \
                / np.abs(want).max()
            worst = max(worst, float(gap))
    print(f"kda_state_step against float64, eight tiles: {worst:.2e}")
    assert worst < 1e-5, worst
    del state, new, o

    def three(step):
        def run(states, ins, active):
            outs = [step(s, *i, active) for s, i in zip(states, ins)]
            return [o for o, _ in outs], [s for _, s in outs]
        return jax.jit(run, donate_argnums=(0,))

    ins = [inputs(10 + layer) for layer in range(layers)]
    ms = {}
    for name, step in (("kda_state_step", kda_state_step),
                       ("jax.numpy step", state_step)):
        fn = three(step)
        states = [fresh(20 + layer) for layer in range(layers)]
        _, states = fn(states, ins, active)          # compile, warm
        jax.block_until_ready(states)
        t0 = time.perf_counter()
        for _ in range(20):
            _, states = fn(states, ins, active)
        jax.block_until_ready(states)
        ms[name] = (time.perf_counter() - t0) / 20 * 1e3
        del states
    print("three layers' recurrent step at (256, 64, 128, 128), ms a call: "
          + ", ".join(f"{n} {t:.2f}" for n, t in ms.items()))
    assert ms["kda_state_step"] < ms["jax.numpy step"]


def test_mamba_prompt_scan_compiled_at_the_cell():
    """The one-pass prompt scan (``ops/mamba_scan.py``) compiled through
    Mosaic at the state-space cell's size, a layer's 2048 positions of
    5120 channels and 16 states, the last 500 of them padding (``dt``
    0): sixteen sampled channels against the recurrence in float64 on
    the host (1e-4 of the largest entry, and the ``jax.numpy`` scan
    beside it: a slow channel's state is a product of a thousand of the
    chip's exponentials, each a few float32 roundings off, and both
    read 2-3e-5 where one step reads 1e-7), the rows of the blocks
    behind the prompt zeros, and the state and the real rows the same
    bits at the 2048 rung as the first 1548 positions leave at a rung
    of their own; then both rungs timed beside the ``jax.numpy`` scan
    XLA makes two loops of (PERF.md section 6, PR 38)."""
    import time

    from pbs_tpu.models.mamba import mamba_scan
    from pbs_tpu.ops.mamba_scan import BLOCK, mamba_prompt_scan

    S, C, N, plen = 2048, 5120, 16, 1548
    f32 = jnp.float32
    ks = jax.random.split(jax.random.PRNGKey(38), 4)
    x = jax.random.normal(ks[0], (S, C), f32)
    bm, cm = (jax.random.normal(k, (S, N), f32) for k in ks[1:3])
    dt = jnp.exp(jax.random.uniform(ks[3], (S, C), f32, np.log(1e-3),
                                    np.log(1e-1)))
    dt = jnp.where(jnp.arange(S)[:, None] < plen, dt, 0.0)
    a_log = jnp.log(jnp.broadcast_to(
        jnp.arange(1, N + 1, dtype=f32)[:, None], (N, C)))
    kernel = jax.jit(mamba_prompt_scan)
    y, h = kernel(x, dt, bm, cm, a_log, jnp.int32(plen))
    behind = -(-plen // BLOCK) * BLOCK
    assert not bool(y[behind:].any())
    short = -(-plen // BLOCK) * BLOCK + BLOCK       # a rung of its own
    y2, h2 = kernel(x[:short], dt[:short], bm[:short], cm[:short], a_log,
                    jnp.int32(plen))
    as_bits = lambda t: jax.lax.bitcast_convert_type(t, jnp.uint32)  # noqa
    assert bool(jnp.array_equal(as_bits(h), as_bits(h2)))
    assert bool(jnp.array_equal(as_bits(y[:plen]), as_bits(y2[:plen])))
    cols = np.random.default_rng(0).choice(C, 16, replace=False)
    xs, dts = (np.asarray(t, np.float64)[:, cols] for t in (x, dt))
    A = -np.exp(np.asarray(a_log, np.float64))[:, cols]
    b64, c64 = np.asarray(bm, np.float64), np.asarray(cm, np.float64)
    state, want = np.zeros((N, 16)), np.zeros((plen, 16))
    for t in range(plen):
        state = np.exp(dts[t][None] * A) * state \
            + (dts[t] * xs[t])[None] * b64[t][:, None]
        want[t] = (state * c64[t][:, None]).sum(0)
    y_np, h_np = jax.jit(mamba_scan)(x, dt, bm, cm, a_log)
    gaps = {}
    for name, ys, hs in (("mamba_prompt_scan", y, h),
                         ("jax.numpy scan", y_np, h_np)):
        gaps[name] = (
            float(np.abs(np.asarray(ys, np.float64)[:plen, cols]
                         - want).max() / np.abs(want).max()),
            float(np.abs(np.asarray(hs, np.float64)[:, cols]
                         - state).max() / np.abs(state).max()))
    print("against float64, sixteen channels, (y, state): " + ", ".join(
        f"{n} ({a:.2e}, {b:.2e})" for n, (a, b) in gaps.items()))
    assert max(gaps["mamba_prompt_scan"]) < 1e-4, gaps

    ms = {}
    for rows in (2048, 1024):
        real = jnp.int32(min(plen, rows))
        args = (x[:rows], dt[:rows], bm[:rows], cm[:rows], a_log)
        for name, fn, extra in (("mamba_prompt_scan", kernel, (real,)),
                                ("jax.numpy scan", jax.jit(mamba_scan), ())):
            jax.block_until_ready(fn(*args, *extra))     # compile, warm
            t0 = time.perf_counter()
            for _ in range(20):
                out = fn(*args, *extra)
            jax.block_until_ready(out)
            ms[name, rows] = (time.perf_counter() - t0) / 20 * 1e3
    print("a layer's prompt scan at (rows, 5120) x 16 states, ms a call: "
          + ", ".join(f"{n} at {r} {t:.3f}" for (n, r), t in ms.items()))
    for rows in (2048, 1024):
        assert ms["mamba_prompt_scan", rows] < ms["jax.numpy scan", rows]


def test_mla_attend_compiled_at_the_cell():
    """The one-pass latent attention of the decode tick
    (``ops/mla_attend.py``) compiled through Mosaic at the selecting
    cell's size, a layer's 64 lanes of 10,240 kept positions (latent
    512, rotary key 64, bfloat16) under 64 heads, cursors drawn
    3,072-9,700 and two lanes at rest, the choice ``decode_choice``'s
    over seeded indexer scores: eight sampled (lane, head) rows against
    the softmax in float64 on the host over the same bfloat16 rows
    (2^-6 of the lane's largest entry: the probabilities go to the
    values' product in bfloat16, in both forms), the ``jax.numpy`` form
    beside it; the blocks past every cursor poisoned with NaN, which
    the kernel never reads; then five layers of it timed beside the four
    fusions XLA makes of the ``jax.numpy`` form (PERF.md section 6, PR
    42)."""
    import time

    from pbs_tpu.models.mla import attend_rows, top_mask
    from pbs_tpu.ops.mla_attend import attend_block, mla_attend

    B, H, T, R, E, topk, layers = 64, 64, 10240, 512, 64, 2048, 5
    bf16, scale = jnp.bfloat16, 1.0 / 16.0
    tk = attend_block(T)
    rng = np.random.default_rng(42)
    cursors = rng.integers(3072, 9700, B)
    cursors[[5, 40]] = 0                                  # lanes at rest
    row_pos = jnp.asarray(cursors, jnp.int32)

    def layer(seed):
        ks = jax.random.split(jax.random.PRNGKey(seed), 5)
        q_lat, q_r, ckv, kr = (
            jax.random.normal(k, s, bf16) for k, s in zip(
                ks, ((B, H, R), (B, H, E), (B, T, R), (B, T, E))))
        live = jnp.arange(T)[None, :] <= row_pos[:, None]
        index = jax.random.normal(ks[4], (B, T), jnp.float32)
        chosen = top_mask(jnp.where(live, index, -jnp.inf), topk) & live
        return q_lat * 0.05, q_r, ckv, kr, chosen

    kernel = jax.jit(lambda *a: mla_attend(*a, scale=scale))
    numpy_way = jax.jit(lambda *a: attend_rows(*a, scale=scale))
    args = layer(1)
    got, ref = kernel(*args, row_pos), numpy_way(*args)
    worst = {"mla_attend": 0.0, "jax.numpy form": 0.0}
    for b, h in zip(rng.integers(0, B, 8), rng.integers(0, H, 8)):
        q_lat, q_r, ckv, kr, chosen = (
            np.asarray(t[b], np.float64) for t in args)
        keep = chosen > 0
        s = (ckv[keep] @ q_lat[h] + kr[keep] @ q_r[h]) * scale
        p = np.exp(s - s.max())
        want = (p / p.sum()) @ ckv[keep]
        for name, out in (("mla_attend", got), ("jax.numpy form", ref)):
            gap = np.abs(np.asarray(out[b, h], np.float64) - want).max() \
                / np.abs(want).max()
            worst[name] = max(worst[name], float(gap))
    print("against float64, eight (lane, head) rows: " + ", ".join(
        f"{n} {g:.2e}" for n, g in worst.items()))
    assert worst["mla_attend"] < 2 ** -6, worst
    dead = (jnp.arange(T)[None, :] // tk > row_pos[:, None] // tk)[..., None]
    poisoned = kernel(args[0], args[1], jnp.where(dead, jnp.nan, args[2]),
                      jnp.where(dead, jnp.nan, args[3]), args[4], row_pos)
    assert bool(jnp.array_equal(poisoned, got))
    del got, ref, poisoned, args, q_lat, q_r, ckv, kr, chosen

    def five(attend):
        return jax.jit(lambda ls, pos: [attend(*l, pos, scale=scale)
                                        for l in ls])

    ls = [layer(10 + i) for i in range(layers)]
    ms = {}
    for name, attend in (
            ("mla_attend", mla_attend),
            ("jax.numpy form", lambda *a, scale: attend_rows(
                *a[:-1], scale=scale))):
        fn = five(attend)
        jax.block_until_ready(fn(ls, row_pos))           # compile, warm
        t0 = time.perf_counter()
        for _ in range(20):
            out = fn(ls, row_pos)
        jax.block_until_ready(out)
        ms[name] = (time.perf_counter() - t0) / 20 * 1e3
    blocks = int((cursors // tk + 1).sum())
    print(f"five layers' latent attention at (64, 10240, 512 + 64), "
          f"{blocks} of {B * T // tk} blocks of {tk} live, ms a call: "
          + ", ".join(f"{n} {t:.2f}" for n, t in ms.items()))
    assert ms["mla_attend"] < ms["jax.numpy form"]


def test_mla_attend_window_compiled_at_the_drafting_cell():
    """The one-pass latent attention of a drafting tick's verify window
    (``ops/mla_attend.py::mla_attend_window``) compiled through Mosaic
    at the drafting cell's size: a layer's 128 lanes of 2,560 kept
    positions (latent 512, rotary key 64, bfloat16) under 128 heads,
    **two queries a lane** (256 rows of one product, the second seeing
    the row the first wrote), cursors drawn 64-2,500 (some a row short
    of a block's end, so that the window straddles two blocks) and two
    lanes at rest, no choice: against ``attend_rows`` under the causal
    mask (``mla.window_rows``, the ``jax.numpy`` form a CPU runs) and,
    for eight sampled (lane, query, head) rows, against the softmax in
    float64 on the host over the same bfloat16 rows (2^-6 of the row's
    largest entry); the blocks past every window poisoned with NaN,
    which the kernel never reads; then six layers of it timed beside
    the ``jax.numpy`` form."""
    import time

    from pbs_tpu.models.mla import window_rows
    from pbs_tpu.ops.mla_attend import attend_block, mla_attend_window

    B, S, H, T, R, E, layers = 128, 2, 128, 2560, 512, 64, 6
    bf16 = jnp.bfloat16
    scale = 1.3689 ** 2 / np.sqrt(192.0)
    tk = attend_block(T)
    assert tk == 512
    rng = np.random.default_rng(50)
    cursors = rng.integers(64, 2500, B)
    cursors[[3, 77]] = (tk - 1, 3 * tk - 1)     # the window straddles
    cursors[[9, 100]] = 0                       # lanes at rest
    row_pos = jnp.asarray(cursors, jnp.int32)

    def layer(seed):
        ks = jax.random.split(jax.random.PRNGKey(seed), 4)
        q_lat, q_r, ckv, kr = (
            jax.random.normal(k, s, bf16) for k, s in zip(
                ks, ((B, S, H, R), (B, S, H, E), (B, T, R), (B, T, E))))
        return q_lat * 0.05, q_r, ckv, kr

    kernel = jax.jit(lambda *a: mla_attend_window(*a, scale=scale))
    numpy_way = jax.jit(lambda *a: window_rows(*a, scale=scale))
    args = layer(1)
    got, ref = kernel(*args, row_pos), numpy_way(*args, row_pos)
    gap = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                - ref.astype(jnp.float32)))
                / jnp.max(jnp.abs(ref.astype(jnp.float32))))
    worst = {"mla_attend_window": 0.0, "jax.numpy form": 0.0}
    for b, q, h in zip(rng.integers(0, B, 8), rng.integers(0, S, 8),
                       rng.integers(0, H, 8)):
        q_lat, q_r, ckv, kr = (np.asarray(t[b], np.float64) for t in args)
        n = cursors[b] + q + 1
        s = (ckv[:n] @ q_lat[q, h] + kr[:n] @ q_r[q, h]) * scale
        p = np.exp(s - s.max())
        want = (p / p.sum()) @ ckv[:n]
        for name, out in (("mla_attend_window", got),
                          ("jax.numpy form", ref)):
            err = np.abs(np.asarray(out[b, q, h], np.float64) - want).max() \
                / np.abs(want).max()
            worst[name] = max(worst[name], float(err))
    print(f"window kernel against attend_rows {gap:.2e}; against float64, "
          "eight (lane, query, head) rows: " + ", ".join(
              f"{n} {g:.2e}" for n, g in worst.items()))
    assert gap < 2 ** -5 and worst["mla_attend_window"] < 2 ** -6, worst
    dead = (jnp.arange(T)[None, :] // tk
            > (row_pos[:, None] + S - 1) // tk)[..., None]
    poisoned = kernel(args[0], args[1], jnp.where(dead, jnp.nan, args[2]),
                      jnp.where(dead, jnp.nan, args[3]), row_pos)
    assert bool(jnp.array_equal(poisoned, got))
    del got, ref, poisoned, args, q_lat, q_r, ckv, kr

    def six(attend):
        return jax.jit(lambda ls, pos: [attend(*l, pos, scale=scale)
                                        for l in ls])

    ls = [layer(10 + i) for i in range(layers)]
    ms = {}
    for name, attend in (("mla_attend_window", mla_attend_window),
                         ("jax.numpy form", window_rows)):
        fn = six(attend)
        jax.block_until_ready(fn(ls, row_pos))           # compile, warm
        t0 = time.perf_counter()
        for _ in range(20):
            out = fn(ls, row_pos)
        jax.block_until_ready(out)
        ms[name] = (time.perf_counter() - t0) / 20 * 1e3
    blocks = int(((cursors + S - 1) // tk + 1).sum())
    rows = int((cursors + S).sum())
    print(f"six layers' window attention at (128 x 2, 2560, 512 + 64), "
          f"{blocks} of {B * T // tk} blocks of {tk} live ({rows} rows), "
          f"ms a call: " + ", ".join(f"{n} {t:.2f}" for n, t in ms.items()))
    assert ms["mla_attend_window"] < ms["jax.numpy form"]


def test_mla_ingest_attend_compiled_at_the_cell():
    """The one-pass latent attention of a prompt's ingestion
    (``ops/mla_ingest_attend.py``) compiled through Mosaic at the
    selecting cell's size: a block of 256 queries of 64 heads (192 + 64
    and 256 wide, bfloat16) that ends an 8,192-row prompt, against all
    8,192 keys under a seeded choice of 2,048 a query
    (``top_mask``'s): the worst gap to the ``jax.numpy`` form
    (``_attend_chunks``) over the whole block under 2^-6 of its largest
    entry (the probabilities go to the values' product in bfloat16, in
    both forms, against another maximum); a block in mid-prompt with
    the key blocks past its own end poisoned with NaN, which the kernel
    never reads; then the pass timed beside the four chunks of fusions
    XLA makes of the ``jax.numpy`` form (PERF.md section 6, PR 48)."""
    import time

    from pbs_tpu.models.mla import _attend_chunks, top_mask
    from pbs_tpu.ops.mla_ingest_attend import ingest_attend, ingest_block

    H, Q, S, D, V, topk = 64, 256, 8192, 256, 256, 2048
    bf16, scale = jnp.bfloat16, 1.0 / 16.0
    tk = ingest_block(S)
    ks = jax.random.split(jax.random.PRNGKey(48), 4)
    q = jax.random.normal(ks[0], (H, S, D), bf16)
    k = jax.random.normal(ks[1], (H, S, D), bf16)
    v = jax.random.normal(ks[2], (H, S, V), bf16)
    index = jax.random.normal(ks[3], (Q, S), jnp.float32)

    def block(first):
        seen = jnp.arange(S)[None, :] <= first + jnp.arange(Q)[:, None]
        return (jax.lax.dynamic_slice_in_dim(q, first, Q, 1), k, v,
                top_mask(jnp.where(seen, index, -jnp.inf), topk) & seen)

    kernel = jax.jit(lambda *a: ingest_attend(*a, scale=scale))
    numpy_way = jax.jit(lambda *a: _attend_chunks(*a, scale, bf16))
    args = block(S - Q)
    assert int(args[3].sum(-1).min()) == topk
    got, ref = (np.asarray(t, np.float32)
                for t in (kernel(*args, S - Q), numpy_way(*args)))
    gap = float(np.abs(got - ref).max() / np.abs(ref).max())
    print(f"a block of 256 queries over 8192 keys, worst gap to the "
          f"jax.numpy form {gap:.2e}")
    assert np.isfinite(got).all() and gap < 2 ** -6, gap
    first = 3072 + Q
    mid = block(first)
    dead = (jnp.arange(S) // tk > (first + Q - 1) // tk)[None, :, None]
    assert bool(dead.any())
    clean = kernel(*mid, first)
    poisoned = kernel(mid[0], jnp.where(dead, jnp.nan, k),
                      jnp.where(dead, jnp.nan, v), mid[3], first)
    assert bool(jnp.isfinite(clean.astype(jnp.float32)).all())
    assert bool(jnp.array_equal(poisoned, clean))

    ms = {}
    for name, fn in (("mla_ingest_attend", lambda: kernel(*args, S - Q)),
                     ("jax.numpy form", lambda: numpy_way(*args))):
        jax.block_until_ready(fn())
        t0 = time.perf_counter()
        for _ in range(20):
            out = fn()
        jax.block_until_ready(out)
        ms[name] = (time.perf_counter() - t0) / 20 * 1e3
    print(f"a (block of 256 queries, 8192 keys) pass over 64 heads, "
          f"{S // tk} key blocks of {tk}, ms: "
          + ", ".join(f"{n} {t:.3f}" for n, t in ms.items()))
    assert ms["mla_ingest_attend"] < ms["jax.numpy form"]


# The shapes ``models/moe._sorted_rows`` meets in the benchmark's cells:
# (sorted rows, held experts, experts a token can choose among that are
# held out of how many, hidden, expert width, the MLP's form).
GROUPED_SHAPES = {
    "nemotron forward 1024": (6144, 32, 128, 2688, 1856, "relu2"),
    "nemotron forward 2048": (12288, 32, 128, 2688, 1856, "relu2"),
    "glm-5 forward piece": (16384, 16, 256, 6144, 2048, "silu"),
    "laguna forward 512": (5120, 128, 256, 3072, 1024, "silu"),
    "laguna forward 1024": (10240, 128, 256, 3072, 1024, "silu"),
    "solar forward 256, tick": (2048, 40, 320, 4096, 1280, "silu"),
    "solar forward 512": (4096, 40, 320, 4096, 1280, "silu"),
    "laguna tick": (640, 128, 256, 3072, 1024, "silu"),
}


def test_grouped_matmul_compiled_at_the_cells():
    """The one-pass grouped product (``ops/grouped_matmul.py``) compiled
    through Mosaic at each shape the expert layers meet, beside
    ``jax.lax.ragged_dot``: a share ``held / experts`` of the sorted
    rows drawn over the held experts, the rest behind them poisoned
    with NaN, which the kernel never reads. Each of an expert's two
    kinds of matrix alone (in, which XLA:TPU keeps turned where the
    width is no multiple of 128, and out) against the float64 product
    of the same bfloat16 rows over four sampled groups (2^-7 of the
    group's largest entry: the output is bfloat16); then a layer's
    products together as ``mlp_ffn`` chains them, timed in both forms
    (PERF.md section 6, PR 44: where the predicate of
    ``models/moe.grouped_kernel_takes`` comes from)."""
    import json
    import os
    import time

    from pbs_tpu.models.moe import mlp_ffn
    from pbs_tpu.ops.grouped_matmul import grouped_matmul

    bf16 = jnp.bfloat16
    rng = np.random.default_rng(44)
    table = {}
    for name, (m, groups, experts, d, f, form) in GROUPED_SHAPES.items():
        held = m * groups // experts
        sizes_h = rng.multinomial(held, np.full(groups, 1.0 / groups))
        sizes = jnp.asarray(sizes_h, jnp.int32)
        ks = jax.random.split(jax.random.PRNGKey(len(table)), 5)
        x = jax.random.normal(ks[0], (m, d), bf16)
        x = jnp.where(jnp.arange(m)[:, None] < held, x, jnp.nan)
        w1, w3 = (jax.random.normal(k, (groups, d, f), bf16) * d ** -0.5
                  for k in ks[1:3])
        w2 = jax.random.normal(ks[3], (groups, f, d), bf16) * f ** -0.5
        kernel = jax.jit(grouped_matmul)
        starts = np.concatenate([[0], np.cumsum(sizes_h)])
        mid = jnp.where(jnp.arange(m)[:, None] < held,
                        jax.random.normal(ks[4], (m, f), bf16), jnp.nan)
        worst = 0.0
        for rows, w in ((x, w1), (mid, w2)):
            got = kernel(rows, w, sizes)
            assert not bool(jnp.isnan(got[:held].astype(jnp.float32)).any())
            for e in rng.choice(np.flatnonzero(sizes_h), 4):
                lo, hi = starts[e], starts[e + 1]
                want = np.asarray(rows[lo:hi], np.float64) \
                    @ np.asarray(w[e], np.float64)
                gap = np.abs(np.asarray(got[lo:hi], np.float64)
                             - want).max() / np.abs(want).max()
                worst = max(worst, float(gap))
        assert worst < 2 ** -7, (name, worst)
        del got, mid

        ms = {}
        for way, product in (("grouped_matmul", grouped_matmul),
                             ("ragged_dot", jax.lax.ragged_dot)):
            fn = jax.jit(lambda x, w1, w3, w2, sizes, product=product:
                         mlp_ffn(x, w1, w3 if form == "silu" else None, w2,
                                 form, lambda r, w: product(r, w, sizes)))
            out = jax.block_until_ready(fn(x, w1, w3, w2, sizes))
            t0 = time.perf_counter()
            for _ in range(20):
                out = fn(x, w1, w3, w2, sizes)
            jax.block_until_ready(out)
            ms[way] = (time.perf_counter() - t0) / 20 * 1e3
        nbytes = (3 if form == "silu" else 2) * d * f * 2 * int(
            (sizes_h > 0).sum())
        table[name] = dict(
            ms, sorted_rows=m, held_rows=held, groups=groups, hidden=d,
            width=f, products=3 if form == "silu" else 2,
            touched_weight_mb=nbytes / 1e6,
            weights_at_819_gbs_ms=nbytes / 819e9 * 1e3, gap=worst)
        print(f"{name}: {m} sorted rows, {held} on {groups} held experts "
              f"of {d} x {f}, a layer's {table[name]['products']} products "
              f"ms: " + ", ".join(f"{k} {v:.3f}" for k, v in ms.items())
              + f" (the touched weights at 819 GB/s: "
              f"{table[name]['weights_at_819_gbs_ms']:.3f}); against "
              f"float64 {worst:.2e}", flush=True)
        del x, w1, w3, w2, out
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/grouped_matmul.json", "w") as fh:
        json.dump(table, fh, indent=1)
    assert table["nemotron forward 2048"]["grouped_matmul"] \
        < table["nemotron forward 2048"]["ragged_dot"]


# A layer's decode attention over K and V in the benchmark's cells:
# (lanes, query heads, KV heads, positions kept, layers stacked (0: a
# cache a layer), the cursors' range).
KV_SHAPES = {
    "mistral 16 x 1024, stacked": (16, 32, 8, 1024, 4, (128, 260)),
    "internlm2 16 x 1024, stacked": (16, 16, 8, 1024, 4, (128, 260)),
    "solar 256 x 2048": (256, 64, 8, 2048, 0, (256, 1024)),
    "laguna full 64 x 2048": (64, 48, 8, 2048, 0, (512, 1280)),
    "laguna ring 64 x 512, lapped": (64, 72, 8, 512, 0, (600, 1280)),
    "nemotron 128 x 3072": (128, 32, 2, 3072, 0, (1024, 1500)),
}


def _copy_only(k, v, row_pos, layer, tk):
    """The blocks ``kv_attend`` fetches through the same pipeline with
    no arithmetic but one add of eight rows a block: what the stream
    alone takes."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from pbs_tpu.ops.live_attend import live_block

    L, B, T, nkv, hd = k.shape
    limit = jnp.clip(row_pos, 0, T - 1)

    def body(last_ref, layer_ref, k_ref, v_ref, o_ref):
        b, j = pl.program_id(0), pl.program_id(1)

        @pl.when(j == 0)
        def _():
            o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

        @pl.when(j <= last_ref[b])
        def _():
            o_ref[0] += (k_ref[0, 0, :8].astype(jnp.float32)
                         + v_ref[0, 0, :8].astype(jnp.float32))

    def cache(b, j, last, layer):
        lane, block = live_block(b, j, last, B)
        return (layer[0], lane, block, 0)

    return pl.pallas_call(
        body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B, T // tk),
            in_specs=[pl.BlockSpec((1, 1, tk * nkv, hd), cache)] * 2,
            out_specs=pl.BlockSpec((1, 8, hd), lambda b, j, *_: (b, 0, 0))),
        out_shape=jax.ShapeDtypeStruct((B, 8, hd), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(limit // tk, jnp.asarray(layer, jnp.int32).reshape(1),
      k.reshape(L, B, T * nkv, hd), v.reshape(L, B, T * nkv, hd))


def test_kv_attend_compiled_at_the_cells():
    """The one-pass attention of the decode tick over K and V
    (``ops/kv_attend.py``) compiled through Mosaic at each cell's
    shape, cursors drawn over the cell's range and two lanes at rest:
    eight sampled (lane, head) rows against the softmax in float64 on
    the host over the same bfloat16 rows (2^-6 of the row's largest
    entry: the probabilities go to the values' product in bfloat16 in
    both forms), the ``jax.numpy`` form beside it; the blocks past
    every cursor poisoned with NaN, which the kernel never reads; then
    the layer timed at each block size beside the ``jax.numpy`` form
    (with the slice of the layer where the cache is stacked) and beside
    a kernel that only fetches the same blocks (PERF.md section 6, PR
    45)."""
    import json
    import os

    from pbs_tpu.models.slot_programs import _grouped_attention
    from pbs_tpu.ops.kv_attend import attend_block, kv_attend

    bf16, hd, reps = jnp.bfloat16, 128, 20
    rng = np.random.default_rng(45)
    table = {}

    def numpy_way(q, k, v, at, layer):
        k, v = (jax.lax.dynamic_index_in_dim(t, layer, 0, keepdims=False)
                for t in (k, v))
        seen = jnp.arange(k.shape[1])[None, :] <= at[:, None]
        return _grouped_attention(q[:, None], k, v, seen[:, None, :],
                                  bf16)[:, 0]

    def timed(attend, q, k, v, at):
        """ms a call of ``attend(q, k, v, at, layer)``, ``reps`` calls
        chained through the queries inside one program."""
        layers = k.shape[0]

        @jax.jit
        def chain(q, k, v, at):
            def one(i, q):
                out = attend(q, k, v, at, i % layers)
                return q + (out[:, :1, :1] * 0).astype(q.dtype)
            return jax.lax.fori_loop(0, reps, one, q)

        jax.block_until_ready(chain(q, k, v, at))
        import time
        t0 = time.perf_counter()
        jax.block_until_ready(chain(q, k, v, at))
        return (time.perf_counter() - t0) / reps * 1e3

    for name, (B, H, nkv, T, stack, (lo, hi)) in KV_SHAPES.items():
        L = stack or 1
        cursors = rng.integers(lo, hi, B)
        cursors[[1, B - 2]] = 0                           # lanes at rest
        at = jnp.asarray(np.minimum(cursors, T - 1), jnp.int32)
        ks = jax.random.split(jax.random.PRNGKey(len(table)), 3)
        q = jax.random.normal(ks[0], (B, H, hd), bf16)
        k = jax.random.normal(ks[1], (L, B, T, nkv, hd), bf16)
        v = jax.random.normal(ks[2], (L, B, T, nkv, hd), bf16)
        tk = attend_block(T, nkv)
        kernel = jax.jit(kv_attend, static_argnames=("block",))
        layer = jnp.int32(L - 1)
        got = kernel(q, k, v, at, layer)
        ref = jax.jit(numpy_way)(q, k, v, at, layer)
        worst = {"kv_attend": 0.0, "jax.numpy form": 0.0}
        for b, h in zip(rng.integers(0, B, 8), rng.integers(0, H, 8)):
            n, live = h // (H // nkv), int(at[b]) + 1
            keys = np.asarray(k[L - 1, b, :live, n], np.float64)
            vals = np.asarray(v[L - 1, b, :live, n], np.float64)
            s = keys @ np.asarray(q[b, h], np.float64) / np.sqrt(hd)
            p = np.exp(s - s.max())
            want = (p / p.sum()) @ vals
            for way, out in (("kv_attend", got), ("jax.numpy form", ref)):
                gap = np.abs(np.asarray(out[b, h], np.float64) - want).max() \
                    / np.abs(want).max()
                worst[way] = max(worst[way], float(gap))
        assert worst["kv_attend"] < 2 ** -6, (name, worst)
        dead = (jnp.arange(T)[None, :] // tk > at[:, None] // tk)
        dead = dead[None, :, :, None, None]
        poisoned = kernel(q, jnp.where(dead, jnp.nan, k),
                          jnp.where(dead, jnp.nan, v), at, layer)
        assert bool(jnp.array_equal(poisoned, got)), name
        del poisoned, ref, got

        ms = {"jax.numpy form": timed(numpy_way, q, k, v, at)}
        for block in (tk // 2, tk, tk * 2):
            if T % block or block % 128:
                continue
            try:
                ms[f"kv_attend {block}"] = timed(
                    lambda *a, block=block: kv_attend(*a, block=block),
                    q, k, v, at)
                ms[f"copy only {block}"] = timed(
                    lambda q, k, v, at, i, block=block: _copy_only(
                        k, v, at, i, block)[:, :1].astype(bf16),
                    q, k, v, at)
            except Exception as e:  # a block VMEM does not hold
                ms[f"kv_attend {block}"] = float("nan")
                print(f"{name}: blocks of {block}: {str(e)[:200]}")
        fetched = int((np.asarray(at) // tk + 1).sum()) * tk * nkv * hd * 4
        table[name] = dict(
            ms, block=tk, lanes=B, heads=H, kv_heads=nkv, kept=T,
            live_positions=int(np.asarray(at).sum() + B),
            fetched_mb=fetched / 1e6,
            fetched_at_819_gbs_ms=fetched / 819e9 * 1e3, gap=worst)
        print(f"{name}: {H} heads on {nkv}, block {tk}, "
              f"{fetched / 1e6:.1f} MB fetched "
              f"({fetched / 819e9 * 1e3:.3f} ms at 819 GB/s), ms a layer: "
              + ", ".join(f"{n} {t:.3f}" for n, t in ms.items())
              + "; against float64 " + ", ".join(
                  f"{n} {g:.2e}" for n, g in worst.items()), flush=True)
        if "ring" not in name:  # all of a lapped ring is live
            assert ms[f"kv_attend {tk}"] < ms["jax.numpy form"], name
        del q, k, v
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/kv_attend.json", "w") as fh:
        json.dump(table, fh, indent=1)


def test_kv_attend_reads_64_wide_heads_two_to_a_row():
    """LFM2's attention layer at the cell's shape: 256 lanes of 3,072
    positions, 32 query heads on 8 KV heads of **64**, the cache packed
    two heads to a row of 128 lanes (``slot_programs.kv_pack``: ``(256,
    3072, 4, 128)``), the queries zero outside their own head's half
    and the kernel as it stands told the head's scale. Eight sampled
    (lane, head) rows against the softmax in float64 on the host over
    the head's own 64-wide keys and values (2^-6 of the row's largest
    entry, the bound of ``test_kv_attend_compiled_at_the_cells``), the
    ``jax.numpy`` form over the packed rows beside it; the blocks past
    every cursor poisoned with NaN; then a layer timed in both forms
    (PERF.md section 6, PR 47)."""
    import json
    import os
    import time

    from pbs_tpu.models.slot_programs import (
        _from_packed, _grouped_attention, _to_packed, kv_pack)
    from pbs_tpu.ops.kv_attend import attend_block, kv_attend

    bf16, reps = jnp.bfloat16, 20
    B, H, nkv, hd, T = 256, 32, 8, 64, 3072
    pack = kv_pack(nkv, hd)
    assert pack == 2
    rows = (B, T, nkv // pack, pack * hd)
    rng = np.random.default_rng(47)
    cursors = rng.integers(200, 2400, B)
    cursors[[1, B - 2]] = 0                               # lanes at rest
    at = jnp.asarray(cursors, jnp.int32)
    ks = jax.random.split(jax.random.PRNGKey(47), 3)
    q = jax.random.normal(ks[0], (B, 1, H, hd), bf16)
    k = jax.random.normal(ks[1], (B, T, nkv, hd), bf16)
    v = jax.random.normal(ks[2], (B, T, nkv, hd), bf16)
    kp, vp = k.reshape(rows), v.reshape(rows)
    tk = attend_block(T, nkv // pack)
    assert tk == 512

    @jax.jit
    def kernel(q, kp, vp, at):
        out = kv_attend(_to_packed(q, nkv, pack)[:, 0], kp, vp, at,
                        scale=hd ** -0.5)
        return _from_packed(out[:, None], nkv, pack)[:, 0]

    @jax.jit
    def numpy_way(q, kp, vp, at):
        seen = jnp.arange(T)[None, :] <= at[:, None]
        out = _grouped_attention(_to_packed(q, nkv, pack), kp, vp,
                                 seen[:, None, :], bf16, hd)
        return _from_packed(out, nkv, pack)[:, 0]

    got, ref = kernel(q, kp, vp, at), numpy_way(q, kp, vp, at)
    worst = {"kv_attend": 0.0, "jax.numpy form": 0.0}
    for b, h in zip(rng.integers(0, B, 8), rng.integers(0, H, 8)):
        n, live = h // (H // nkv), int(at[b]) + 1
        keys = np.asarray(k[b, :live, n], np.float64)
        vals = np.asarray(v[b, :live, n], np.float64)
        s = keys @ np.asarray(q[b, 0, h], np.float64) / np.sqrt(hd)
        p = np.exp(s - s.max())
        want = (p / p.sum()) @ vals
        for way, out in (("kv_attend", got), ("jax.numpy form", ref)):
            gap = np.abs(np.asarray(out[b, h], np.float64) - want).max() \
                / np.abs(want).max()
            worst[way] = max(worst[way], float(gap))
    assert worst["kv_attend"] < 2 ** -6, worst
    dead = (jnp.arange(T)[None, :] // tk > at[:, None] // tk)[:, :, None,
                                                             None]
    poisoned = kernel(q, jnp.where(dead, jnp.nan, kp),
                      jnp.where(dead, jnp.nan, vp), at)
    assert bool(jnp.array_equal(poisoned, got))
    del poisoned

    def timed(attend):
        @jax.jit
        def chain(q, kp, vp, at):
            def one(i, q):
                out = attend(q, kp, vp, at)
                return q + (out[:, None, :, :1] * 0).astype(q.dtype)
            return jax.lax.fori_loop(0, reps, one, q)

        jax.block_until_ready(chain(q, kp, vp, at))
        t0 = time.perf_counter()
        jax.block_until_ready(chain(q, kp, vp, at))
        return (time.perf_counter() - t0) / reps * 1e3

    ms = {"kv_attend 512": timed(kernel), "jax.numpy form": timed(numpy_way)}
    fetched = int((cursors // tk + 1).sum()) * tk * nkv * hd * 4
    table = dict(ms, lanes=B, heads=H, kv_heads=nkv, head_dim=hd, kept=T,
                 live_positions=int(cursors.sum() + B),
                 fetched_mb=fetched / 1e6,
                 fetched_at_819_gbs_ms=fetched / 819e9 * 1e3, gap=worst)
    print(f"lfm2 256 x 3072, 32 heads on 8 of 64, two to a row: "
          f"{fetched / 1e6:.1f} MB fetched "
          f"({fetched / 819e9 * 1e3:.3f} ms at 819 GB/s), ms a layer: "
          + ", ".join(f"{n} {t:.3f}" for n, t in ms.items())
          + "; against float64 " + ", ".join(
              f"{n} {g:.2e}" for n, g in worst.items()), flush=True)
    assert ms["kv_attend 512"] < ms["jax.numpy form"]
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/kv_attend_packed.json", "w") as fh:
        json.dump(table, fh, indent=1)
