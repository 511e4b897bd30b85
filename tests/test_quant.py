"""Weight-only int8 serving quantization: accuracy, memory, and the
serving engines consuming quantized trees unchanged."""

import jax
import jax.numpy as jnp
import numpy as np

from pbs_tpu.models import init_params, make_generate, prefill
from pbs_tpu.models.generate import init_cache
from pbs_tpu.models.quant import (
    quantize_weights,
    quantized_nbytes,
    wload,
)
from pbs_tpu.models.transformer import TransformerConfig

CFG = TransformerConfig(
    vocab=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
    d_ff=128, max_seq=128, dtype=jnp.float32)


def _params():
    return init_params(CFG, jax.random.PRNGKey(0))


def test_quant_roundtrip_error_small():
    params = _params()
    qp = quantize_weights(params)
    w = params["layers"]["wq"]
    wq = wload(qp["layers"]["wq"], jnp.float32)
    rel = float(jnp.max(jnp.abs(w - wq))) / float(jnp.max(jnp.abs(w)))
    assert rel < 0.02, rel  # int8 per-channel: <2% of the channel max


def test_quant_memory_halves():
    params = _params()
    qp = quantize_weights(params)
    # fp32 masters -> int8 + fp32 scales: ~4x smaller; even vs a bf16
    # serving copy it must be well under 60%.
    assert quantized_nbytes(qp) < 0.3 * quantized_nbytes(params)
    # Norm vectors survive unquantized.
    assert qp["layers"]["attn_norm"].dtype == jnp.float32


def test_quant_prefill_logits_close():
    params = _params()
    qp = quantize_weights(params)
    prompt = jax.random.randint(
        jax.random.PRNGKey(1), (2, 32), 0, CFG.vocab, jnp.int32)
    lf, _ = prefill(CFG, params, prompt, init_cache(CFG, 2, 64))
    lq, _ = prefill(CFG, qp, prompt, init_cache(CFG, 2, 64))
    # Logit perturbation stays small relative to the logit scale.
    scale = float(jnp.std(lf))
    err = float(jnp.max(jnp.abs(lf - lq))) / scale
    assert err < 0.35, err


def test_quant_generate_runs_and_mostly_agrees():
    """Greedy decode from the quantized tree: same API, and the token
    stream stays close to fp (identical first tokens; int8 noise may
    fork the tail, which is expected behavior, not an error)."""
    params = _params()
    qp = quantize_weights(params)
    prompt = jax.random.randint(
        jax.random.PRNGKey(2), (2, 16), 0, CFG.vocab, jnp.int32)
    gen = jax.jit(make_generate(CFG, max_new_tokens=8, temperature=0.0))
    tf = np.asarray(gen(params, prompt, jax.random.PRNGKey(3)))
    tq = np.asarray(gen(qp, prompt, jax.random.PRNGKey(3)))
    assert tf.shape == tq.shape == (2, 8)
    assert (tf[:, 0] == tq[:, 0]).all()  # first token robust to int8


def test_quant_continuous_batcher():
    """The slot engine serves from a quantized tree unchanged."""
    from pbs_tpu.models.serving import ContinuousBatcher

    qp = quantize_weights(_params())
    eng = ContinuousBatcher(CFG, qp, n_slots=2, prompt_bucket=8,
                            max_len=32)
    rid = eng.submit([1, 2, 3], max_new_tokens=4)
    done = []
    for _ in range(20):
        done += eng.step()
        if done:
            break
    assert done and done[0].request_id == rid
    assert len(done[0].tokens) == 4


def test_quant_moe_generate():
    """Quantized MoE tree through the cached MoE decode path (router
    stays fp32 by design; experts are int8)."""
    from pbs_tpu.models import MoEConfig, init_moe_params, make_moe_generate

    mcfg = MoEConfig(
        vocab=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=64, max_seq=64, dtype=jnp.float32, n_experts=4, top_k=2)
    mp = init_moe_params(mcfg, jax.random.PRNGKey(0))
    qp = quantize_weights(mp)
    assert isinstance(qp["layers"]["we1"], dict)
    assert not isinstance(qp["layers"]["router"], dict)  # router fp32
    prompt = jax.random.randint(
        jax.random.PRNGKey(1), (2, 8), 0, mcfg.vocab, jnp.int32)
    gen = jax.jit(make_moe_generate(mcfg, max_new_tokens=4,
                                    temperature=0.0))
    toks, _drops = gen(qp, prompt, jax.random.PRNGKey(2))
    assert toks.shape == (2, 4)


def test_quant_tp_mesh_token_exact():
    """r5: the former tp x quantized rejection is lifted — a quantized
    tree on a tp serving mesh (quant-aware shardings: q like the fp
    weight, scales with the size-1 reduced axis unsharded) produces
    token-exact greedy output vs the single-device quantized engine."""
    import pytest

    from pbs_tpu.models.serving import ContinuousBatcher
    from pbs_tpu.parallel import make_mesh
    from pbs_tpu.serve.partition import place

    if len(jax.devices()) < 2:
        pytest.skip("needs 2 devices")
    qp = quantize_weights(_params())

    def run(mesh):
        eng = ContinuousBatcher(
            CFG, qp if mesh is None else place(qp, mesh), n_slots=2,
            prompt_bucket=8, max_len=32, mesh=mesh)
        rid = eng.submit([1, 2, 3], max_new_tokens=6)
        done = []
        for _ in range(30):
            done += eng.step()
            if done:
                break
        assert done and done[0].request_id == rid
        return done[0].tokens

    gold = run(None)
    mesh = make_mesh({"tp": 2}, devices=jax.devices()[:2])
    assert run(mesh) == gold


def test_quant_moe_tp_mesh_token_exact():
    """The fourth weight form x mesh cell: int8 MoE tree on a tp mesh
    (expert q/s shards on d_ff, router fp32 replicated) — token-exact
    vs single-device."""
    import pytest

    from pbs_tpu.models import MoEConfig, init_moe_params
    from pbs_tpu.models.moe import moe_slot_mlp
    from pbs_tpu.models.serving import ContinuousBatcher
    from pbs_tpu.parallel import make_mesh
    from pbs_tpu.serve.partition import place

    if len(jax.devices()) < 2:
        pytest.skip("needs 2 devices")
    mcfg = MoEConfig(
        vocab=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=64, max_seq=64, dtype=jnp.float32, n_experts=4, top_k=2,
        dropless=True, router_group_size=8)
    qp = quantize_weights(init_moe_params(mcfg, jax.random.PRNGKey(0)))

    def run(mesh):
        eng = ContinuousBatcher(
            mcfg, qp if mesh is None else place(qp, mesh), n_slots=2,
            prompt_bucket=8, max_len=32,
            mlp_fn=moe_slot_mlp(mcfg), mesh=mesh)
        rid = eng.submit([1, 2, 3], max_new_tokens=5)
        done = []
        for _ in range(30):
            done += eng.step()
            if done:
                break
        assert done and done[0].request_id == rid
        return done[0].tokens

    gold = run(None)
    mesh = make_mesh({"tp": 2}, devices=jax.devices()[:2])
    assert run(mesh) == gold


def test_quantize_cli_roundtrip(tmp_path):
    """pbst quantize: checkpoint -> int8 checkpoint; the quantized tree
    loads template-free and serves."""
    import json as _json

    from pbs_tpu.ckpt import load_checkpoint, save_checkpoint
    from pbs_tpu.cli.pbst import main
    from pbs_tpu.models import make_generate

    params = _params()
    src = str(tmp_path / "fp")
    dst = str(tmp_path / "q8")
    save_checkpoint(src, jax.tree.map(np.asarray, params),
                    metadata={"job": "m"})
    assert main(["quantize", src, dst]) == 0
    qp, meta = load_checkpoint(dst)
    assert meta["quantized"] == "int8-weight-only"
    assert qp["layers"]["wq"]["q"].dtype == np.int8
    # Serves: greedy decode runs from the loaded tree.
    qp = jax.tree.map(jnp.asarray, qp)
    gen = jax.jit(make_generate(CFG, max_new_tokens=4, temperature=0.0))
    prompt = jnp.zeros((1, 8), jnp.int32)
    toks = gen(qp, prompt, jax.random.PRNGKey(0))
    assert toks.shape == (1, 4)
