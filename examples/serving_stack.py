"""The serving stack in one script: continuous batching with prefix
caching, int8 quantization, and speculative decoding, on one model.

    python examples/serving_stack.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from pbs_tpu.data import VOCAB, decode_tokens, encode_text
from pbs_tpu.models import (
    TransformerConfig,
    init_params,
    make_speculative_generate,
    quantize_weights,
    quantized_nbytes,
)
from pbs_tpu.models.serving import ContinuousBatcher
from pbs_tpu.utils.compile_cache import setup_compilation_cache

CFG = TransformerConfig(
    vocab=VOCAB, d_model=128, n_layers=4, n_heads=8, n_kv_heads=4,
    d_ff=256, max_seq=256, dtype=jnp.float32)


def main() -> int:
    setup_compilation_cache()
    params = init_params(CFG, jax.random.PRNGKey(0))

    # int8 weight-only: the serving copy at ~1/4 the bytes.
    qp = quantize_weights(params)
    print(f"params: {quantized_nbytes(params) / 1e6:.1f} MB fp32 -> "
          f"{quantized_nbytes(qp) / 1e6:.1f} MB int8")

    # Continuous batching + exact-prompt prefix cache.
    eng = ContinuousBatcher(CFG, qp, n_slots=4, prompt_bucket=32,
                            max_len=96, prefix_cache_size=8)
    system = "You are a scheduler. "
    for i in range(6):
        eng.submit(encode_text(system, add_eos=False), max_new_tokens=12)
    done = []
    while eng.has_work():
        done += eng.step()
    st = eng.stats()
    print(f"served {st['completed']} requests; prefix hits "
          f"{st['prefix_hits']}/{st['prefix_hits'] + st['prefix_misses']}; "
          f"ttft_p50 {st['ttft_p50_s'] * 1e3:.1f} ms")
    print("sample:", repr(decode_tokens(done[0].tokens))[:60])

    # Speculative decoding (greedy token-exact). Untrained random
    # models disagree almost always, so for the demo the target drafts
    # for itself — the 100% ceiling; a real deployment pairs a small
    # trained draft with a large target and lands in between.
    spec = jax.jit(make_speculative_generate(CFG, CFG, 16, k=4))
    prompt = jnp.asarray(
        encode_text(system, add_eos=False))[None, :]
    toks, stats = spec(params, params, prompt)
    acc, prop = int(stats["accepted"]), int(stats["proposed"])
    print(f"speculative (self-draft ceiling): {int(stats['rounds'])} "
          f"rounds, acceptance {acc}/{prop} = {acc / max(prop, 1):.0%}")

    # The two composed: speculative CONTINUOUS batching — draft
    # propose-k + one-forward verify per engine tick, each slot
    # advancing by its own acceptance; bit-identical to the plain
    # engine, ~acceptance-rate fewer ticks.
    from pbs_tpu.models import SpeculativeBatcher

    seng = SpeculativeBatcher(CFG, params, CFG, params, k=4, n_slots=2,
                              prompt_bucket=64, max_len=128)
    for q in ("tell me a story", "what is a tpu?"):
        seng.submit(encode_text(system + q, add_eos=False),
                    max_new_tokens=12)
    while seng.has_work():
        seng.step()
    sst = seng.stats()
    print(f"speculative serving: {sst['completed']} requests in "
          f"{sst['steps']} engine ticks, acceptance "
          f"{sst['spec_acceptance']:.0%}")

    # The second model family through the SAME engine: MoE serving via
    # the shared FFN seam, with router drop telemetry in the stats.
    from pbs_tpu.models import MoEConfig, init_moe_params
    from pbs_tpu.models.moe import moe_slot_mlp

    mcfg = MoEConfig(vocab=CFG.vocab, d_model=64, n_layers=2, n_heads=4,
                     n_kv_heads=2, d_ff=96, max_seq=128,
                     dtype=CFG.dtype, n_experts=4, top_k=2,
                     capacity_factor=4.0)
    mparams = init_moe_params(mcfg, jax.random.PRNGKey(3))
    meng = ContinuousBatcher(mcfg, mparams, n_slots=2, prompt_bucket=64,
                             max_len=128, mlp_fn=moe_slot_mlp(mcfg))
    meng.submit(encode_text(system, add_eos=False), max_new_tokens=8)
    while meng.has_work():
        meng.step()
    mst = meng.stats()
    print(f"MoE serving: {mst['completed']} request, router drop "
          f"telemetry {mst['mlp_extra_mean']:.3f} (dropless)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
