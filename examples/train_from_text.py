"""End-to-end training from raw text on whatever device is present.

    python examples/train_from_text.py [path/to/text.txt]

Byte-level tokens (no external tokenizer), packed corpus, prefetched
batches, jitted train step with remat, checkpoint at the end. Scale
the config up on a real chip; this default runs in seconds on CPU.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import tempfile

import jax
import jax.numpy as jnp

from pbs_tpu.ckpt import save_checkpoint
from pbs_tpu.data import (
    VOCAB,
    Prefetcher,
    TokenDataset,
    corpus_from_file,
    corpus_from_text,
    ShardedBatchSource,
)
from pbs_tpu.models import TransformerConfig, init_params, make_train_step
from pbs_tpu.utils.compile_cache import setup_compilation_cache

BATCH, SEQ, STEPS = 4, 128, 30


def main() -> int:
    setup_compilation_cache()
    workdir = tempfile.mkdtemp(prefix="pbst_example_")
    corpus = os.path.join(workdir, "corpus.tok")
    if len(sys.argv) > 1:
        n = corpus_from_file(corpus, sys.argv[1])
    else:
        n = corpus_from_text(
            corpus, ["The credit scheduler multiplexes tenants over "
                     "step quanta; telemetry feeds the slice. "] * 200)
    print(f"corpus: {n} byte-tokens")

    cfg = TransformerConfig(
        vocab=VOCAB, d_model=128, n_layers=4, n_heads=8, n_kv_heads=4,
        d_ff=256, max_seq=SEQ,
        dtype=jnp.bfloat16 if jax.default_backend() == "tpu"
        else jnp.float32,
        remat=True, remat_policy="dots")
    params = init_params(cfg, jax.random.PRNGKey(0))
    init_opt, step = make_train_step(cfg, learning_rate=3e-3)
    state = (params, jax.jit(init_opt)(params), 0)
    step = jax.jit(step, donate_argnums=(0,))

    ds = TokenDataset(corpus)
    # ShardedBatchSource: on a multi-host pod each host would pass its
    # own host_id/n_hosts and draw its disjoint slice of one global
    # schedule; the cursor rides the checkpoint so a restore resumes
    # the exact data position on every host.
    src = ShardedBatchSource(ds, global_batch=BATCH, seq_len=SEQ,
                             host_id=0, n_hosts=1, seed=0)
    with Prefetcher(src, depth=2) as pf:
        for i in range(STEPS):
            state, m = step(state, jnp.asarray(next(pf)))
            if i % 10 == 0 or i == STEPS - 1:
                print(f"step {i:3d}  loss {float(m['loss']):.3f}")
    ckpt = os.path.join(workdir, "ckpt")
    # Cursor from the CONSUMED count (one batch per step), not the
    # producer counter: the prefetcher sources ahead by a thread-
    # timing-dependent amount, which would desync hosts on restore.
    cursor = dict(src.state(), step=STEPS)
    save_checkpoint(ckpt, jax.device_get(state[0]),
                    metadata={"steps": STEPS, "data_cursor": cursor})
    print(f"checkpoint: {ckpt}  (pbst ckpt-info / pbst quantize)")
    ds.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
