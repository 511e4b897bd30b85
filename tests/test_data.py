"""Input pipeline: packed token files, mmap gathers, prefetching."""

from __future__ import annotations

import numpy as np
import pytest

from pbs_tpu.data import (
    Prefetcher,
    TokenDataset,
    make_batch_source,
    write_token_file,
)


@pytest.fixture
def corpus(tmp_path):
    toks = np.arange(10_000, dtype=np.int64) % 32_000
    path = str(tmp_path / "corpus.pbst")
    write_token_file(path, toks)
    ds = TokenDataset(path)
    yield ds, toks
    ds.close()


def test_roundtrip_and_dtype(corpus, tmp_path):
    ds, toks = corpus
    assert len(ds) == 10_000
    assert ds.dtype == np.uint16  # vocab < 65536 packs to u16
    big = np.array([0, 1, 1 << 20], dtype=np.int64)
    p = str(tmp_path / "big.pbst")
    write_token_file(p, big)
    ds2 = TokenDataset(p)
    assert ds2.dtype == np.uint32
    np.testing.assert_array_equal(ds2.window(0, 1, 3)[0], big)
    ds2.close()


def test_window_deterministic_and_correct(corpus):
    ds, toks = corpus
    w = ds.window(0, 4, 128)
    assert w.shape == (4, 128) and w.dtype == np.int32
    for b in range(4):
        np.testing.assert_array_equal(w[b], toks[b * 128:(b + 1) * 128])
    np.testing.assert_array_equal(w, ds.window(0, 4, 128))


def test_sample_windows_are_valid_slices(corpus):
    ds, toks = corpus
    rng = np.random.default_rng(7)
    s = ds.sample(8, 64, rng)
    assert s.shape == (8, 64)
    for row in s:
        start = int(row[0])  # corpus is arange: first token = offset
        np.testing.assert_array_equal(row, toks[start:start + 64])


def test_native_and_python_gather_agree(corpus):
    ds, _ = corpus
    starts = np.array([0, 17, 9_000], dtype=np.int64)
    nat = ds._gather(starts, 50)
    saved = ds._nat
    ds._nat = None
    try:
        py = ds._gather(starts, 50)
    finally:
        ds._nat = saved
    np.testing.assert_array_equal(nat, py)


def test_gather_bounds_checked(corpus):
    ds, _ = corpus
    with pytest.raises((IndexError, ValueError)):
        ds._gather(np.array([9_990], dtype=np.int64), 64)


def test_bad_magic_rejected(tmp_path):
    p = tmp_path / "junk.bin"
    p.write_bytes(b"nope" + b"\0" * 32)
    with pytest.raises(ValueError, match="not a PBST"):
        TokenDataset(str(p))


def test_prefetcher_streams_and_stops(corpus):
    ds, _ = corpus
    src = make_batch_source(ds, batch=4, seq_len=32, seed=3)
    seen = []
    with Prefetcher(src, depth=2, place=lambda x: x) as pf:
        for _ in range(10):
            seen.append(next(pf))
    assert len(seen) == 10
    assert all(b.shape == (4, 32) for b in seen)
    # deterministic given the seed: a fresh source replays the stream
    src2 = make_batch_source(ds, batch=4, seq_len=32, seed=3)
    np.testing.assert_array_equal(seen[0], src2())


def test_prefetcher_propagates_worker_error():
    calls = {"n": 0}

    def bad_source():
        calls["n"] += 1
        if calls["n"] > 2:
            raise RuntimeError("disk gone")
        return np.zeros((2, 8), np.int32)

    pf = Prefetcher(bad_source, depth=1, place=lambda x: x)
    with pytest.raises(RuntimeError, match="disk gone"):
        for _ in range(10):
            next(pf)
    pf.stop()


def test_prefetcher_feeds_training(corpus):
    """End-to-end: the loader drives a real (tiny) train step."""
    import jax

    from pbs_tpu.models import init_params, make_train_step
    from pbs_tpu.models import flagship_config

    ds, _ = corpus
    cfg = flagship_config(tiny=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    init_opt, train_step = make_train_step(cfg, learning_rate=1e-3)
    state = (params, jax.jit(init_opt)(params), 0)
    step = jax.jit(train_step)
    src = make_batch_source(ds, batch=2, seq_len=33, seed=0)
    losses = []
    with Prefetcher(src, depth=2) as pf:
        for _ in range(4):
            state, m = step(state, next(pf) % cfg.vocab)
            losses.append(float(m["loss"]))
    assert int(state[2]) == 4
    assert all(np.isfinite(losses))


def test_negative_tokens_rejected(tmp_path):
    with pytest.raises(ValueError, match="negative"):
        write_token_file(str(tmp_path / "neg.pbst"),
                         np.array([1, -1, 2], dtype=np.int64))


def test_python_fallback_gather_bounds_checked(corpus):
    ds, _ = corpus
    saved = ds._nat
    ds._nat = None
    try:
        with pytest.raises(IndexError):
            ds._gather(np.array([9_990], dtype=np.int64), 64)
        with pytest.raises(IndexError):
            ds._gather(np.array([-5], dtype=np.int64), 8)
    finally:
        ds._nat = saved


def test_byte_tokenizer_roundtrip(tmp_path):
    """Text -> byte tokens -> text, lossless incl. non-ASCII."""
    from pbs_tpu.data import (
        BOS,
        EOS,
        VOCAB,
        corpus_from_text,
        decode_tokens,
        encode_text,
    )

    text = "Hello, scheduler — café ü"
    toks = encode_text(text)
    assert toks[0] == BOS and toks[-1] == EOS
    assert toks.max() < VOCAB
    assert decode_tokens(toks) == text


def test_text_to_training_end_to_end(tmp_path):
    """The full loop a new user needs: text -> packed corpus ->
    TokenDataset -> prefetched batches -> train steps; loss moves."""
    import jax
    import jax.numpy as jnp

    from pbs_tpu.data import (
        VOCAB,
        Prefetcher,
        TokenDataset,
        corpus_from_text,
        make_batch_source,
    )
    from pbs_tpu.models import TransformerConfig, init_params, make_train_step

    path = str(tmp_path / "corpus.tok")
    docs = ["the quick brown fox jumps over the lazy dog. " * 8
            for _ in range(4)]
    n = corpus_from_text(path, docs)
    assert n > 512
    ds = TokenDataset(path)
    src = make_batch_source(ds, batch=2, seq_len=64, seed=3)

    cfg = TransformerConfig(
        vocab=VOCAB, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq=64, dtype=jnp.float32)
    params = init_params(cfg, jax.random.PRNGKey(0))
    init_opt, step = make_train_step(cfg, learning_rate=3e-3)
    state = (params, jax.jit(init_opt)(params), 0)
    step = jax.jit(step)
    losses = []
    with Prefetcher(src, depth=2) as pf:
        for _ in range(8):
            state, m = step(state, jnp.asarray(next(pf)))
            losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]  # byte-level text actually trains
    ds.close()


def test_sharded_source_partitions_and_resumes(tmp_path):
    """Multi-host sampling: hosts draw disjoint slices of ONE global
    schedule with no communication, and the one-int cursor resumes the
    exact schedule position."""
    import numpy as np

    from pbs_tpu.data import ShardedBatchSource

    path = str(tmp_path / "corpus.pbst")
    write_token_file(path, np.arange(10_000) % 251)
    ds = TokenDataset(path)

    srcs = [ShardedBatchSource(ds, global_batch=8, seq_len=16,
                               host_id=h, n_hosts=4, seed=5)
            for h in range(4)]
    # One global step: concatenating host shards = the global batch a
    # single-host source with the same seed would draw.
    shards = [s() for s in srcs]
    assert all(sh.shape == (2, 16) for sh in shards)
    whole = ShardedBatchSource(ds, global_batch=8, seq_len=16,
                               host_id=0, n_hosts=1, seed=5)()
    np.testing.assert_array_equal(np.concatenate(shards), whole)

    # Resume: a fresh source loading host 2's cursor reproduces its
    # NEXT batch exactly.
    nxt = srcs[2]()
    fresh = ShardedBatchSource(ds, global_batch=8, seq_len=16,
                               host_id=2, n_hosts=4, seed=5)
    fresh.load_state({"step": 1, "seed": 5, "host_id": 2, "n_hosts": 4,
                      "global_batch": 8, "seq_len": 16})
    np.testing.assert_array_equal(fresh(), nxt)

    # Mismatched schedule refuses to resume.
    import pytest

    with pytest.raises(ValueError, match="different data schedule"):
        fresh.load_state({"step": 3, "seed": 99, "n_hosts": 4,
                          "global_batch": 8, "seq_len": 16})
    with pytest.raises(ValueError, match="different data schedule"):
        # A changed batch size or seq_len is a DIFFERENT schedule too.
        fresh.load_state({"step": 3, "seed": 5, "n_hosts": 4,
                          "global_batch": 16, "seq_len": 16})
    with pytest.raises(ValueError):
        ShardedBatchSource(ds, global_batch=7, seq_len=16, n_hosts=4)
