"""The one-pass latent attention of a prompt's ingestion
(``pbs_tpu/ops/mla_ingest_attend.py``) in Pallas interpret mode, at toy
widths and key blocks of 16 or 32 positions: against the ``jax.numpy``
form the CPU lowers (``models/mla.py::_attend_chunks``) under
``top_mask``'s own choice, and ``mla_ingest`` whole with the kernel
interpreted in that form's place. What the chip's compiler makes of it
is ``tests/test_tpu_compile.py``'s to say, and what the chip computes
``tpu_tests/``'s."""

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
from pbs_tpu.models import slot_programs
from pbs_tpu.models.serving import ContinuousBatcher
from pbs_tpu.models.slot_programs import live_ingest, rope_table
from pbs_tpu.obs.trace import Ev
from pbs_tpu.ops.mla_ingest_attend import (
    ingest_attend, ingest_attend_tiles, ingest_block)

H, Q, D, V, S, TOPK = 4, 32, 16, 24, 128, 20
SCALE = 0.25
TOL = 1e-5


def rows(seed: int = 0, dtype=jnp.float32):
    """Seeded heads-major queries of one block, keys and values of a
    prompt, and an indexer's scores: q (H, Q, D), k (H, S, D), v (H, S,
    V), index (Q, S) float32."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, k, v = (jax.random.normal(key, s, jnp.float32).astype(dtype)
               for key, s in zip(ks, ((H, Q, D), (H, S, D), (H, S, V))))
    return q, k, v, jax.random.normal(ks[3], (Q, S), jnp.float32)


def choice(index, first: int, keys: int, topk: int = TOPK):
    """``mla_ingest``'s mask of a block of queries that starts at
    ``first``: of the ``keys`` first keys the ``topk`` each query's
    indexer scores highest among those at or before it."""
    seen = jnp.arange(keys)[None, :] <= first + jnp.arange(Q)[:, None]
    return mla.top_mask(jnp.where(seen, index[:, :keys], -jnp.inf),
                        topk) & seen


def both(q, k, v, seen, first, tk):
    got = jax.jit(functools.partial(
        ingest_attend, scale=SCALE, block=tk, interpret=True))(
            q, k, v, seen, jnp.int32(first))
    want = mla._attend_chunks(q, k, v, seen, SCALE, q.dtype)
    return (np.asarray(got.astype(jnp.float32)),
            np.asarray(want.astype(jnp.float32)))


def avoid_a_block(index, first, tk):
    """Every query's indexer shuns the second key block: each chooses
    nothing there, and the block adds nothing."""
    return index.at[:, tk:2 * tk].add(-100.0)


def one_query_elsewhere(index, first, tk):
    """Query 3 alone shuns the block its fellows prefer."""
    index = index.at[:, :tk].add(50.0)
    return index.at[3, :tk].add(-200.0)


CASES = {
    # name: (first query, keys given, topk, what is done to the scores)
    "span-under-topk": (32, 64, 64, None),
    "every-seen-key-chosen": (96, 128, 128, None),
    "a-subset": (96, 128, TOPK, None),
    "nothing-chosen-in-a-block": (96, 128, TOPK, avoid_a_block),
    "one-query-sees-nothing-in-a-block": (96, 128, TOPK,
                                          one_query_elsewhere),
    "first-query-block": (0, 128, TOPK, None),
    "first-block-of-a-span": (64, 128, TOPK, None),
    "more-keys-than-the-span": (32, 64, TOPK, None),
}


@pytest.mark.parametrize("tk", [16, 32, 64])
@pytest.mark.parametrize("case", list(CASES))
def test_the_kernel_attends_as_the_numpy_form_does(case, tk):
    """Every head's output of every query within a few float32
    roundings of the ``jax.numpy`` form's, wherever the block of
    queries lies in its span and wherever its chosen keys lie."""
    first, keys, topk, shape = CASES[case]
    q, k, v, index = rows(seed=len(case))
    if shape is not None:
        index = shape(index, first, tk)
    seen = choice(index, first, keys, topk)
    counts = np.asarray(seen.sum(-1))
    assert (counts == np.minimum(first + np.arange(Q) + 1, topk)).all()
    if case == "nothing-chosen-in-a-block":
        assert not np.asarray(seen)[:, tk:2 * tk].any()
    if case == "one-query-sees-nothing-in-a-block":
        held = np.asarray(seen)[:, :tk]
        assert not held[3].any() and held[4].any()
    got, want = both(q, k, v, seen, first, tk)
    assert np.isfinite(got).all()
    assert float(np.abs(got - want).max()) <= TOL * float(np.abs(want).max())


@pytest.mark.parametrize("tk", [16, 32, 64])
@pytest.mark.parametrize("first", [0, 16, 32])
def test_a_key_block_past_the_queries_own_end_is_never_read(first, tk):
    """The keys and values of every block behind the one the query
    block's last position lies in are poisoned with NaN: the kernel's
    output stays finite and equal to the clean prompt's (the
    ``jax.numpy`` form multiplies the poison by its zeros and returns
    NaN)."""
    q, k, v, index = rows(seed=first + tk)
    seen = choice(index, first, S)
    dead = (jnp.arange(S) // tk > (first + Q - 1) // tk)[None, :, None]
    assert np.asarray(dead).any()
    clean, want = both(q, k, v, seen, first, tk)
    got, numpy_way = both(q, jnp.where(dead, jnp.nan, k),
                          jnp.where(dead, jnp.nan, v), seen, first, tk)
    assert np.isnan(numpy_way).all()
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, clean)
    assert float(np.abs(got - want).max()) <= TOL * float(np.abs(want).max())


@pytest.mark.parametrize("first,topk", [(0, TOPK), (96, TOPK), (96, 128)])
def test_bfloat16_rows_give_the_numpy_forms_bfloat16(first, topk):
    """At the cell's own precision (bfloat16 queries, keys and values,
    float32 scores, sums and accumulator, probabilities rounded to
    bfloat16 for the values' product) the two forms differ by the
    rounding of the probabilities against a block's maximum in place
    of a chunk's: a bfloat16 unit of the output or two."""
    q, k, v, index = rows(seed=5, dtype=jnp.bfloat16)
    seen = choice(index, first, S, topk)
    got, want = both(q, k, v, seen, first, 16)
    assert np.isfinite(got).all()
    assert float(np.abs(got - want).max()) <= 2 ** -6 * float(
        np.abs(want).max())


# --- mla_ingest whole, the kernel interpreted in _attend_chunks' place ---

BUCKET, MAX_LEN = 64, 96
SPEC = Spec()
FAMILY = SPEC.family("moe-mla-dsa")


@functools.lru_cache(maxsize=None)
def toy_layer():
    """The configuration file's rehearsal preset (4 heads of 12 + 4
    over latents of 24 and 16, an indexer that picks 16 positions), its
    one layer's kind and the seeded parameters."""
    full = SPEC.config("glm-5")
    c = copy.deepcopy(overlay(full, full["rehearsal"]))
    cfg = FAMILY.program_config(c, 1, MAX_LEN)
    params = jax.jit(lambda s: FAMILY.reference.init_tree(
        c, s, 1, jnp.float32))(FAMILY.reference.seed_word(11))
    return cfg, P.plan_of(cfg).attn[0], params


def interpreted(q, k, v, seen, first, live, *, scale, dt):
    """``mla._attend_block`` as a TPU lowers it where ``live``, the
    kernel interpreted (key blocks of 16)."""
    assert live
    return ingest_attend(q, k, v, seen, first, scale=scale, block=16,
                         interpret=True)


@pytest.mark.parametrize("plen", [BUCKET, 50, 33, 7])
def test_the_ingestion_is_the_same_through_the_kernel(plen, monkeypatch):
    """A prompt's whole pass in blocks of 16 queries (four spans: the
    first chooses nothing, the others against their own span's keys; a
    prompt that ends inside a block, inside a span and in the first
    block) gives the same rows and, at every real position, the same
    heads' output whichever form attended. A block of queries past the
    prompt's end is run by neither."""
    cfg, a, params = toy_layer()
    ap = params["blocks"]["00"]["attn"]
    monkeypatch.setattr(mla, "MLA_BLOCK", 16)
    h = jax.random.normal(jax.random.PRNGKey(2), (1, BUCKET, cfg.d_model))
    valid = (jnp.arange(BUCKET) < plen)[None]
    cos, sin = (t[jnp.arange(BUCKET)][None]
                for t in rope_table(a.rope, 0, MAX_LEN))
    args = (a, ap, h, valid, cos, sin, cfg.norm_eps, jnp.float32)
    want = mla.mla_ingest(*args)
    monkeypatch.setattr(mla, "_attend_block", interpreted)
    got = mla.mla_ingest(*args, live=True)
    for g, w in zip(got[1:], want[1:]):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    g, w = (np.asarray(t)[0] for t in (got[0], want[0]))
    assert float(np.abs(g[:plen] - w[:plen]).max()) <= TOL * float(
        np.abs(w[:plen]).max())
    ran = -(-plen // 16) * 16
    assert not g[ran:].any() and not w[ran:].any()


# --- which lowering runs, and what the engine counts ---


@pytest.mark.parametrize("queries,qk,v,span,takes", [
    (256, 256, 256, 2048, True),        # GLM-5's cell at the 8,192 rung
    (256, 256, 256, 1024, True),        # and at the 4,096 rung
    (256, 256, 256, 1536, True),        # blocks of 512
    (256, 256, 256, 768, False),        # no whole key blocks
    (256, 192, 256, 2048, False),       # a query of 1.5 rows of lanes
    (256, 64, 64, 2048, False),         # a 64-wide head
    (16, 256, 256, 2048, False),        # queries under an int8 tile
])
def test_the_shapes_decide_which_lowering_runs(queries, qk, v, span, takes):
    assert ingest_attend_tiles(queries, qk, v, span) is takes


def test_a_span_of_no_whole_blocks_is_refused():
    assert ingest_block(2048) == 1024 and ingest_block(1536) == 512
    assert ingest_block(1000) == 0
    q, k, v, index = rows()
    with pytest.raises(ValueError, match="whole blocks"):
        ingest_attend(q, k, v, index > 0, 0, scale=SCALE, block=24,
                      interpret=True)


@pytest.mark.parametrize("case,rung,devices,lowered,layers", [
    ("glm-5's rungs", 8192, 1, False, {0, 1, 2, 3, 4}),
    ("glm-5's rungs", 4096, 1, False, {0, 1, 2, 3, 4}),
    ("a rung of no whole key blocks", 3072, 1, False, set()),
    ("a 64-wide head", 8192, 1, False, set()),
    ("a mesh of two devices", 8192, 2, False, set()),
    ("as it runs on a CPU", 8192, 1, True, set()),
])
def test_the_program_says_which_layers_ingest_through_the_kernel(
        case, rung, devices, lowered, layers):
    """``live_ingest``: GLM-5's five layers at the cell's two rungs on
    one device, and nothing for a head narrower than a row of lanes,
    for a rung whose spans are not whole key blocks, on a mesh, or,
    asked how it *runs*, on a device that is no TPU."""
    plan = P.plan_of(FAMILY.program_config(SPEC.config("glm-5"), 5, 10240))
    if "64-wide" in case:
        narrow = dataclasses.replace(plan.attn[0], nope_dim=32, rope_dim=32,
                                     v_dim=64, rope=dataclasses.replace(
                                         plan.attn[0].rope, rotary_dim=32))
        plan = dataclasses.replace(plan, attn=(narrow,))
    got = live_ingest(plan, rung, tuple(jax.devices()[:devices]), lowered)
    assert got == layers


class _Chip:
    platform = "tpu"


def _select_blocks(eng, prompts):
    """The ``blocks`` of each prompt's ``ENG_SELECT``, served through
    ``eng`` (a prefill's record counts a row a prompt token)."""
    for p in prompts:
        eng.submit(p, 2)
    while eng.has_work():
        eng.step()
    rows = {r[3]: r[7] for r in eng.trace.peek().tolist()
            if r[1] == int(Ev.ENG_SELECT)}
    return [rows[len(p)] for p in prompts]


def test_eng_select_counts_a_prefills_pairs_where_the_kernel_runs(
        monkeypatch):
    """A prefill's ``ENG_SELECT.blocks``: 0 on the CPU these tests run
    on, where the ``jax.numpy`` form attends; with the cache on one TPU
    and shapes the kernel's tiling takes (told so: the toy's are not),
    the (query block, key block) pairs of one layer's passes, from the
    prompt's length alone: blocks of 16 queries and 16 keys, 50 tokens
    four query blocks against 1, 2, 3 and 4 key blocks, 7 tokens one
    against one."""
    cfg, _, params = toy_layer()
    prompts = [[3] * 50, [5] * 7]
    make = functools.partial(ContinuousBatcher, cfg, params, n_slots=2,
                             prompt_bucket=BUCKET, max_len=MAX_LEN)
    eng = make()
    assert eng._ingest_live == frozenset()
    assert _select_blocks(eng, prompts) == [0, 0]
    monkeypatch.setattr(mla, "MLA_BLOCK", 16)
    monkeypatch.setattr(mla, "ingest_block", lambda keys: 16)
    monkeypatch.setattr(mla, "_kernel_ingest", functools.partial(
        ingest_attend, block=16, interpret=True))
    monkeypatch.setattr(slot_programs, "ingest_tiles", lambda a, rung: True)
    monkeypatch.setattr(slot_programs, "_placed_on", lambda mesh: (_Chip(),))
    eng = make()
    assert eng._ingest_live == {BUCKET}
    assert _select_blocks(eng, prompts) == [1 + 2 + 3 + 4, 1]


@pytest.mark.parametrize("rung,plen,pairs", [
    # 8,192 rows: blocks of 256 queries, key blocks of 1,024
    (8192, 8192, 4 * (1 + 2 + 3 + 4 + 5 + 6 + 7 + 8)),
    (8192, 4097, 4 * (1 + 2 + 3 + 4) + 5),
    (8192, 256, 1), (8192, 257, 2), (8192, 1, 1),
    (4096, 4096, 4 * (1 + 2 + 3 + 4)),
    (4096, 3072, 4 * (1 + 2 + 3)),
])
def test_the_host_counts_the_pairs_the_kernel_runs(rung, plen, pairs):
    """``ENG_SELECT``'s ``blocks`` of a prefill: every query block that
    holds a token against the key blocks up to its own last position's,
    from ``plen`` and the block sizes alone."""
    assert mla.ingest_pairs(rung, plen) == pairs
