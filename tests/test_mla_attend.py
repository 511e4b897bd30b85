"""The one-pass latent attention of the decode tick
(``pbs_tpu/ops/mla_attend.py``) in Pallas interpret mode, at toy widths
and blocks of 16 or 32 positions: against the ``jax.numpy`` form the
CPU lowers (``models/mla.py::attend_rows``) under ``decode_choice``'s
own mask. What the chip's compiler makes of it is
``tests/test_tpu_compile.py``'s to say, and what the chip computes
``tpu_tests/``'s."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pbs_tpu.models.mla import attend_rows, top_mask
from pbs_tpu.ops.kv_attend import kv_attend
from pbs_tpu.ops.mla_attend import attend_block, mla_attend, mla_attend_tiles

H, R, E, T, TOPK = 8, 32, 16, 64, 12
SCALE = 0.25
TOL = 2e-6


def rows(B: int, seed: int = 0):
    """Seeded queries and caches, float32: q_lat (B, H, R), q_r (B, H,
    E), ckv (B, T, R), kr (B, T, E) and an indexer's scores (B, T)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    shapes = ((B, H, R), (B, H, E), (B, T, R), (B, T, E), (B, T))
    return [jax.random.normal(k, s, jnp.float32) for k, s in zip(ks, shapes)]


def choice(index, row_pos, topk=TOPK):
    """``decode_choice``'s mask from the indexer's scores."""
    live = jnp.arange(T)[None, :] <= jnp.asarray(row_pos)[:, None]
    return top_mask(jnp.where(live, index, -jnp.inf), topk) & live


def both(q_lat, q_r, ckv, kr, chosen, row_pos, tk):
    pos = jnp.asarray(row_pos, jnp.int32)
    got = jax.jit(functools.partial(
        mla_attend, scale=SCALE, block=tk, interpret=True))(
            q_lat, q_r, ckv, kr, chosen, pos)
    want = attend_rows(q_lat, q_r, ckv, kr, chosen, scale=SCALE)
    return np.asarray(got), np.asarray(want)


def first_block_only(index, row_pos, tk):
    """Every lane's indexer prefers its first block: whatever the
    cursor, the chosen rows all lie there."""
    return index.at[:, :tk].add(100.0)


def tied(index, row_pos, tk):
    """Lane 0's ``TOPK``-th and next largest scores are equal, a block
    apart: both rows are in."""
    order = jnp.argsort(-index[0, :40])
    a, b = order[TOPK - 1], order[TOPK]
    return index.at[0, b].set(index[0, a])


CASES = {
    # name: (cursors, what is done to the indexer's scores)
    "cursor-0": ((0, 5, 20), None),
    "cursor-block-end": ((15, 31, 47), None),
    "cursor-block-start": ((16, 32, 48), None),
    "cursor-last-row": ((T - 1, T - 1, 7), None),
    "chosen-all-in-first-block": ((63, 40, 33), first_block_only),
    "fewer-live-than-topk": ((3, TOPK - 2, TOPK - 1), None),
    "tie-at-the-topk-th": ((39, 50, 12), tied),
}


@pytest.mark.parametrize("tk", [16, 32])
@pytest.mark.parametrize("case", list(CASES))
def test_the_kernel_attends_as_the_numpy_form_does(case, tk):
    """Every lane's output within a few float32 roundings of the
    ``jax.numpy`` form's, wherever its cursor stands in a block and
    wherever its chosen rows lie."""
    row_pos, shape = CASES[case]
    q_lat, q_r, ckv, kr, index = rows(len(row_pos), seed=len(case))
    if shape is not None:
        index = shape(index, row_pos, tk)
    chosen = choice(index, row_pos)
    counts = np.asarray(chosen.sum(-1))
    if case == "tie-at-the-topk-th":
        assert counts[0] == TOPK + 1, counts
    elif case == "chosen-all-in-first-block":
        assert not np.asarray(chosen)[:, tk:].any()
    else:
        assert (counts == np.minimum(np.asarray(row_pos) + 1, TOPK)).all()
    got, want = both(q_lat, q_r, ckv, kr, chosen, row_pos, tk)
    assert np.isfinite(got).all()
    assert float(np.abs(got - want).max()) <= TOL * float(np.abs(want).max())


@pytest.mark.parametrize("tk", [16, 32])
def test_an_idle_lanes_row_is_computed_all_the_same(tk):
    """An idle lane is nothing to the kernel but a cursor (wherever the
    engine left it): its row comes out as the ``jax.numpy`` form's, so
    the layers behind see the same activations whichever lowering
    ran."""
    row_pos = (0, 37, 0)          # lanes 0 and 2 idle, cursors at rest
    q_lat, q_r, ckv, kr, index = rows(3, seed=7)
    chosen = choice(index, row_pos)
    got, want = both(q_lat, q_r, ckv, kr, chosen, row_pos, tk)
    assert float(np.abs(got - want).max()) <= TOL * float(np.abs(want).max())
    # one position chosen: the softmax is that row itself
    np.testing.assert_allclose(
        got[0], np.broadcast_to(np.asarray(ckv[0, 0]), (H, R)), rtol=1e-6)


@pytest.mark.parametrize("tk", [16, 32])
def test_a_block_past_a_lanes_cursor_is_never_read(tk):
    """The rows of every block behind the one a lane's cursor is in are
    poisoned with NaN in both caches: the kernel's output stays finite
    and equal to the clean caches' (the ``jax.numpy`` form multiplies
    the poison by its zeros and returns NaN)."""
    row_pos = (0, tk - 1, 5, T - tk - 1)
    q_lat, q_r, ckv, kr, index = rows(len(row_pos), seed=3)
    chosen = choice(index, row_pos)
    dead = (jnp.arange(T)[None, :] // tk
            > jnp.asarray(row_pos)[:, None] // tk)[..., None]
    assert np.asarray(dead).any(axis=(1, 2)).all()
    clean, want = both(q_lat, q_r, ckv, kr, chosen, row_pos, tk)
    got, numpy_way = both(q_lat, q_r, jnp.where(dead, jnp.nan, ckv),
                          jnp.where(dead, jnp.nan, kr), chosen, row_pos, tk)
    assert np.isnan(numpy_way).all(axis=(1, 2)).all()
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, clean)
    assert float(np.abs(got - want).max()) <= TOL * float(np.abs(want).max())


def test_bfloat16_rows_give_the_numpy_forms_bfloat16():
    """At the cell's own precision (bfloat16 rows and queries, float32
    scores, sums and accumulator, probabilities rounded to bfloat16 for
    the values' product) the two forms differ by the rounding of the
    probabilities against a block's maximum in place of the row's: a
    bfloat16 unit of the output or two."""
    row_pos = (63, 20, 41)
    q_lat, q_r, ckv, kr, index = (
        t.astype(jnp.bfloat16) for t in rows(len(row_pos), seed=5))
    chosen = choice(index.astype(jnp.float32), row_pos)
    got, want = (t.astype(np.float32) for t in both(
        q_lat, q_r, ckv, kr, chosen, row_pos, 16))
    assert got.dtype == np.float32 and np.isfinite(got).all()
    assert float(np.abs(got - want).max()) <= 2 ** -6 * float(
        np.abs(want).max())


@pytest.mark.parametrize("heads,T,kv_rank,takes", [
    (64, 10240, 512, True),             # GLM-5's cell
    (64, 10240 + 256, 512, False),      # no whole blocks
    (4, 96, 24, False),                 # the rehearsal's toy widths
    (64, 10240, 576, False),            # a latent of 4.5 rows of lanes
    (12, 10240, 512, False),            # heads not by the eight
])
def test_the_shapes_decide_which_lowering_runs(heads, T, kv_rank, takes):
    assert mla_attend_tiles(heads, T, kv_rank) is takes


def test_a_cache_of_no_whole_blocks_is_refused():
    assert attend_block(10240) == 1024 and attend_block(1536) == 512
    assert attend_block(1000) == 0
    q_lat, q_r, ckv, kr, index = rows(1)
    with pytest.raises(ValueError, match="whole blocks"):
        mla_attend(q_lat, q_r, ckv, kr, index > 0, jnp.zeros(1, jnp.int32),
                   scale=SCALE, block=24, interpret=True)


class _Specs(Exception):
    """What a kernel asked ``pallas_call`` for."""


def _asked(call, monkeypatch):
    """The grid spec and the prefetched scalars of the ``pallas_call``
    that ``call()`` makes (which is not run)."""
    from jax.experimental import pallas as pl

    def caught(kernel, *, grid_spec, **kw):
        def operands(*args):
            raise _Specs(grid_spec, args[:grid_spec.num_scalar_prefetch])
        return operands

    monkeypatch.setattr(pl, "pallas_call", caught)
    with pytest.raises(_Specs) as asked:
        call()
    return asked.value.args


@pytest.mark.parametrize("kernel", ["kv_attend", "mla_attend"])
def test_a_dead_step_names_the_next_lanes_first_block(kernel, monkeypatch):
    """The index map of every streamed operand (the ones whose block
    follows the grid step while a lane is live), evaluated on cursors:
    up to the cursor's block a step names its own lane and block; past
    it the NEXT lane's first block, so that the pipeline, which looks
    one step ahead, fetches it under this lane's last live step; the
    last lane's dead steps stay on its own last block. One map, in
    ``ops/live_attend.py``, under both kernels."""
    tk, row_pos = 16, (20, 0, 63, 37)           # the cursors' blocks: 1 0 3 2
    if kernel == "kv_attend":
        q, k, v = (jnp.zeros(s, jnp.float32) for s in (
            (4, 4, 16), (3, 4, T, 2, 16), (3, 4, T, 2, 16)))
        call = functools.partial(
            kv_attend, q, k, v, jnp.asarray(row_pos), jnp.int32(2),
            block=tk, interpret=True)
    else:
        q_lat, q_r, ckv, kr, index = rows(4)
        call = functools.partial(
            mla_attend, q_lat, q_r, ckv, kr, index > 0, jnp.asarray(row_pos),
            scale=SCALE, block=tk, interpret=True)
    spec, scalars = _asked(call, monkeypatch)
    scalars = [np.asarray(x) for x in scalars]
    lanes, blocks = spec.grid
    assert (lanes, blocks) == (4, T // tk)
    assert scalars[0].tolist() == [1, 0, 3, 2]  # the block each cursor is in

    def named(b, j):
        return [tuple(int(i) for i in s.index_map(
                    np.int32(b), np.int32(j), *scalars))
                for s in spec.in_specs]

    # the streamed operands: the ones a live step moves along
    streamed = [i for i, (here, there) in enumerate(zip(
        named(2, 0), named(2, 1))) if here != there]
    assert len(streamed) == (2 if kernel == "kv_attend" else 3)
    for i in streamed:
        where = {(b, j): named(b, j)[i]
                 for b in range(lanes) for j in range(blocks)}
        for b, last in enumerate(scalars[0]):
            for j in range(last + 1, blocks):   # the lane's dead steps
                assert where[b, j] == (where[b + 1, 0] if b + 1 < lanes
                                       else where[b, last]), (i, b, j)
        # and the live steps name lanes and blocks of their own
        live = [where[b, j] for b, last in enumerate(scalars[0])
                for j in range(last + 1)]
        assert len(set(live)) == len(live)
