"""The one-pass grouped product (``pbs_tpu/ops/grouped_matmul.py``) in
interpret mode against ``jax.lax.ragged_dot``, its oracle and its CPU
lowering, and the expert layer's sorted rows with the kernel in
``ragged_dot``'s place against both other forms of the same sum. Toy
sizes, CPU: tiles of 16 rows, widths a scaled 2688 x 1856 (168 x 116:
neither a multiple of the tile), a width the kernel takes turned (256 x
116) and one it takes in two column blocks.

Tolerances: both forms accumulate in float32, in another order, so
float32 outputs agree to a few roundings of the sum (1e-5 of the
largest entry) and bfloat16 ones to one rounding of the output
(2^-8)."""

import dataclasses
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pbs_tpu.models import moe

# the module: the package's own name for it is the function
gm = importlib.import_module("pbs_tpu.ops.grouped_matmul")

TILE = 16
#: name -> (sorted rows, rows each group holds)
CASES = {
    "an empty group": (64, [5, 0, 20, 3]),
    "a group that crosses row tiles": (64, [3, 40, 9]),
    "several groups inside one tile": (64, [3, 4, 2, 5]),
    "rows behind the last group": (96, [7, 6]),
    "no group has a row": (64, [0, 0, 0]),
    "the groups fill the buffer": (64, [20, 30, 14]),
    "a last tile that is not whole": (70, [40, 0, 24]),
    "first and last groups empty": (64, [0, 17, 16, 0]),
}
#: name -> (k, n, most bytes a weight block or None for the module's)
WIDTHS = {
    "168 x 116": (168, 116, None),
    "256 x 116, turned": (256, 116, None),
    "128 x 256, two column blocks": (128, 256, 128 * 128 * 4),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("widths", WIDTHS)
@pytest.mark.parametrize("case", CASES)
def test_the_kernel_forms_ragged_dots_sum(case, widths, dtype, monkeypatch):
    """Each group's rows against its own matrix as ``ragged_dot`` forms
    them; the rows behind the last group are never read: poisoned with
    NaN, the held rows come out bit for bit as they did."""
    m, sizes = CASES[case]
    k, n, block = WIDTHS[widths]
    if block is not None:
        monkeypatch.setattr(gm, "BLOCK_BYTES", block)
        assert gm._columns(k, n, 4) == 128
    assert gm.lies_turned(k, n) == ("turned" in widths)
    keys = jax.random.split(jax.random.PRNGKey(len(case)), 2)
    x = jax.random.normal(keys[0], (m, k), jnp.float32).astype(dtype)
    w = jax.random.normal(keys[1], (len(sizes), k, n),
                          jnp.float32).astype(dtype)
    sizes = jnp.asarray(sizes, jnp.int32)
    held = int(sizes.sum())
    want = np.asarray(jax.lax.ragged_dot(x, w, sizes)[:held], np.float32)
    got = gm.grouped_matmul(x, w, sizes, tile=TILE, interpret=True)
    assert got.shape == (m, n) and got.dtype == x.dtype
    got = np.asarray(got[:held], np.float32)
    if held:
        tol = 2.0 ** -8 if dtype == jnp.bfloat16 else 1e-5
        assert np.abs(got - want).max() <= tol * np.abs(want).max()
    behind = jnp.arange(m)[:, None] >= held
    poisoned = gm.grouped_matmul(jnp.where(behind, jnp.nan, x), w, sizes,
                                 tile=TILE, interpret=True)
    assert np.array_equal(np.asarray(poisoned[:held], np.float32), got)


def test_a_ticks_few_rows_a_group_are_ragged_dots_sum():
    """Laguna's tick in small (640 sorted rows on 128 held experts, half
    of them held, ~118 touched): 144 sorted rows on 32 groups of 2 or 3
    held rows each, a tenth of the groups empty, the half of the buffer
    behind the last group poisoned. Six or seven groups share a tile of
    16 rows, a visit each; the held rows equal ``ragged_dot``'s and the
    tiles behind them are never visited."""
    groups, m, k, n = 32, 144, 168, 116
    sizes = np.where(np.arange(groups) % 2, 3, 2)
    sizes[[4, 13, 27]] = 0
    held = int(sizes.sum())
    assert 2 * held == m
    keys = jax.random.split(jax.random.PRNGKey(49), 2)
    x = jax.random.normal(keys[0], (m, k), jnp.float32).astype(jnp.bfloat16)
    x = jnp.where(jnp.arange(m)[:, None] >= held, jnp.nan, x)
    w = jax.random.normal(keys[1], (groups, k, n),
                          jnp.float32).astype(jnp.bfloat16)
    sizes = jnp.asarray(sizes, jnp.int32)
    _, tile, _, count = gm._visits(sizes, m // TILE, TILE)
    assert int(count[0]) > groups - 3 and int(tile.max()) == (held - 1) // TILE
    want = np.asarray(jax.lax.ragged_dot(x[:held], w, sizes), np.float32)
    got = np.asarray(gm.grouped_matmul(x, w, sizes, tile=TILE,
                                       interpret=True)[:held], np.float32)
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() <= 2.0 ** -8 * np.abs(want).max()


def test_a_visit_is_a_tile_a_group_has_rows_in():
    """The grid's map for sizes (3, 0, 40, 9) in tiles of 16: group 0
    in tile 0, group 2 in tiles 0-2, group 3 in tiles 2-3; the steps
    past the sixth visit repeat it."""
    group, tile, offsets, count = gm._visits(
        jnp.asarray([3, 0, 40, 9], jnp.int32), 4, 16)
    assert int(count[0]) == 6
    assert group.tolist() == [0, 2, 2, 2, 3, 3, 3]
    assert tile.tolist() == [0, 0, 1, 2, 2, 3, 3]
    assert offsets.tolist() == [0, 3, 3, 43, 52]
    group, tile, _, count = gm._visits(jnp.zeros((3,), jnp.int32), 4, 16)
    assert int(count[0]) == 0 and not tile.any() and int(group.max()) < 3


def test_what_the_kernels_tiling_takes():
    bf16 = jnp.bfloat16
    assert gm.grouped_matmul_tiles(12288, 32, 2688, 1856, bf16)
    assert gm.grouped_matmul_tiles(12288, 32, 1856, 2688, bf16)
    assert not gm.grouped_matmul_tiles(12288, 32, 2688, 1856, jnp.int8)
    assert not gm.grouped_matmul_tiles(64, 4, 100, 128, bf16)
    # a group's matrix in one block where it fits, in column blocks of
    # whole rows of lanes where not
    assert gm._columns(2688, 1856, 2) == 1856
    assert gm._columns(6144, 2048, 2) == 1024
    assert gm._columns(2048, 6144, 2) == 3072
    assert gm.lies_turned(2688, 1856) and not gm.lies_turned(1856, 2688)
    assert not gm.lies_turned(4096, 1280)
    with pytest.raises(ValueError, match="against weights"):
        gm.grouped_matmul(jnp.zeros((16, 8), bf16),
                          jnp.zeros((2, 8, 8), jnp.float32),
                          jnp.zeros((2,), jnp.int32), interpret=True)


#: The shapes the benchmark's cells meet: name -> (sorted rows, held
#: experts, hidden, expert width, whether the kernel takes them).
CELL_SHAPES = {
    "nemotron forward 1024": (6144, 32, 2688, 1856, True),
    "nemotron forward 2048": (12288, 32, 2688, 1856, True),
    "glm-5 forward piece": (16384, 16, 6144, 2048, True),
    "laguna forward 512": (5120, 128, 3072, 1024, True),
    "laguna forward 1024": (10240, 128, 3072, 1024, True),
    "solar forward 256 and tick": (2048, 40, 4096, 1280, True),
    "solar forward 512": (4096, 40, 4096, 1280, True),
    "laguna tick": (640, 128, 3072, 1024, True),
}


@pytest.mark.parametrize("shape", CELL_SHAPES)
def test_the_static_shape_chooses_the_form(shape):
    """``grouped_kernel_takes`` at the eight shapes of PERF.md section
    6 (PR 44), for an expert's matrices in and out alike, and
    ``expert_form``'s name for it. The rule is the kernel's tiling and
    nothing else (``grouped_matmul_tiles``: a 16-bit or 32-bit float
    type, ``k`` whole sublane tiles, a weight block that fits): no
    floor of rows a group, so laguna's tick of 5 sorted rows a held
    expert takes the kernel as the forwards do, and int8 takes
    ``ragged_dot``; a tick's few (row, held expert) pairs go through
    every expert whatever the kernel would take."""
    from pbs_tpu.models import plan as P

    m, groups, d, f, takes = CELL_SHAPES[shape]
    bf16 = jnp.bfloat16
    assert moe.grouped_kernel_takes(m, groups, d, f, bf16) == takes
    assert moe.grouped_kernel_takes(m, groups, f, d, bf16) == takes
    kind = P.MlpKind("experts", f, n_experts=4 * groups, top_k=8,
                     held=(0, groups))
    assert moe.expert_form(m // 8, d, kind, bf16) == (
        "grouped-kernel" if takes else "ragged_dot", m)
    assert moe.expert_form(moe.DENSE_PAIRS // groups, d, kind, bf16) == (
        "every", moe.DENSE_PAIRS // groups)
    assert not moe.grouped_kernel_takes(m, groups, d, f, jnp.int8)


@pytest.mark.parametrize("form", ["relu2", "silu"])
def test_sorted_rows_through_the_kernel_are_the_other_forms_sum(
        form, monkeypatch):
    """``_sorted_rows`` with the kernel where a TPU's lowering puts it
    against ``_sorted_rows`` with ``ragged_dot`` and ``_every_expert``,
    on the matrix-state family's toy expert layer (4 of 8 experts
    held, top-3, 40 rows of which every fifth is no token): the same
    sum and the same counters, in both forms of the MLP."""
    from tests.test_mamba2_serving import (
        FAMILY, LAYERS, SEED, expert_layer, ref, toy)

    c = toy(total=8, held=4)
    h, outer = expert_layer(c)
    kind = dataclasses.replace(FAMILY.layer_plan(c, LAYERS).mlp[0],
                               held=(2, 4), form=form)
    lp = dict(outer, **ref.expert_block(c, ref.seed_word(SEED), 1, 2, 4,
                                        jnp.float32))
    if form == "silu":
        lp["we3"] = jax.random.normal(jax.random.PRNGKey(3),
                                      lp["we1"].shape, jnp.float32) / 7
    valid = jnp.arange(h.shape[0]) % 5 != 0
    dense, dense_counts = moe.held_expert_ffn(h, lp, kind, valid,
                                              jnp.float32)
    monkeypatch.setattr(moe, "DENSE_PAIRS", 0)
    ragged, ragged_counts = moe.held_expert_ffn(h, lp, kind, valid,
                                                jnp.float32)
    calls = []

    def kernel(rows, w, sizes):
        calls.append(rows.shape)
        return gm.grouped_matmul(rows, w, sizes, tile=TILE, interpret=True)

    monkeypatch.setattr(moe, "_grouped_product", kernel)
    got, counts = moe.held_expert_ffn(h, lp, kind, valid, jnp.float32)
    assert len(calls) == (3 if form == "silu" else 2)
    assert float(jnp.abs(got).max()) > 0.1
    assert float(jnp.abs(got - ragged).max()) < 1e-5
    assert float(jnp.abs(got - dense).max()) < 1e-5
    assert np.array_equal(np.asarray(counts), np.asarray(ragged_counts))
    assert np.array_equal(np.asarray(counts), np.asarray(dense_counts))
    assert not np.asarray(got)[::5].any()


@pytest.mark.parametrize("dense_pairs,block_bytes,want", [
    (4096, None, {("experts.every", 48), ("experts.every", 3)}),
    (0, None, {("experts.grouped-kernel", 144),
               ("experts.grouped-kernel", 9)}),
    (0, 0, {("experts.ragged_dot", 144), ("experts.ragged_dot", 9)}),
], ids=["few pairs", "sorted rows", "sorted rows no block fits"])
def test_a_program_says_its_experts_form_as_it_is_traced(
        dense_pairs, block_bytes, want, monkeypatch):
    """One ``HOST_PHASE`` record of no length a form, named
    ``experts.<form>`` with the rows one product is over, each time a
    planned program with expert layers is traced: the toy's prompt
    forward (48 rows, top-3: 144 sorted rows over 4 held experts) and
    its tick (3 lanes: 9 sorted rows, two a held expert, the kernel's
    as the forward's are); ``ragged_dot`` is the name where the
    kernel's tiling refuses the shape (here: no weight block fits)."""
    from pbs_tpu.obs import trace as T
    from tests.test_mamba2_serving import BUCKET, MAX_LEN, SLOTS, program
    from tests.test_setup_records import _host_records, _now

    monkeypatch.setattr(moe, "DENSE_PAIRS", dense_pairs)
    if block_bytes is not None:
        monkeypatch.setattr(gm, "BLOCK_BYTES", block_bytes)
    _, params, prog, _, _ = program()
    cache = jax.eval_shape(lambda: prog.init_cache(SLOTS, MAX_LEN))
    since = _now()
    jax.eval_shape(prog.ingest, params, cache, 0,
                   jnp.zeros((BUCKET,), jnp.int32), 5)
    jax.eval_shape(prog.decode, params, cache,
                   jnp.zeros((SLOTS,), jnp.int32), jnp.ones((SLOTS,), bool))
    marks = [r for r in _host_records(T.Ev.HOST_PHASE, since)
             if T.tag_name(r[2]).startswith("experts.")]
    assert {(T.tag_name(r[2]), r[5]) for r in marks} == want
    assert all(r[3] < 1_000_000 and r[4] == 0 for r in marks)
