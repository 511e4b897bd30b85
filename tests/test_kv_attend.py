"""The one-pass attention of the decode tick over K and V
(``pbs_tpu/ops/kv_attend.py``) in Pallas interpret mode, at toy widths
and blocks of 16 positions: against the ``jax.numpy`` form the CPU
lowers and every prompt forward runs
(``models/slot_programs.py::_grouped_attention``) under the decode's own
mask, at the cells' head shapes; then through the two call sites, a toy
scan engine and a toy planned engine, whose greedy tokens are the same
with the kernel and without. What the chip's compiler makes of it is
``tests/test_tpu_compile.py``'s to say, and what the chip computes
``tpu_tests/``'s."""

import dataclasses
import functools
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pbs_tpu.models import mla
from pbs_tpu.models import plan as P
from pbs_tpu.models import slot_programs
from pbs_tpu.models.serving import ContinuousBatcher
from pbs_tpu.models.slot_programs import _grouped_attention, slot_program
from pbs_tpu.models.transformer import TransformerConfig, init_params
from pbs_tpu.obs.trace import Ev
from pbs_tpu.ops.kv_attend import (
    BLOCKS, attend_block, kv_attend, kv_attend_tiles)
from pbs_tpu.ops.mla_attend import mla_attend

HD, T, TK = 16, 64, 16
TOL = 2e-6

#: (KV heads, query heads a KV head) of the cells: mistral, laguna's
#: full layers, solar, internlm2, nemotron, jamba.
HEADS = [(8, 4), (8, 6), (8, 8), (8, 2), (2, 16), (1, 20)]
CURSORS = {
    "cursor-0": (0, 5, 20),
    "inside-a-block": (7, 22, 41),
    "block-last-row": (15, 31, 47),
    "block-first-row": (16, 32, 48),
    "cache-last-row": (T - 1, T - 1, 9),
    "idle-beside-full": (0, T - 1, 0),
}


def rows(B, nkv, g, seed=0, kept=T, layers=None, dtype=jnp.float32):
    """Seeded queries (B, H, hd) and caches (B, kept, nkv, hd), or
    ``layers`` of them stacked."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    cache = (B, kept, nkv, HD) if layers is None \
        else (layers, B, kept, nkv, HD)
    return (jax.random.normal(ks[0], (B, nkv * g, HD), dtype),
            jax.random.normal(ks[1], cache, dtype),
            jax.random.normal(ks[2], cache, dtype))


def numpy_way(q, k, v, row_pos, ring=False):
    """``_softmax_layer``'s decode mask (a ring entry is live once
    written, always after a lap) through ``_grouped_attention``."""
    pos = jnp.asarray(row_pos, jnp.int32)[:, None]
    K = k.shape[1]
    seen = (jnp.arange(K)[None, :] <= pos) | ((pos >= K) if ring else False)
    return np.asarray(_grouped_attention(
        q[:, None], k, v, seen[:, None, :], q.dtype)[:, 0])


def kernel(q, k, v, row_pos, layer=None, tk=TK):
    args = () if layer is None else (jnp.int32(layer),)
    return np.asarray(jax.jit(functools.partial(
        kv_attend, block=tk, interpret=True))(
            q, k, v, jnp.asarray(row_pos, jnp.int32), *args))


def gap(got, want) -> float:
    return float(np.abs(got - want).max()) / float(np.abs(want).max())


@pytest.mark.parametrize("nkv,g", HEADS)
@pytest.mark.parametrize("case", list(CURSORS))
def test_the_kernel_attends_as_the_numpy_form_does(case, nkv, g):
    """Every lane's output within a few float32 roundings of the
    ``jax.numpy`` form's, wherever its cursor stands in a block, at
    every cell's head shape (query heads padded to the eight at 20)."""
    row_pos = CURSORS[case]
    q, k, v = rows(len(row_pos), nkv, g, seed=len(case) + nkv)
    got, want = kernel(q, k, v, row_pos), numpy_way(q, k, v, row_pos)
    assert got.shape == want.shape == (len(row_pos), nkv * g, HD)
    assert gap(got, want) <= TOL
    for b, pos in enumerate(row_pos):
        if pos == 0:  # one position live: the softmax is that row
            np.testing.assert_allclose(
                got[b], np.repeat(np.asarray(v[b, 0]), g, axis=0), rtol=1e-6)


@pytest.mark.parametrize("nkv,g", [(8, 9), (2, 16)])
@pytest.mark.parametrize("row_pos", [(3, 17, 31), (32, 45, 200), (31, 32, 0)],
                         ids=["unlapped", "lapped", "at-the-lap"])
def test_a_ring_is_live_up_to_the_cursor_and_whole_once_lapped(
        row_pos, nkv, g):
    """A window layer's ring of 32 entries: up to the cursor before the
    first lap, every entry after it, as ``_softmax_layer``'s mask
    says."""
    q, k, v = rows(len(row_pos), nkv, g, seed=5, kept=32)
    assert gap(kernel(q, k, v, row_pos),
               numpy_way(q, k, v, row_pos, ring=True)) <= TOL


@pytest.mark.parametrize("layer", [0, 2])
def test_a_stacked_cache_is_read_at_the_layers_index(layer):
    """The dense layer scan's carry, ``(L, B, T, nkv, hd)`` and the
    layer's index: the layer's own keys and values, no other's."""
    row_pos = (40, 3, 63)
    q, k, v = rows(3, 2, 4, seed=9, layers=3)
    assert gap(kernel(q, k, v, row_pos, layer=layer),
               numpy_way(q, k[layer], v[layer], row_pos)) <= TOL


@pytest.mark.parametrize("nkv,g", [(8, 4), (1, 20)])
def test_a_block_past_a_lanes_cursor_is_never_read(nkv, g):
    """The positions of every block behind the one a lane's cursor is
    in are poisoned with NaN in both caches: the kernel's output stays
    finite and equal to the clean caches' (the ``jax.numpy`` form
    multiplies the poison by its zeros and returns NaN)."""
    row_pos = (0, TK - 1, 20, T - TK - 1)
    q, k, v = rows(len(row_pos), nkv, g, seed=3)
    dead = (jnp.arange(T)[None, :] // TK
            > jnp.asarray(row_pos)[:, None] // TK)[..., None, None]
    assert np.asarray(dead).any(axis=(1, 2, 3)).all()
    clean = kernel(q, k, v, row_pos)
    poisoned = [jnp.where(dead, jnp.nan, t) for t in (k, v)]
    assert np.isnan(numpy_way(q, *poisoned, row_pos)).all()
    got = kernel(q, *poisoned, row_pos)
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, clean)


def test_bfloat16_rows_give_the_numpy_forms_bfloat16():
    """At the cells' own precision (bfloat16 queries and caches,
    float32 scores, sums and accumulator, probabilities rounded to
    bfloat16 for the values' product) the two forms differ by the
    ``jax.numpy`` form's bfloat16 scores and by the rounding of the
    probabilities against a block's maximum in place of the row's: a
    bfloat16 unit of the output or two."""
    row_pos = (63, 20, 41)
    q, k, v = rows(3, 8, 4, seed=5, dtype=jnp.bfloat16)
    got = kernel(q, k, v, row_pos).astype(np.float32)
    want = numpy_way(q, k, v, row_pos).astype(np.float32)
    assert np.isfinite(got).all()
    assert gap(got, want) <= 2 ** -6


@pytest.mark.parametrize("nkv,hd,kept,block", [
    (8, 128, 1024, 256),     # mistral's and internlm2's cells
    (8, 128, 2048, 256),     # solar's, laguna's full layers
    (8, 128, 512, 256),      # laguna's ring (which stays on jax.numpy)
    (2, 128, 3072, 512),     # nemotron's
    (1, 128, 2560, 0),       # jamba's one KV head stays on jax.numpy
    (8, 128, 1000, 0),       # no whole blocks
    (8, 64, 1024, 0),        # a head of half a row of lanes
    (2, 16, 64, 0),          # these tests' toy widths
    (6, 128, 1024, 0),       # KV heads no power of two
])
def test_the_shapes_decide_which_lowering_runs(nkv, hd, kept, block):
    assert kv_attend_tiles(nkv, hd, kept) is bool(block)
    if block:
        assert attend_block(kept, nkv) == block
    assert BLOCKS == (512, 256, 128)


def test_a_cache_of_no_whole_blocks_is_refused():
    q, k, v = rows(1, 2, 2)
    with pytest.raises(ValueError, match="whole blocks"):
        kv_attend(q, k, v, jnp.zeros((1,), jnp.int32), block=24,
                  interpret=True)
    q, k, v = rows(1, 3, 2)
    with pytest.raises(ValueError, match="power of two"):
        kv_attend(q, k, v, jnp.zeros((1,), jnp.int32), block=16,
                  interpret=True)


# -- the two call sites -------------------------------------------------------

PROMPTS = [[5, 9, 2], [7] * 10, [3, 1, 4, 1, 5, 9, 2, 6], [11, 12]]
MAX_LEN, BUCKET, NEW = 48, 12, 20


def dense_model(vocab=97):
    cfg = TransformerConfig(vocab=vocab, d_model=48, n_layers=3, n_heads=4,
                            n_kv_heads=2, d_ff=96, max_seq=MAX_LEN,
                            dtype=jnp.float32)
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def planned_model():
    """A full layer without rotary under an elementwise gate, a ring of
    8 and a second full layer with rotary, over dense MLPs."""
    cfg = dataclasses.replace(dense_model()[0], layer_plan=P.LayerPlan(
        attn=(P.AttnKind("nope", 4, None, None, wide_gate=True),
              P.AttnKind("ring", 6, 8, P.Rope()),
              P.AttnKind("full", 4, None, P.Rope())),
        mlp=(P.MlpKind("dense", 96),),
        layers=((0, 0), (1, 0), (2, 0))))
    return cfg, slot_program(cfg).init_params(jax.random.PRNGKey(1))


def latent_model():
    """Two latent layers that choose 8 of their positions around a full
    layer, over dense MLPs."""
    rope = P.Rope(rotary_dim=8, interleave=True)
    cfg = dataclasses.replace(dense_model()[0], layer_plan=P.LayerPlan(
        attn=(P.MlaKind("latent", 4, 16, 32, 8, 8, 8, 2, 8, 8, rope),
              P.AttnKind("full", 4, None, P.Rope())),
        mlp=(P.MlpKind("dense", 96),),
        layers=((0, 0), (1, 0), (0, 0))))
    return cfg, slot_program(cfg).init_params(jax.random.PRNGKey(2))


def serve(engine, prompts=PROMPTS, max_new=NEW):
    done = {}
    for p in prompts:
        engine.submit(p, max_new)
    while engine.has_work():
        done.update({c.request_id: list(c.tokens) for c in engine.step()})
    return [done[i] for i in range(len(prompts))]


def engine(model, bucket=BUCKET, **kw):
    return ContinuousBatcher(*model, n_slots=3, prompt_bucket=bucket,
                             max_len=MAX_LEN, **kw)


def as_on_a_chip(monkeypatch, form):
    """The predicate (``slot_programs.live_layers``) takes the toy
    shapes at blocks of 16; ``form`` "kernel" puts the interpreted
    kernel where a TPU's lowering puts the compiled one, "numpy" leaves
    ``_cursor_attention`` to lower for the CPU (the branch a TPU would
    take is traced all the same, at blocks of 16)."""
    calls = []
    for name, fn in (
            ("kv_attend_tiles", lambda nkv, hd, kept: kept % TK == 0),
            ("kv_attend_block", lambda kept, nkv: TK),
            ("mla_attend_tiles", lambda heads, kept, rank: kept % TK == 0),
            ("mla_attend_block", lambda kept: TK),
            ("_kernel_attend", functools.partial(
                kv_attend, block=TK, interpret=True))):
        monkeypatch.setattr(slot_programs, name, fn)
    monkeypatch.setattr(mla, "_kernel_attend", functools.partial(
        mla_attend, block=TK, interpret=True))
    if form == "kernel":
        def attend(q, k, v, at, layer, dt, width=None):
            calls.append((k.shape, layer is not None))
            return kv_attend(q[:, 0], k, v, at, layer, block=TK,
                             scale=width and width ** -0.5,
                             interpret=True)[:, None]

        monkeypatch.setattr(slot_programs, "_cursor_attention", attend)
    return calls


@pytest.mark.parametrize("form", ["kernel", "numpy"])
@pytest.mark.parametrize("model", [dense_model, planned_model],
                         ids=["scan", "planned"])
def test_an_engine_serves_the_same_tokens_with_the_kernel(
        model, form, monkeypatch):
    """Greedy tokens of four prompts through three slots (a lane idle
    beside busy ones, a slot taken twice), the decode's attention
    through ``_cursor_attention`` (the kernel interpreted, or its own
    ``jax.numpy`` lowering) and as it was: the same. The scan hands the
    kernel its stacked cache and the layer's index; the planned stack a
    cache a full layer, and its ring stays on the ``jax.numpy`` form."""
    cfg, params = model()
    want = serve(engine((cfg, params)))
    assert all(len(t) == NEW for t in want)
    calls = as_on_a_chip(monkeypatch, form)
    assert serve(engine((cfg, params))) == want
    if form == "kernel":
        stacked = model is dense_model
        assert calls and all(
            shape[-3:] == (MAX_LEN, 2, 12) and layer is stacked
            for shape, layer in calls)
        # traced once a program: three layers of the scan's body are
        # one call, the planned stack's two full layers two
        assert len(calls) == (1 if stacked else 2)


def test_a_mesh_of_more_devices_keeps_the_numpy_form(monkeypatch):
    """``place_cache`` on a tensor axis of two: the scan's decode gives
    ``_slot_forward`` no ``active``, and nothing reaches the kernel."""
    from pbs_tpu.serve.partition import make_serve_mesh, place

    cfg, params = dense_model(vocab=96)
    calls = as_on_a_chip(monkeypatch, "kernel")
    mesh = make_serve_mesh(tp=2, dp=1)
    eng = engine((cfg, place(params, mesh)), mesh=mesh)
    assert len(eng.program.devices) == 2 and calls == []
    assert eng.program.live_layers(eng.cache) == {}
    one = make_serve_mesh(tp=1, dp=1)
    assert len(engine((cfg, place(params, one)),
                      mesh=one).program.devices) == 1
    assert calls


def host_marks(since):
    from pbs_tpu.obs import trace as T
    from tests.test_setup_records import _host_records

    return {(T.tag_name(r[2]), r[5])
            for r in _host_records(T.Ev.HOST_PHASE, since)
            if T.tag_name(r[2]).startswith("attn.")}


class _Chip:
    platform = "tpu"


def mesh_of_chips(n: int):
    """All a program asks of the mesh its cache will lie on, the
    devices: ``n`` TPUs."""
    return types.SimpleNamespace(
        devices=np.array([_Chip() for _ in range(n)], dtype=object))


@pytest.mark.parametrize("model,takes,chips,want", [
    (dense_model, False, 1, {("attn.jnp", 3)}),
    (dense_model, True, 0, {("attn.jnp", 3)}),
    (dense_model, True, 1, {("attn.live-kernel", 3)}),
    (dense_model, True, 2, {("attn.jnp", 3)}),
    (planned_model, False, 1, {("attn.jnp", 3)}),
    (planned_model, True, 0, {("attn.jnp", 3)}),
    (planned_model, True, 1, {("attn.live-kernel", 2), ("attn.jnp", 1)}),
], ids=["scan", "scan-tiled-cpu", "scan-tiled", "scan-tiled-mesh",
        "planned", "planned-tiled-cpu", "planned-tiled"])
def test_a_decode_says_the_form_its_attention_runs_in(
        model, takes, chips, want, monkeypatch):
    """One ``HOST_PHASE`` record of no length a form, ``attn.live-kernel``
    or ``attn.jnp`` with the layers it covers, each time a decode
    program is traced; a prompt forward writes none. The kernel's name
    is said where ``live_layers`` as lowered (the engine's
    ``ENG_ATTEND``) says it runs: shapes its tiling takes on one TPU,
    not the CPU these tests run on (``chips`` 0), whatever was
    traced."""
    from tests.test_setup_records import _now

    if takes:
        as_on_a_chip(monkeypatch, "numpy")
    cfg, params = model()
    prog = slot_program(cfg, mesh=mesh_of_chips(chips) if chips else None)
    assert [d.platform for d in prog.devices] == (["tpu"] * chips or ["cpu"])
    cache = jax.eval_shape(lambda: prog.init_cache(3, MAX_LEN))
    since = _now()
    jax.eval_shape(prog.ingest, params, cache, 0,
                   jnp.zeros((BUCKET,), jnp.int32), 5)
    assert host_marks(since) == set()
    jax.eval_shape(prog.decode, params, cache, jnp.zeros((3,), jnp.int32),
                   jnp.ones((3,), bool))
    assert host_marks(since) == want
    assert len(prog.live_layers(cache, lowered=True)) == sum(
        n for form, n in want if form == "attn.live-kernel")
    # the forwards go by the same answer less the platform
    assert len(prog.live_layers(cache)) == (
        0 if chips > 1 or not takes else 3 if model is dense_model else 2)


def records(eng, event):
    return [r for r in eng.trace.peek().tolist() if r[1] == int(event)]


@pytest.mark.parametrize("model", ["told", "scan", "latent"])
def test_eng_attend_counts_the_blocks_fetched_from_the_slot_table(
        model, monkeypatch):
    """One record a dispatched decode, stamped like its ``ENG_DECODE``:
    busy lanes, their live positions, the (lane, block) pairs the
    kernel fetches over its layers (a cursor at 15 counts one block of
    16 and at 16 two; an idle lane one; a ring of 32 no more than two)
    and the pairs the caches have. A CPU runs the ``jax.numpy`` form
    and writes none. ``told``: the engine is told of two layers of 48
    positions and one ring of 32 as if a chip held them. ``scan`` and
    ``latent``: the table is ``live_layers``' own answer for a cache on
    one TPU (three layers of 48 positions; two latent layers around a
    full one), and a latent layer's ``ENG_SELECT`` blocks come from the
    same count as a full layer's ``ENG_ATTEND``."""
    make = latent_model if model == "latent" else dense_model
    eng = engine(make(), bucket=40)
    assert eng.program.live_layers(eng.cache, lowered=True) == {} == eng._live
    serve(eng, [[1] * 14], 5)
    assert records(eng, Ev.ENG_ATTEND) == []
    assert all(r[7] == 0 for r in records(eng, Ev.ENG_SELECT))
    if model == "told":
        eng = engine(make(), bucket=40)
        eng._live = {("kv", 32, 16): 1, ("kv", 48, 16): 2}
    else:
        as_on_a_chip(monkeypatch, "numpy")
        monkeypatch.setattr(slot_programs, "_placed_on",
                            lambda mesh: (_Chip(),))
        eng = engine(make(), bucket=40)
        assert eng._live == {"scan": {("kv", 48, 16): 3}, "latent": {
            ("kv", 48, 16): 1, ("latent", 48, 16): 2}}[model]
    lengths = (40, 14)
    serve(eng, [[1] * n for n in lengths], 5)
    attends = records(eng, Ev.ENG_ATTEND)
    decodes = {r[0] for r in records(eng, Ev.ENG_DECODE)}
    selects = [r for r in records(eng, Ev.ENG_SELECT) if r[0] in decodes]
    # four decodes after each prefill's first token, two of three lanes
    assert len(attends) == 4 and {r[0] for r in attends} <= decodes
    for i, r in enumerate(attends):
        live = [n + 1 + i for n in lengths]
        # cursors 40-43 lie in the third block (the ring's second);
        # 14, 15 | 16, 17; the idle lane's one block a layer
        short = (1, 1, 2, 2)[i]
        full = 3 + short + 1
        assert r[3:8] == [2, sum(live)] + {
            "told": [(2 + short + 1) + 2 * full, 3 * (2 + 2 * 3), 3],
            "scan": [3 * full, 3 * 3 * 3, 3],
            "latent": [full, 3 * 3, 1]}[model]
    if model == "latent":  # the busy lanes' blocks of one latent layer
        assert [r[0] for r in selects] == [r[0] for r in attends]
        assert [r[7] for r in selects] == [
            r[5] - 1 for r in attends] == [4, 4, 5, 5]
    else:
        assert records(eng, Ev.ENG_SELECT) == []
