"""What XLA:TPU makes of the dense slot forward, compiled for a v5e
that is described and not attached (libtpu is installed; nothing runs,
no chip is needed): the copies a CPU compile cannot show.

The topology is described inside a fixture, never at import: one
process at a time may load libtpu, and every pytest-xdist worker
imports every test file. All such compiles live in this one file, so
one worker loads the library."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from pbs_tpu.models import TransformerConfig, init_params
from pbs_tpu.models import plan as P
from pbs_tpu.models.quant import quantize_weights
from pbs_tpu.models.slot_programs import (
    _slot_forward, ingest_slot_prompt, init_slot_cache, slot_program)
from pbs_tpu.parallel.sharding import slot_cache_kv_sharding
from pbs_tpu.serve.partition import make_serve_mesh, rule_shardings
from pbs_tpu.telemetry.hlo import dims, materialised, written

# mistral-7b's widths, three layers of them: the copies were of a
# layer's projections, whatever the depth.
CFG = TransformerConfig(
    vocab=32768, d_model=4096, n_layers=3, n_heads=32, n_kv_heads=8,
    d_ff=14336, max_seq=256, rope_theta=1e6, dtype=jnp.bfloat16)
SLOTS = 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu to compile with
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to JAX's persistent
    # cache and cannot be read back without the chip (a warning every
    # time after the first): the cache is off around these tests.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shapes(topo, tp: int, int8: bool):
    """The serving tree and the slot cache as shapes, laid on one
    described chip or on a tensor axis of four by the rule table."""
    params = jax.eval_shape(lambda: jax.tree.map(
        lambda x: x.astype(CFG.dtype),
        init_params(CFG, jax.random.PRNGKey(0))))
    if int8:
        params = jax.eval_shape(quantize_weights, params)
    cache = jax.eval_shape(
        lambda: init_slot_cache(CFG, SLOTS, CFG.max_seq))
    if tp == 1:
        one = SingleDeviceSharding(topo.devices[0])
        where, kv, rest = jax.tree.map(lambda _: one, params), one, one
    else:
        mesh = make_serve_mesh(tp=tp, dp=1, devices=topo.devices[:tp])
        where = rule_shardings(params, mesh)
        kv = slot_cache_kv_sharding(mesh)
        rest = NamedSharding(mesh, PartitionSpec())
    lay = lambda x, s: jax.ShapeDtypeStruct(  # noqa: E731
        x.shape, x.dtype, sharding=s)
    return (jax.tree.map(lay, params, where),
            {"k": lay(cache["k"], kv), "v": lay(cache["v"], kv),
             "pos": lay(cache["pos"], rest)},
            lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32,
                                                sharding=rest))


def _weightlike(tp: int) -> set:
    """One layer's wq, wk or wv (wo is wq's size) on one device: (d,
    H * hd) or split into heads (d, H, hd), its axes in any order."""
    d, hd = CFG.d_model, CFG.head_dim
    out = set()
    for heads in (CFG.n_heads // tp, CFG.n_kv_heads // tp):
        out |= {tuple(sorted((d, heads * hd))), tuple(sorted((d, heads, hd)))}
    return out


@pytest.mark.parametrize("case,rows,tp,int8", [
    ("decode", 1, 1, False),
    ("verify-window", 5, 1, False),
    ("prefill", 256, 1, False),
    ("decode-int8", 1, 1, True),
    ("decode-tp4", 1, 4, False),
    ("decode-int8-tp4", 1, 4, True),
])
def test_attention_projections_are_read_where_they_lie(topo, case, rows,
                                                       tp, int8):
    """No instruction of the compiled layer scan writes a tensor that
    is one layer's attention projection, sliced out of the stack,
    transposed or dequantised whole: the q, k and v products read the
    stacked leaf as the MLP's always did. (XLA:TPU moves a reshape to
    heads through the product onto the weight; PERF.md section 6, PR
    32.) On a tensor axis of four the collectives are the layer's two
    reductions and nothing gathers a weight."""
    params, cache, i32 = _shapes(topo, tp, int8)
    if case == "prefill":
        fn = lambda p, c, prompt: ingest_slot_prompt(  # noqa: E731
            CFG, p, c, 0, prompt, 7)[:2]
        args = (params, cache, i32(rows))
    else:
        fn = lambda p, c, tok: _slot_forward(  # noqa: E731
            CFG, p, tok, c, c["pos"])[:2]
        args = (params, cache, i32(SLOTS, rows))
    ops = materialised(jax.jit(fn, donate_argnums=(1,)).lower(
        *args).compile().as_text())
    # The reading sees into the scan: an MLP product's result is there.
    width = CFG.d_ff // tp
    assert any(dims(shape)[-1:] == (width,) and op == "fusion"
               for _, op, shape in ops), case
    moved = written(ops, _weightlike(tp))
    assert not moved, moved
    gathers = [op for op in written(ops, _weightlike(1) | _weightlike(tp))
               if op[1].startswith("all-gather")]
    assert not gathers, gathers


def _attend_kernels(hlo: str, scope: str = "attn.full") -> list[str]:
    """The compiled program's calls of the one-pass attention over K
    and V (``ops/kv_attend.py``), each under ``scope``."""
    kernels = [ln for ln in hlo.splitlines()
               if 'custom_call_target="tpu_custom_call"' in ln
               and "kv_attend" in ln]
    assert all(re.search(rf'op_name="[^"]*/{re.escape(scope)}/[^"]*kv_attend',
                         ln) for ln in kernels), [ln[:300] for ln in kernels]
    return kernels


def _nothing_moves_a_layers_keys(ops, lanes: int, kept: int, nkv: int):
    """No instruction but the write of the tick's new position writes
    a tensor of one layer's K or V (a copy, a transpose to heads-major,
    a slice out of a stack), and none a
    float32 tensor a lane and a position long (the ``jax.numpy`` form's
    scores and probabilities)."""
    moved = [op for op in written(
        ops, {tuple(sorted((lanes, kept, nkv, 128)))})
        if "dynamic-update-slice" not in op[0]]  # the new position, in place
    assert not moved, moved
    wide = [op for op in ops if op[2].startswith("f32")
            and {lanes, kept} <= set(dims(op[2]))]
    assert not wide, wide


@pytest.mark.parametrize("case", ["decode", "decode-no-lanes", "prefill"])
def test_the_scans_decode_streams_live_blocks_out_of_the_stack(topo, case):
    """The dense layer scan's decode tick as the engine runs it
    (``_ScanProgram.decode``: mistral's widths, 16 slots of 1,024
    positions) holds the one-pass attention ``kv_attend`` under
    ``attn.full``, once in the scan's body, fed the stacked cache
    itself: no instruction slices, copies or transposes a layer's K or
    V (XLA's two ``dynamic-slice`` fusions a layer of the ``jax.numpy``
    form are gone) and no float32 scores over 1,024 columns are
    written. Without the lanes' word (any other caller of
    ``_slot_forward``; a cache on a mesh) and in a prompt forward the
    ``jax.numpy`` form stays, and no kernel."""
    cfg = dataclasses.replace(CFG, max_seq=1024)
    one = SingleDeviceSharding(topo.devices[0])
    lay = lambda tree: jax.tree.map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one), tree)
    prog = slot_program(cfg)
    params = lay(jax.eval_shape(lambda: jax.tree.map(
        lambda x: x.astype(cfg.dtype),
        prog.init_params(jax.random.PRNGKey(0)))))
    cache = lay(jax.eval_shape(lambda: prog.init_cache(SLOTS, cfg.max_seq)))
    i32 = lambda *shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.int32, sharding=one)
    if case == "prefill":
        fn = lambda p, c, prompt: prog.ingest(  # noqa: E731
            p, c, 0, prompt, 7)[:2]
        args = (params, cache, i32(256))
    elif case == "decode":
        fn = lambda p, c, tok, active: prog.decode(  # noqa: E731
            p, c, tok, active)[:2]
        args = (params, cache, i32(SLOTS), jax.ShapeDtypeStruct(
            (SLOTS,), bool, sharding=one))
    else:
        fn = lambda p, c, tok: _slot_forward(  # noqa: E731
            cfg, p, tok, c, c["pos"])[:2]
        args = (params, cache, i32(SLOTS, 1))
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
    hlo = compiled.as_text()
    ops = materialised(hlo)
    if case != "decode":
        assert "tpu_custom_call" not in hlo
        if case == "decode-no-lanes":
            assert written(ops, {tuple(sorted((SLOTS, 1024, 8, 128)))})
        return
    assert len(_attend_kernels(hlo)) == 1
    _nothing_moves_a_layers_keys(ops, SLOTS, 1024, 8)
    m = compiled.memory_analysis()
    stack = 2 * cfg.n_layers * SLOTS * 1024 * 8 * 128 * 2
    assert m.alias_size_in_bytes >= stack
    # beyond its arguments and the logits: no second cache, no layer's
    assert m.temp_size_in_bytes < SLOTS * 1024 * 8 * 128 * 2, \
        m.temp_size_in_bytes >> 20


# One delta-rule layer at Solar-Open2's published widths (64 heads of
# 128, kernel 4, hidden 4096) over a small dense MLP: what is checked
# is the layer's recurrent state, 4 MiB a slot.
KDA_CFG = TransformerConfig(
    vocab=1024, d_model=4096, n_layers=1, n_heads=64, n_kv_heads=8,
    d_ff=256, max_seq=1024, dtype=jnp.bfloat16, head_size=128,
    layer_plan=P.LayerPlan((P.KdaKind("kda", 64, 128, conv=4, rank=128),),
                           (P.MlpKind("dense", 256),), ((0, 0),)))
KDA_SLOTS = 32


@pytest.mark.parametrize("case", ["decode", "ingest"])
def test_a_recurrent_state_is_updated_where_it_lies(topo, case):
    """The decode step over every lane and the chunked ingestion of a
    512-row prompt compile for the chip (the triangular solve of a
    chunk included), donate the cache and write the state in place:
    beyond its arguments the decode step needs less than half of the
    layer's state (a second copy of it would be the whole) and the
    ingestion a chunk's pairwise decays (64 heads x 64 x 64 positions x
    128 channels, 128 MiB) and no more, whatever the slots. **The
    decode step passes over the state once**: one instruction of the
    program takes or gives a tensor of the state's size, it is the
    Mosaic kernel ``kda_state_step`` under ``attn.kda/kda.state`` (the
    program is lowered for a TPU, whatever the process's own backend),
    its result aliased to the state it was given, and no fusion or copy
    of that size stands before or behind it. The ingestion's one writer
    is a fusion, as it was."""
    one = SingleDeviceSharding(topo.devices[0])
    lay = lambda tree: jax.tree.map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one), tree)
    prog = slot_program(KDA_CFG)
    params = lay(jax.eval_shape(lambda: jax.tree.map(
        lambda x: x.astype(KDA_CFG.dtype),
        prog.init_params(jax.random.PRNGKey(0)))))
    cache = lay(jax.eval_shape(
        lambda: prog.init_cache(KDA_SLOTS, KDA_CFG.max_seq)))
    i32 = lambda *shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.int32, sharding=one)
    if case == "decode":
        fn = lambda p, c, tok, active: prog.decode(  # noqa: E731
            p, c, tok, active)[:2]
        args = (params, cache, i32(KDA_SLOTS), jax.ShapeDtypeStruct(
            (KDA_SLOTS,), bool, sharding=one))
    else:
        fn = lambda p, c, slot, prompt, plen: prog.ingest(  # noqa: E731
            p, c, slot, prompt, plen)[:2]
        args = (params, cache, i32(), i32(512), i32())
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
    state = KDA_SLOTS * 64 * 128 * 128 * 4
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= state
    assert m.temp_size_in_bytes + m.output_size_in_bytes \
        - m.alias_size_in_bytes < (state // 2 if case == "decode"
                                   else 160 << 20)
    hlo = compiled.as_text()
    moved = written(materialised(hlo), {(KDA_SLOTS, 64, 128, 128)})
    if case == "ingest":
        # the one writer is the update itself, fused with its arithmetic
        assert all(op[1] == "fusion" for op in moved), moved
        assert len(moved) <= 1, moved
        return
    assert not moved, moved         # array-valued: a fusion, a copy
    size = f"f32[{KDA_SLOTS},64,128,128]"
    entry = hlo[hlo.index("ENTRY "):]
    touching = [ln for ln in entry.splitlines()[1:]
                if size in ln and not re.search(
                    r" (parameter|get-tuple-element|tuple|bitcast)\(", ln)]
    assert len(touching) == 1, [ln[:160] for ln in touching]
    kernel, = touching
    assert 'custom_call_target="tpu_custom_call"' in kernel
    assert re.search(r'op_name="[^"]*/attn\.kda/kda\.state/[^"]*'
                     r'kda_state_step', kernel), kernel[:300]
    assert re.search(r"output_to_operand_aliasing=\{\{1\}: \(\d+, \{\}\)\}",
                     kernel)


# One state-space layer and one multi-query attention layer at
# AI21-Jamba2-3B's published widths (hidden 2560, d_inner 5120, d_state
# 16, dt_rank 160, kernel 4; 20 query heads on one KV head of 128) over
# a small dense MLP, under the published vocabulary's tied embedding:
# what is checked is the layer's state and the head.
SSM_CFG = TransformerConfig(
    vocab=65536, d_model=2560, n_layers=2, n_heads=20, n_kv_heads=1,
    d_ff=256, max_seq=2560, norm_eps=1e-6, dtype=jnp.bfloat16,
    head_size=128, tie_embeddings=True,
    layer_plan=P.LayerPlan(
        (P.MambaKind("mamba", 5120, 16, 160, conv=4),
         P.AttnKind("full", 20, None, None)),
        (P.MlpKind("dense", 256),), ((0, 0), (1, 0))))
SSM_SLOTS = 64


@pytest.mark.parametrize("case", ["decode", "ingest"])
def test_a_state_space_layer_at_published_widths(topo, case):
    """The decode step over every lane and the ingestion of a 2048-row
    prompt (the scan's two loops, the 2048 x 2048 scores of 20 heads)
    compile for the chip, donate the cache and write the state in
    place: beyond its arguments and the logits it returns the decode
    step needs less than a quarter of the layer's state, and no
    instruction but a fusion
    writes a tensor of the state's size (a copy would be a second pass
    over it). **The tied head reads the embedding where it lies**: no
    instruction writes a tensor of the embedding's size, transposed or
    not. **The ingestion's scan is one kernel**: a Mosaic call under
    ``attn.mamba/mamba.scan`` (the program is lowered for a TPU,
    whatever the process's own backend) that holds a tile of channels'
    state in VMEM, so nothing writes a step's ``(chunks, d_state,
    d_inner)``, a chunk's ``(chunk, d_state, d_inner)`` or the prompt's
    671 MB of decays, and the program needs less beyond its arguments
    than the ``jax.numpy`` scan's two loops did. Two state-space
    layers' call sites share one lowered kernel function: a Pallas
    kernel is lowered to its Mosaic module in Python at every start,
    once a rung and not once a layer."""
    one = SingleDeviceSharding(topo.devices[0])
    lay = lambda tree: jax.tree.map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one), tree)
    prog = slot_program(SSM_CFG)
    params = lay(jax.eval_shape(lambda: jax.tree.map(
        lambda x: x.astype(jnp.float32 if x.ndim < 2 or x.shape[0] == 16
                           else SSM_CFG.dtype),
        prog.init_params(jax.random.PRNGKey(0)))))
    assert "head" not in params
    cache = lay(jax.eval_shape(
        lambda: prog.init_cache(SSM_SLOTS, SSM_CFG.max_seq)))
    i32 = lambda *shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.int32, sharding=one)
    if case == "decode":
        fn = lambda p, c, tok, active: prog.decode(  # noqa: E731
            p, c, tok, active)[:2]
        args = (params, cache, i32(SSM_SLOTS), jax.ShapeDtypeStruct(
            (SSM_SLOTS,), bool, sharding=one))
    else:
        fn = lambda p, c, slot, prompt, plen: prog.ingest(  # noqa: E731
            p, c, slot, prompt, plen)[:2]
        args = (params, cache, i32(), i32(2048), i32())
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
    state = SSM_SLOTS * 16 * 5120 * 4
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= state
    beyond = m.temp_size_in_bytes + m.output_size_in_bytes \
        - m.alias_size_in_bytes
    # ingestion: 320 MiB of float32 scores, the in-projection's output,
    # a handful of (2048, 5120) float32 rows; no carry of 32 chunks'
    # states, no stacked output (323 MiB read, and 345 with the
    # ``jax.numpy`` scan in this place)
    logits = SSM_SLOTS * SSM_CFG.vocab * 4
    assert beyond < (logits + state // 4 if case == "decode"
                     else 335 << 20), beyond >> 20
    hlo = compiled.as_text()
    ops = materialised(hlo)
    assert not written(ops, {(2560, 65536)})
    moved = written(ops, {(16, SSM_SLOTS, 5120)})
    assert all(op[1] in ("fusion", "dynamic-update-slice")
               for op in moved), moved
    assert len(moved) <= 2, moved
    assert not written(ops, {(16, 2048, 5120)})     # (T, d_state, d_inner)
    if case == "decode":
        assert "mamba_prompt_scan" not in hlo
        # one KV head: the attention layer keeps the ``jax.numpy`` form
        # (``kv_attend_tiles``; with the kernel in this program XLA
        # staged the state through VMEM in copies, PERF.md 6, PR 45)
        assert not _attend_kernels(hlo)
        return
    assert not written(ops, {(32, 16, 5120), (64, 16, 5120)})
    kernels = [ln for ln in hlo.splitlines()
               if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(kernels) == 1, len(kernels)
    assert re.search(r'op_name="[^"]*/attn\.mamba/mamba\.scan/[^"]*'
                     r'mamba_prompt_scan', kernels[0]), kernels[0][:300]
    twice = dataclasses.replace(SSM_CFG, n_layers=3, layer_plan=P.LayerPlan(
        SSM_CFG.layer_plan.attn, SSM_CFG.layer_plan.mlp,
        ((0, 0), (0, 0), (1, 0))))
    prog = slot_program(twice)
    shapes = lambda f: lay(jax.eval_shape(f))  # noqa: E731
    lowered = jax.jit(lambda p, c, slot, prompt, plen: prog.ingest(
        p, c, slot, prompt, plen)[:2]).lower(
        shapes(lambda: prog.init_params(jax.random.PRNGKey(0))),
        shapes(lambda: prog.init_cache(2, twice.max_seq)),
        i32(), i32(2048), i32()).as_text()
    assert len(re.findall(r"func\.func private @\w*mamba_prompt_scan",
                          lowered)) == 1
    assert len(re.findall(r"call @\w*mamba_prompt_scan", lowered)) == 2
    assert lowered.count("tpu_custom_call") == 1


def _cell_programs(topo, config: str):
    """A benchmark configuration and its family's sizing programs (the
    engine's decode and its prefill at each rung, as the cell runs
    them), their arguments as shapes on one described chip; with no
    ``topo``, on the process's own default device."""
    from benchmarks.harness.spec import Spec

    spec = Spec()
    c = spec.config(config)
    where = {} if topo is None else {
        "sharding": SingleDeviceSharding(topo.devices[0])}
    return c, spec.family(c["family"]).sizing(
        c, lambda tree: jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, **where), tree))


# GLM-5's cell whole (benchmarks/configs/glm-5.json: one dense and four
# expert layers at the published widths, 16 of 256 experts, an eighth of
# the vocabulary; 64 slots of 10,240 positions, prompts at 4,096 and
# 8,192 rows), through the family's own sizing programs: the engine's
# decode and its prefill at both rungs, each donating the cache.
DSA_PROGRAMS = ("decode L=5", "prefill L=5 rung=4096",
                "prefill L=5 rung=8192")
V5E_USABLE = int(15.75 * 2 ** 30)


@pytest.mark.parametrize("name", DSA_PROGRAMS)
def test_the_latent_cells_programs_compile_and_fit(topo, name):
    """Each compiles for the chip, writes the latent, rotary and indexer
    rows into the donated cache in place (nothing but a fusion or an
    update in place writes a tensor of a layer's latent or indexer
    rows; the rotary keys, 64 wide, XLA:TPU keeps positions-minor, the
    shape of a head's float32 scores over every lane, and a prefill
    stages them through VMEM around the row writes: PERF.md section
    7), and fits: its peak, weights and cache included, is under the
    chip's usable 15.75 GiB. **The decode's attention is the Mosaic
    kernel ``mla_attend``** (the program is lowered for a TPU, whatever
    the process's own backend), lowered once as a private function that
    each of the five layers calls, under ``attn.mla/mla.attend``; it is
    given the rotary keys as they lie (a bitcast, no copy), the float32
    scores of every lane and head over the whole cache (160 MiB a
    layer) are never formed, and the program needs 64 MiB beyond its
    arguments where it needed 250. **The prefill's attention is the
    Mosaic kernel ``mla_ingest_attend``**, a block of 256 queries
    against the key blocks up to its own, under ``attn.mla/mla.attend``
    in each of the five layers (one private function a span's length
    of keys, each called by every layer): no float32 scores of a block
    over a chunk of 2,048 keys (128 MiB) and no head's (prompt, prompt)
    square is formed, and no fusion is
    left with the tiling XLA:TPU falls back to when its search gives up
    (``estimated_cycles`` at the int64 maximum: the softmax over a
    whole 6,144- or 8,192-key span was one, 27-47 ms a block where
    4,096 keys took 1.2; PERF.md section 6, PR 41)."""
    c, progs = _cell_programs(topo, "glm-5")
    prog = next(p for p in progs if p["name"] == name)
    lowered = prog["fn"].lower(*prog["args"])
    compiled = lowered.compile()
    sv = c["serve"]
    slots, T = sv["slots"], sv["max_len"]
    cache = 5 * slots * T * (512 + 64 + 128) * 2
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= cache
    assert m.peak_memory_in_bytes < V5E_USABLE
    assert str(2 ** 63 - 1) not in compiled.as_text()
    beyond = m.temp_size_in_bytes + m.output_size_in_bytes \
        - m.alias_size_in_bytes
    ops = materialised(compiled.as_text())
    rows = {tuple(sorted((slots, T, w))) for w in (512, 128)}
    moved = written(ops, rows)
    assert all(op[1] in ("fusion", "dynamic-update-slice", "while")
               for op in moved), moved
    if name.startswith("decode"):
        text = lowered.as_text()
        assert len(re.findall(r"func\.func private @\w*mla_attend",
                              text)) == 1
        assert len(re.findall(r"call @\w*mla_attend", text)) == 5
        kernels = [ln for ln in compiled.as_text().splitlines()
                   if 'custom_call_target="tpu_custom_call"' in ln
                   and re.search(r'op_name="[^"]*/attn\.mla/mla\.attend/'
                                 r'[^"]*mla_attend', ln)]
        assert len(kernels) == 5
        # the logits and a layer's choice; no scores of every lane and
        # head over the cache, no second tensor of a layer's rotary keys
        wide = written(ops, {tuple(sorted((slots, slots, T)))})
        assert all(op[1] in ("bitcast", "dynamic-update-slice", "while")
                   and not op[2].startswith("f32") for op in wide), wide
        assert beyond < 64 << 20
    else:
        rung = int(name.rsplit("=", 1)[1])
        assert beyond < (3 << 30) * rung // 8192
        assert not written(ops, {tuple(sorted((64, rung, rung))),
                                 tuple(sorted((32, rung, rung)))})
        spans = 4
        text = lowered.as_text()
        assert len(re.findall(r"func\.func private @\w*ingest_attend",
                              text)) == spans
        assert len(re.findall(r"call @\w*ingest_attend", text)) == 5 * spans
        kernels = [ln for ln in compiled.as_text().splitlines()
                   if 'custom_call_target="tpu_custom_call"' in ln
                   and re.search(r'op_name="[^"]*/attn\.mla/[^"]*mla\.attend/'
                                 r'[^"]*mla_ingest_attend', ln)]
        assert len(kernels) == 5 * spans
        scores = written(ops, {tuple(sorted((64, 256, 2048)))})
        assert not [op for op in scores if op[2].startswith("f32")], scores


@pytest.mark.parametrize("name", DSA_PROGRAMS[1:])
def test_the_latent_prefill_lowered_for_a_cpu_holds_no_kernel(name):
    """The same program traced the same way and lowered for the
    process's own CPU: ``platform_dependent`` leaves the ``jax.numpy``
    form alone in it (``_attend_chunks``), no Mosaic call."""
    _, progs = _cell_programs(None, "glm-5")
    prog = next(p for p in progs if p["name"] == name)
    lowered = prog["fn"].lower(*prog["args"])
    text = lowered.as_text()
    assert "tpu_custom_call" not in text and "ingest_attend" not in text
    assert "mla.attend" in lowered.as_text(debug_info=True)


# Nemotron-3-Nano's cell whole (benchmarks/configs/
# nemotron-3-nano-30b-a3b.json: the first 26 blocks at the published
# widths, 12 Mamba-2 mixers, 11 expert layers of 32 held experts, 3
# attention layers; 128 slots of 3,072 positions, prompts at 1,024 and
# 2,048 rows), through the family's own sizing programs.
MAMBA2_PROGRAMS = ("decode L=26", "prefill L=26 rung=1024",
                   "prefill L=26 rung=2048")


@pytest.mark.parametrize("name", MAMBA2_PROGRAMS)
def test_the_matrix_state_cells_programs_compile_and_fit(topo, name):
    """Each compiles for the chip, donates the cache whole (a float32
    ``(slots, 64, 64, 128)`` state and a 3-row tail for each of the 12
    Mamba-2 blocks, keys and values for the 3 attention blocks only:
    4.18 GiB) and fits: its peak, weights and cache included, is under
    the chip's usable 15.75 GiB. An expert is two matrices (no ``we3``
    among the arguments). **The decode sends a tick's 128 rows through
    every held expert** (``models/moe.DENSE_PAIRS``: 128 rows x 32
    held experts): no grouped product
    is in it, no instruction writes a tensor of a layer's
    held experts (a transposed copy would be a second pass over 319
    MB), and beyond its arguments it needs under 64 MiB: no second
    tensor of the state. **The prefill's grouped products are the
    Pallas kernel** (``ops/grouped_matmul.py``; the program is lowered
    for a TPU, whatever the process's own backend) at both rungs: two
    for each of the eleven expert layers under ``moe.experts``, no
    ``ragged-dot``, ``we1`` taken turned as it lies (XLA:TPU's own
    grouped product copied all 319 MB of it before each layer's first
    product), and every instruction that reads an expert's matrix
    under the scope the expert metrics read. **The prefill forms the chunked scan's
    pairwise decays a layer at a time** (64 heads x 16 chunks x 128 x
    128 float32 = 64 MiB at the 2,048 rung) and never a head's
    (prompt, prompt) square of them."""
    c, progs = _cell_programs(topo, "nemotron-3-nano-30b-a3b")
    prog = next(p for p in progs if p["name"] == name)
    leaves = {jax.tree_util.keystr(path) for path, _ in
              jax.tree_util.tree_leaves_with_path(prog["args"][0])}
    assert any("we1" in leaf for leaf in leaves)
    assert not any(leaf.endswith("3']") for leaf in leaves), leaves
    compiled = prog["fn"].lower(*prog["args"]).compile()
    sv = c["serve"]
    slots, T = sv["slots"], sv["max_len"]
    state = slots * 64 * 64 * 128 * 4
    cache = 12 * (state + slots * 3 * 6144 * 2) \
        + 3 * 2 * slots * T * 2 * 128 * 2
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= cache
    assert m.peak_memory_in_bytes < V5E_USABLE
    beyond = m.temp_size_in_bytes + m.output_size_in_bytes \
        - m.alias_size_in_bytes
    hlo = compiled.as_text()
    ops = materialised(hlo)
    assert "ragged-dot" not in hlo
    # ``we1`` lies with 2688 fastest; turned it is a bitcast, never a copy
    assert all(op[1] == "bitcast" for op in written(
        ops, {tuple(sorted((32, 2688, 1856)))}))
    if name.startswith("decode"):
        # the three attention layers' one-pass kernel, and no other
        assert len(_attend_kernels(hlo)) == hlo.count(
            'custom_call_target="tpu_custom_call"') == 3
        _nothing_moves_a_layers_keys(ops, slots, T, 2)
        assert beyond < 64 << 20, beyond >> 20
    else:
        rung = int(name.rsplit("=", 1)[1])
        assert len(_expert_kernels(hlo)) == 2 * 11
        _expert_weights_are_read_under_their_scope(hlo, 2 * 11)
        assert beyond < (1200 << 20) * rung // 2048 + (256 << 20), \
            beyond >> 20
        assert not written(ops, {tuple(sorted((64, rung, rung))),
                                 tuple(sorted((8, 8, rung, rung)))})


# LFM2-24B-A2B's cell whole (benchmarks/configs/lfm2-24b-a2b.json:
# layers 0-9 at the published widths, 8 gated convolutions, 2 attention
# layers of 64-wide normed heads, 2 dense and 8 expert layers of all 64
# experts; 256 slots of 3,072 positions, prompts at 512 and 1,024
# rows), through the family's own sizing programs.
CONV_PROGRAMS = ("decode L=10", "prefill L=10 rung=1024")


@pytest.mark.parametrize("name", CONV_PROGRAMS)
def test_the_convolution_cells_programs_compile_and_fit(topo, name):
    """Each compiles for the chip, donates the cache whole (keys and
    values of 64-wide heads **two to a row of 128 lanes**, ``(256,
    3072, 4, 128)``: 2 KiB a position a layer, 3.0 GiB, where a head a
    row would be padded to twice that and would not fit; a two-row
    tail for each of the 8 convolutions: 16 MiB) and fits under the
    chip's usable 15.75 GiB with its 9.81 GiB of weights. **The
    decode's two attention layers are the one-pass kernel over each
    lane's live blocks** (the packed rows as they lie: no instruction
    writes a tensor of a layer's keys but the tick's new position, and
    no float32 scores a lane and a position long exist), **and its
    1,024 sorted rows on 64 held experts go through the Pallas grouped
    product** (16 rows an expert): three for each of the eight expert
    layers, no ``ragged-dot``, and beyond its arguments the tick needs
    under 64 MiB. The prefill's grouped products are the same kernel."""
    c, progs = _cell_programs(topo, "lfm2-24b-a2b")
    prog = next(p for p in progs if p["name"] == name)
    compiled = prog["fn"].lower(*prog["args"]).compile()
    sv = c["serve"]
    slots, T = sv["slots"], sv["max_len"]
    assert c["sizing"]["cache_bytes"] == 2 * 2 * slots * T * 4 * 128 * 2 \
        + 8 * slots * 2 * 2048 * 2
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= c["sizing"]["cache_bytes"]
    assert m.argument_size_in_bytes < c["sizing"]["weights_bytes"] \
        + c["sizing"]["cache_bytes"] + (1 << 20)
    assert m.peak_memory_in_bytes < V5E_USABLE
    beyond = m.temp_size_in_bytes + m.output_size_in_bytes \
        - m.alias_size_in_bytes
    hlo = compiled.as_text()
    ops = materialised(hlo)
    assert "ragged-dot" not in hlo
    assert len(_expert_kernels(hlo)) == 3 * 8
    _expert_weights_are_read_under_their_scope(hlo, 3 * 8)
    if name.startswith("decode"):
        assert len(_attend_kernels(hlo)) == 2
        _nothing_moves_a_layers_keys(ops, slots, T, 4)
        assert beyond < 64 << 20, beyond >> 20
    else:
        assert not _attend_kernels(hlo)
        assert beyond < 256 << 20, beyond >> 20


# DeepSeek-V3's cell whole (benchmarks/configs/deepseek-v3.json: one
# dense and four expert layers at the published widths, 16 of 256
# experts, an eighth of the vocabulary, the drafting module behind
# them; 128 slots of 2,560 positions, prompts at 512 and 1,024 rows),
# through the family's own sizing programs: the drafting tick and the
# prefill at its wider rung, each donating the cache.
MTP_PROGRAMS = ("decode L=5+mtp", "prefill L=5+mtp rung=1024")


@pytest.mark.parametrize("name", MTP_PROGRAMS)
def test_the_drafting_cells_programs_compile_and_fit(topo, name):
    """Each compiles for the chip, donates the cache whole (a latent
    row and a rotary key a position in six layers, the drafting block's
    among them: 2.11 GiB, and no indexer's key) and fits under the
    chip's usable 15.75 GiB with its 10.44 GiB of weights. **The tick's
    six latent layers are the one-pass window kernel** (``ops/
    mla_attend.py::mla_attend_window``: a lane's two queries, 256 rows
    of heads, over one read of its live rows; the cache operand a
    bitcast of what lies there), one of them under ``mtp.draft``; the
    accept-and-advance arithmetic carries ``mtp.verify``; 256 rows on
    16 held experts go through every held expert (4,096 pairs), and
    beyond its arguments the tick needs under 128 MiB. The prompt
    forward's attention is the ``jax.numpy`` form (heads of 192 are no
    whole rows of lanes: ``ingest_attend_tiles``), its grouped products
    the Pallas kernel."""
    c, progs = _cell_programs(topo, "deepseek-v3")
    prog = next(p for p in progs if p["name"] == name)
    compiled = prog["fn"].lower(*prog["args"]).compile()
    sv = c["serve"]
    slots, T = sv["slots"], sv["max_len"]
    assert c["sizing"]["cache_bytes"] == 6 * slots * T * (512 + 64) * 2
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= c["sizing"]["cache_bytes"]
    assert m.argument_size_in_bytes < c["sizing"]["weights_bytes"] \
        + c["sizing"]["cache_bytes"] + (1 << 20)
    assert m.peak_memory_in_bytes < V5E_USABLE
    beyond = m.temp_size_in_bytes + m.output_size_in_bytes \
        - m.alias_size_in_bytes
    hlo = compiled.as_text()
    kernels = [ln for ln in hlo.splitlines()
               if 'custom_call_target="tpu_custom_call"' in ln
               and "mla_attend_window" in ln]
    assert "ragged-dot" not in hlo
    if name.startswith("decode"):
        assert len(kernels) == 6
        assert sum("/mtp.draft/" in ln for ln in kernels) == 1
        assert all(re.search(r'op_name="[^"]*/attn\.mla/mla\.attend/',
                             ln) for ln in kernels)
        assert re.search(r'op_name="[^"]*/mtp\.verify/', hlo)
        assert not _expert_kernels(hlo)        # every held expert
        # no float32 scores a lane and a position long exist: the
        # window's are the kernel's, on the chip
        assert not [shape for _, _, shape in materialised(hlo)
                    if shape.startswith("f32[") and T in dims(shape)
                    and slots in dims(shape)]
        assert beyond < 128 << 20, beyond >> 20
    else:
        assert not kernels and "mla_ingest_attend" not in hlo
        assert len(_expert_kernels(hlo)) == 3 * 5
        assert re.search(r'op_name="[^"]*/mtp\.draft/attn\.mla/', hlo)
        assert beyond < 512 << 20, beyond >> 20


def _expert_kernels(hlo: str) -> list[str]:
    """The compiled program's calls of the Pallas grouped product
    (``ops/grouped_matmul.py``), each under the ``moe.experts`` scope."""
    kernels = [ln for ln in hlo.splitlines()
               if 'custom_call_target="tpu_custom_call"' in ln
               and "grouped_matmul" in ln]
    assert all(re.search(r'op_name="[^"]*/moe\.experts/[^"]*grouped_matmul',
                         ln) for ln in kernels)
    return kernels


def _expert_weights_are_read_under_their_scope(hlo: str, least: int):
    """Every instruction of the entry computation that reads a held
    expert's matrix carries the ``moe.experts`` scope, by which
    ``moe.experts_ms_p50`` and ``kernel.expert_matmul_hbm_roofline``
    find its time (``benchmarks/tests/test_moe_mixed.py`` makes the
    check for the ``ragged-dot-*`` ops, which those metrics find by
    name)."""
    entry = hlo[hlo.index("ENTRY "):]
    readers = [ln for ln in entry.splitlines()
               if re.search(r"\(.*%params__blocks____\d+____mlp____we[123]__",
                            ln) and " parameter(" not in ln]
    assert len(readers) >= least, len(readers)
    for ln in readers:
        scope = re.search(r'op_name="([^"]*)"', ln)
        assert scope and "/moe.experts/" in scope.group(1), ln[:300]


# The other cells' decode ticks that sort their rows: every product
# the kernel's tiling fits is the kernel's (``models/moe.
# grouped_kernel_takes``), however few rows a held expert has. Laguna's
# 640 sorted rows over 128 held experts (5 each) and solar's 2,048 over
# 40 (51 each) both go through it, three products for each of their
# four expert layers, and neither tick holds a ``ragged-dot``.
SORTED_TICKS = {"laguna-s-2.1": 3 * 4, "solar-open2-250b": 3 * 4}
#: The same ticks' full softmax layers, each the one-pass attention
#: over the lane's live blocks (``ops/kv_attend.py``): laguna's two of
#: five layers (its three rings stay on the ``jax.numpy`` form: a
#: lapped ring is live whole), solar's one of four.
FULL_LAYERS = {"laguna-s-2.1": 2, "solar-open2-250b": 1}


@pytest.mark.parametrize("config", SORTED_TICKS)
def test_a_ticks_sorted_rows_take_the_form_their_shape_chooses(topo, config):
    prog = _cell_programs(topo, config)[1][0]
    assert prog["name"].startswith("decode")
    hlo = prog["fn"].lower(*prog["args"]).compile().as_text()
    kernels = SORTED_TICKS[config]
    assert len(_expert_kernels(hlo)) == kernels
    assert "ragged-dot" not in hlo
    _expert_weights_are_read_under_their_scope(hlo, kernels)
    assert len(_attend_kernels(hlo)) == FULL_LAYERS[config]
    assert "attn.window/" not in "".join(
        ln for ln in hlo.splitlines() if "kv_attend" in ln)
    sv = _cell_programs(topo, config)[0]["serve"]
    _nothing_moves_a_layers_keys(materialised(hlo), sv["slots"],
                                 sv["max_len"], 8)


def test_materialised_leaves_out_fused_computations():
    hlo = """HloModule m

%fused_computation (p0: bf16[3,8,4], p1: s32[]) -> bf16[2,4] {
  %p0 = bf16[3,8,4]{2,1,0} parameter(0)
  %slice.1 = bf16[1,8,4]{2,1,0:T(8,128)(2,1)} dynamic-slice(%p0, %p1)
  ROOT %dot.1 = bf16[2,4]{1,0} convolution(%x, %slice.1)
}

%body (arg: (s32[], bf16[3,8,4])) -> (s32[], bf16[3,8,4]) {
  %w = bf16[3,8,4]{2,1,0} get-tuple-element(%arg), index=1
  %copy.7 = bf16[1,8,4]{1,2,0:T(8,128)(2,1)S(1)} copy(%slice.9)
  ROOT %fusion.3 = bf16[2,4]{1,0} fusion(%w, %i), kind=kOutput, calls=%fused_computation
}

ENTRY %main (a: bf16[3,8,4]) -> bf16[2,4] {
  %a = bf16[3,8,4]{2,1,0} parameter(0)
  ROOT %while.1 = (s32[], bf16[3,8,4]{2,1,0}) while(%t), body=%body
}
"""
    assert materialised(hlo) == [
        ("w", "get-tuple-element", "bf16[3,8,4]"),
        ("copy.7", "copy", "bf16[1,8,4]"),
        ("fusion.3", "fusion", "bf16[2,4]"),
        ("a", "parameter", "bf16[3,8,4]")]
    assert dims("bf16[1,8,4]") == dims("bf16[4,8]") == (4, 8)
    assert written(materialised(hlo), {(4, 8)}) == [
        ("copy.7", "copy", "bf16[1,8,4]")]
