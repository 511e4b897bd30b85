"""pbs_tpu.serve: rule-table partitioning, the sharded gateway
backend, prefill/decode disaggregation, and the disarmed-golden
contract (docs/SERVING.md)."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pbs_tpu.gateway import Gateway, TenantQuota, run_gateway_chaos
from pbs_tpu.models import TransformerConfig, init_params
from pbs_tpu.obs.spans import SpanAssembler, SpanRecorder
from pbs_tpu.serve import (
    DisaggServeBackend,
    ShardedServeBackend,
    synth_payload,
)
from pbs_tpu.serve.partition import (
    PARTITION_RULES,
    TEMPLATE_PATHS,
    audit_rules,
    iter_leaf_paths,
    make_serve_mesh,
    make_shard_and_gather_fns,
    match_partition_rules,
    place,
    resolve_spec,
    rule_shardings,
)
from pbs_tpu.utils.clock import MS, VirtualClock

TINY = dict(vocab=64, d_model=16, n_layers=1, n_heads=2, n_kv_heads=1,
            d_ff=32, max_seq=64, dtype=jnp.float32)


@pytest.fixture(scope="module")
def cfg():
    return TransformerConfig(**TINY)


@pytest.fixture(scope="module")
def params(cfg):
    return init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def planned_params():
    """A planned stack (models/plan.py) with every leaf kind: a gated
    full layer with a dense MLP, a gated window layer with routed
    experts and a shared one, a delta-rule layer with experts behind a
    sigmoid router, a state-space layer, a latent layer that selects,
    two blocks of one half each (a matrix-state mixer alone, ungated
    relu^2 experts alone), a gated convolution, and a full layer whose
    heads are normed."""
    from pbs_tpu.models import plan as P

    plan = P.LayerPlan(
        attn=(P.AttnKind("full", 2, None, P.Rope(), True),
              P.AttnKind("window", 4, 4, P.Rope(), True),
              P.KdaKind("kda", 2, 8, conv=4, rank=4),
              P.MambaKind("mamba", 32, 4, 3, conv=4),
              P.MlaKind("mla", 2, 8, 8, 4, 4, 8, 2, 8, 4,
                        P.Rope(rotary_dim=4, interleave=True)),
              P.Mamba2Kind("mamba2", 4, 8, 2, 8, conv=4),
              P.ConvKind("conv", 16, conv=3),
              P.AttnKind("normed", 2, None, P.Rope(), qk_norm=True)),
        mlp=(P.MlpKind("dense", 32),
             P.MlpKind("experts", 8, n_experts=4, top_k=2, held=(0, 2),
                       shared_d_ff=8),
             P.MlpKind("experts", 8, n_experts=4, top_k=2, held=(0, 2),
                       scoring="sigmoid"),
             P.MlpKind("relu2", 8, n_experts=4, top_k=2, held=(0, 2),
                       shared_d_ff=16, scoring="sigmoid", form="relu2")),
        layers=((0, 0), (1, 1), (2, 2), (3, 0), (4, 0), (5, None),
                (None, 3), (6, 0), (7, 0)),
        draft=(4, 2))
    cfg = TransformerConfig(**dict(TINY, n_layers=9, head_size=8,
                                   layer_plan=plan))
    return P.init_plan_params(cfg, jax.random.PRNGKey(0))


MOE = dict(TINY, n_layers=2, n_kv_heads=2, n_experts=4, top_k=2,
           dropless=True, router_group_size=8)


@pytest.fixture(scope="module")
def moe_model():
    """The stacked MoE tree (models/moe.py) the engine serves through
    ``mlp_fn=moe_slot_mlp(cfg)``."""
    from pbs_tpu.models import MoEConfig
    from pbs_tpu.models.moe import init_moe_params

    mcfg = MoEConfig(**MOE)
    return mcfg, init_moe_params(mcfg, jax.random.PRNGKey(0))


def tree_paths(*trees):
    """Leaf paths of the trees, a planned tree's layer number as N (a
    drafting block's mixer and MLP are a layer's)."""
    return [re.sub(r"^blocks/(\d+|mtp(?=/(attn|mlp)/))/", "blocks/N/", p)
            for tree in trees for p, _ in iter_leaf_paths(tree)]


def _tiny_kw(seed):
    return dict(tp=1, dp=1, n_slots=2, prompt_bucket=8, max_len=32,
                seed=seed, clock="virtual")


def sharded_factory_for(cfg):
    def factory(name, seed):
        return ShardedServeBackend(name, cfg, **_tiny_kw(seed))
    return factory


def disagg_factory_for(cfg):
    def factory(name, seed):
        return DisaggServeBackend(name, cfg, tp=1, dp=1, n_slots=4,
                                  prompt_bucket=8, max_len=32,
                                  seed=seed, clock="virtual")
    return factory


# -- the rule table ----------------------------------------------------------


def test_every_leaf_matches_exactly_one_rule(params):
    """The exactly-one contract the table's order-free readability
    rests on: for the flagship tree no leaf needs first-match-wins to
    disambiguate — every path matches ONE rule."""
    for path, _leaf in iter_leaf_paths(params):
        hits = [pat for pat, _ in PARTITION_RULES
                if re.search(pat, path)]
        assert len(hits) == 1, f"{path}: matched {hits}"


def test_template_paths_pin_the_param_tree(params, planned_params,
                                           moe_model):
    """TEMPLATE_PATHS is the audit's coverage universe; it must BE the
    leaf set of the trees the engine serves (init_params' stacked one,
    the stacked MoE one, a planned stack's per-layer one) or the audit
    goes blind to drift."""
    actual = set(tree_paths(params, moe_model[1], planned_params))
    assert sorted(actual) == sorted(TEMPLATE_PATHS)


def test_a_stacked_spec_places_one_layer_of_it(planned_params):
    """Written for (layer, ...) stacks, a spec loses its layer entry on
    a planned tree's per-layer leaf under ``blocks/``, and nowhere
    else; every leaf still meets one rule."""
    for path, _leaf in iter_leaf_paths(planned_params):
        hits = [pat for pat, _ in PARTITION_RULES if re.search(pat, path)]
        assert len(hits) == 1, f"{path}: matched {hits}"
    specs = match_partition_rules(PARTITION_RULES, planned_params)
    block = specs["blocks"]["01"]
    assert block["attn"]["wq"] == (None, -1)
    assert block["attn"]["wo"] == (-1, None)
    assert block["attn"]["attn_norm"] == () and block["attn"]["wg"] == ()
    assert block["mlp"]["we1"] == (-1, None, None)
    assert block["mlp"]["ws2"] == (-1, None)
    assert block["mlp"]["router"] == ()


@pytest.mark.parametrize("tree, path", [
    ({"layers": {"wq": jnp.ones((8, 4))}}, "layers/wq"),
    # one entry too many lifts nothing outside blocks/
    ({"stack": {"w2": jnp.ones((8, 4))}}, "stack/w2"),
    # under blocks/ only the layer entry falls away
    ({"blocks": {"00": {"mlp": {"we1": jnp.ones((2, 2, 8, 4))}}}},
     "blocks/00/mlp/we1"),
])
def test_a_spec_of_another_rank_raises_with_the_path(tree, path):
    """A rule written for a tree of another rank would cut the wrong
    axis (the planned tree's ``we`` rule on the stacked MoE tree cut
    its layers over tp): it raises where it is matched, not where the
    spec is resolved, and ``()`` stays replicated at any rank."""
    with pytest.raises(ValueError, match=re.escape(repr(path))):
        match_partition_rules(PARTITION_RULES, tree)
    assert match_partition_rules(
        PARTITION_RULES, {"layers": {"router": jnp.ones((2, 8, 4))},
                          "x": {"wg": jnp.ones((8, 4))}}) == {
        "layers": {"router": ()}, "x": {"wg": ()}}


def test_audit_is_clean():
    audit = audit_rules(PARTITION_RULES)
    assert audit == {"dead": [], "shadowed": [], "uncovered": []}


def test_every_rule_claims_a_leaf(params, planned_params, moe_model):
    paths = tree_paths(params, moe_model[1], planned_params)
    for pat, _spec in PARTITION_RULES:
        assert any(re.search(pat, p) for p in paths), \
            f"rule {pat!r} claims no leaf of either served tree"


def test_unmatched_leaf_is_a_hard_error(params):
    bad = dict(params, mystery=jnp.ones((4, 4)))
    with pytest.raises(ValueError, match="mystery"):
        match_partition_rules(PARTITION_RULES, bad)


def test_scalar_leaves_are_unpartitioned():
    specs = match_partition_rules(
        PARTITION_RULES, {"embed": jnp.ones((8, 4)),
                          "step": jnp.float32(0.0)})
    assert specs["step"] == ()


def test_resolve_spec_positional_semantics():
    mesh = make_serve_mesh(tp=1, dp=1)
    # Python indexing: -1 is the LAST axis name; non-negative indexes
    # forward (SNIPPETS.md positional-spec semantics).
    assert resolve_spec(mesh, (-1, None)) == jax.sharding.PartitionSpec(
        mesh.axis_names[-1], None)
    assert resolve_spec(mesh, (0,)) == jax.sharding.PartitionSpec(
        mesh.axis_names[0])
    with pytest.raises(ValueError, match="out of range"):
        resolve_spec(mesh, (7,))


# -- one table: the training specs and the retired MoE serving specs --------

DENSE_LEAVES = ("embed", "final_norm", "head") + tuple(
    f"layers/{k}" for k in ("attn_norm", "wq", "wk", "wv", "wo",
                            "mlp_norm", "w1", "w3", "w2"))

#: The stacked MoE tree on a serving mesh, the layout
#: tests/test_serving.py's tp test proves token-exact (and a dict table
#: of ``parallel/expert.py`` held until the rule table took the tree
#: over): per leaf, the dimension cut over the tensor axis, None =
#: replicated.
MOE_TP_DIM = {
    "embed": 0, "final_norm": None, "head": 1,
    "layers/attn_norm": None, "layers/mlp_norm": None,
    "layers/wq": 2, "layers/wk": 2, "layers/wv": 2, "layers/wo": 1,
    "layers/router": None,
    "layers/we1": 3, "layers/we3": 3, "layers/we2": 2,
}


def _leaf(tree, path):
    for key in path.split("/"):
        tree = tree[key]
    return tree


@pytest.mark.parametrize("path", DENSE_LEAVES)
def test_training_specs_agree_with_the_rule_table(params, cfg, path):
    """``parallel/sharding.param_specs`` is training's table (meshes
    with sp/pp/ep axes a positional table does not describe); on a
    serving mesh it must say what the rule table says, leaf by leaf."""
    from jax.sharding import NamedSharding

    from pbs_tpu.parallel.sharding import param_specs

    mesh = make_serve_mesh(tp=2, dp=2)
    ruled = _leaf(rule_shardings(params, mesh), path)
    trained = NamedSharding(mesh, _leaf(param_specs(cfg), path))
    assert ruled.is_equivalent_to(trained, _leaf(params, path).ndim), \
        (path, ruled.spec, trained.spec)


@pytest.mark.parametrize("path", sorted(MOE_TP_DIM))
def test_rule_table_places_the_stacked_moe_tree(moe_model, path):
    from jax.sharding import NamedSharding, PartitionSpec

    _mcfg, mparams = moe_model
    assert sorted(MOE_TP_DIM) == sorted(
        p for p, _ in iter_leaf_paths(mparams))
    mesh = make_serve_mesh(tp=2, dp=2)
    ndim = _leaf(mparams, path).ndim
    want = [None] * ndim
    if MOE_TP_DIM[path] is not None:
        want[MOE_TP_DIM[path]] = mesh.axis_names[-1]
    ruled = _leaf(rule_shardings(mparams, mesh), path)
    assert ruled.is_equivalent_to(
        NamedSharding(mesh, PartitionSpec(*want)), ndim), (path, ruled.spec)


# -- placed once -------------------------------------------------------------


def _engine_of(kind, form, through):
    """(the tree as placed, the engine it was handed to). A tp=2 mesh
    for the stacked trees; a planned stack serves on one device."""
    from pbs_tpu.models import MoEConfig
    from pbs_tpu.models.moe import init_moe_params, moe_slot_mlp
    from pbs_tpu.models.serving import ContinuousBatcher
    from pbs_tpu.models.slot_programs import slot_program

    kw = dict(n_slots=2, prompt_bucket=8, max_len=32)
    if kind == "planned":
        from pbs_tpu.models import plan as P

        plan = P.LayerPlan(
            attn=(P.AttnKind("full", 2, None, P.Rope(), True),),
            mlp=(P.MlpKind("experts", 8, n_experts=4, top_k=2,
                           held=(0, 2), shared_d_ff=8),),
            layers=((0, 0),))
        cfg, tp, extra = TransformerConfig(**dict(
            TINY, head_size=8, layer_plan=plan)), 1, {}
        tree = slot_program(cfg).init_params(jax.random.PRNGKey(0))
    elif kind == "moe":
        cfg, tp = MoEConfig(**MOE), 2
        tree = init_moe_params(cfg, jax.random.PRNGKey(0))
        extra = {"mlp_fn": moe_slot_mlp(cfg)}
    else:
        cfg, tp, extra = TransformerConfig(**dict(TINY, n_kv_heads=2)), 2, {}
        tree = init_params(cfg, jax.random.PRNGKey(0))
    if form == "int8":
        from pbs_tpu.models.quant import quantize_weights

        tree = quantize_weights(tree)
    mesh = make_serve_mesh(tp=tp)
    placed = place(tree, mesh)
    if through == "backend":
        return placed, ShardedServeBackend(
            "b", cfg, placed, tp=tp, **kw).engine
    return placed, ContinuousBatcher(cfg, placed, mesh=mesh, **kw, **extra)


@pytest.mark.parametrize("kind, form, through", [
    ("dense", "fp", "backend"), ("dense", "fp", "engine"),
    ("dense", "int8", "backend"), ("dense", "int8", "engine"),
    ("moe", "fp", "engine"),  # ShardedServeBackend takes no mlp_fn
    ("planned", "fp", "backend"), ("planned", "fp", "engine"),
])
def test_a_placed_tree_reaches_the_engine_untouched(kind, form, through):
    """A serving weight is placed once, by the rule table: a tree laid
    out by ``place`` and handed to the backend or to the engine comes
    out as ``engine.params`` with every leaf the array that went in
    (``jax.device_put`` onto the sharding an array already has returns
    that array; a second table, or a second opinion, would not)."""
    placed, engine = _engine_of(kind, form, through)
    went_in = jax.tree_util.tree_leaves_with_path(placed)
    came_out = jax.tree_util.tree_leaves(engine.params)
    assert len(went_in) == len(came_out) > 0
    for (path, a), b in zip(went_in, came_out):
        assert a is b, jax.tree_util.keystr(path)
    if kind != "planned":
        assert any(not leaf.sharding.is_fully_replicated
                   for _, leaf in went_in)


def _imports_of(module) -> list:
    """Every ``(module, name)`` a module's source imports, at its top
    or inside a function."""
    import ast

    with open(module.__file__) as f:
        tree = ast.parse(f.read())
    seen = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            seen += [(a.name, None) for a in node.names]
        elif isinstance(node, ast.ImportFrom) and node.module:
            seen += [(node.module, a.name) for a in node.names]
    return seen


def test_the_engine_names_no_parameter_table():
    """The engine places nothing itself and its programs
    (models/slot_programs.py) their cache and nothing else: neither
    imports anything of ``pbs_tpu.serve``, and of ``pbs_tpu.parallel``
    the programs import the cache's own sharding alone."""
    import pbs_tpu.models.serving as serving
    import pbs_tpu.models.slot_programs as slot_programs

    for module, wants in ((serving, []), (slot_programs, [
            ("pbs_tpu.parallel.sharding", "slot_cache_kv_sharding")])):
        assert [(m, n) for m, n in _imports_of(module) if m.startswith(
            ("pbs_tpu.serve", "pbs_tpu.parallel"))] == wants
    for form in (slot_programs._ScanProgram, slot_programs._PlannedProgram):
        assert not hasattr(form, "place") and callable(form.place_cache)


def test_the_slot_programs_know_no_engine():
    """The arrow points one way: ``models/slot_programs.py`` imports
    nothing of the engine, the serve layer or the gateway, and loading
    it (in a fresh interpreter) loads none of them; the names the
    benchmark imports from ``models/serving.py`` resolve there."""
    import os
    import subprocess
    import sys

    import pbs_tpu.models.serving as serving
    import pbs_tpu.models.slot_programs as slot_programs

    above = ("pbs_tpu.models.serving", "pbs_tpu.models.spec_serving",
             "pbs_tpu.serve", "pbs_tpu.gateway")
    assert [m for m, _ in _imports_of(slot_programs)
            if m.startswith(above)] == []
    out = subprocess.run(
        [sys.executable, "-c",
         "import sys; import pbs_tpu.models.slot_programs; "
         f"print([m for m in sys.modules if m.startswith({above!r})])"],
        capture_output=True, text=True, timeout=120, check=True,
        cwd=os.path.dirname(os.path.dirname(os.path.dirname(
            slot_programs.__file__))))
    assert out.stdout.strip() == "[]", out.stdout
    for name in ("ContinuousBatcher", "slot_program", "prefill_rungs",
                 "_slot_forward", "ingest_slot_prompt", "init_slot_cache"):
        assert callable(getattr(serving, name)), name
    for name in ("slot_program", "prefill_rungs", "_slot_forward",
                 "ingest_slot_prompt", "init_slot_cache"):
        assert getattr(serving, name) is getattr(slot_programs, name)


# -- shard / gather ----------------------------------------------------------


def test_shard_gather_roundtrip_byte_identical(params):
    mesh = make_serve_mesh(tp=1, dp=1)
    shard, gather = make_shard_and_gather_fns(mesh)
    back = gather(shard(params))
    flat_a = jax.tree_util.tree_leaves(params)
    flat_b = jax.tree_util.tree_leaves(back)
    assert len(flat_a) == len(flat_b)
    for a, b in zip(flat_a, flat_b):
        na, nb = np.asarray(a), np.asarray(b)
        assert na.dtype == nb.dtype and na.shape == nb.shape
        assert na.tobytes() == nb.tobytes()


# -- the sharded backend under gateway chaos ---------------------------------

CHAOS_KW = dict(workload="mixed", seed=3, n_backends=3, n_tenants=3,
                ticks=60)


def test_sharded_backend_serves_gateway_chaos(cfg):
    r = run_gateway_chaos(serve=sharded_factory_for(cfg), **CHAOS_KW)
    assert r["problems"] == []
    assert r["ok"] is True
    # No admitted request lost, span chains gap-free (both inside
    # problems==[]), and the serve tier actually served.
    st = r["stats"]
    assert st["admitted"] == st["completed"] > 0
    assert r["serve"]["completed"] > 0
    assert r["serve"]["synth_dispatches"] == r["serve"]["completed"]
    assert r["serve"]["bypass_submits"] == 0
    assert r["killed_backend"] == "b0"  # the sim at [0] still dies


def test_sharded_backend_chaos_same_seed_same_digest(cfg):
    a = run_gateway_chaos(serve=sharded_factory_for(cfg), **CHAOS_KW)
    b = run_gateway_chaos(serve=sharded_factory_for(cfg), **CHAOS_KW)
    assert a["trace_digest"] == b["trace_digest"]
    assert a["serve"] == b["serve"]
    assert a["stats"]["shed"] == b["stats"]["shed"]


def test_disagg_backend_serves_gateway_chaos(cfg):
    r = run_gateway_chaos(serve=disagg_factory_for(cfg), **CHAOS_KW)
    assert r["problems"] == []
    assert r["ok"] is True
    assert r["serve"]["completed"] > 0
    assert r["serve"]["handoffs"] == r["serve"]["completed"]
    # THE disagg contract: the decode pool never ran a prefill — every
    # admission hit the handed-off KV in the prefix cache.
    assert r["serve"]["decode_pool_prefills"] == 0


# -- handoff span stitching --------------------------------------------------


def test_disagg_handoff_span_chain(cfg):
    """One stitched chain per request across the prefill->decode
    handoff: ... EXEC(prefill) HANDOFF DISPATCH EXEC(decode) ...
    validates gap-free under the assembler's state machine."""
    clock = VirtualClock()
    spans = SpanRecorder(capacity=4096)
    backend = DisaggServeBackend("d0", cfg, tp=1, dp=1, n_slots=2,
                                 prompt_bucket=8, max_len=32, seed=0,
                                 clock="virtual")
    gw = Gateway([backend], clock=clock, spans=spans,
                 quotas={"t": TenantQuota(rate=1000.0, burst=64.0,
                                          slo="interactive",
                                          max_queued=64)})
    rids = []
    for i in range(4):
        res = gw.submit("t", {"i": i}, cost=2)
        assert res.admitted
        rids.append(res.rid)
    for _ in range(400):
        if not gw.busy():
            break
        gw.tick()
        clock.advance(MS)
    assert not gw.busy()
    assert backend.stats()["handoffs"] == 4
    assert backend.stats()["decode_pool_prefills"] == 0
    recs = spans.drain()
    asm = SpanAssembler(recs, spans.rid_table(), spans.member_table(),
                        spans.tenant_table())
    assert asm.validate(rids) == []


def test_disagg_window_outlives_the_pool_cache(cfg, donating):
    """The hand-off payload is sliced out of the prefill pool's slab
    before the pool's next (donating) ingest: prefilled at prompt n,
    it installs token-exact after 40 more prompts have gone through
    the pool and the slab it came from is long dead."""
    from pbs_tpu.models import make_generate

    backend = DisaggServeBackend("d0", cfg, tp=1, dp=1, n_slots=4,
                                 prompt_bucket=8, max_len=32, seed=0,
                                 clock="virtual")
    pool, eng = backend.prefill_pool, backend.engine
    assert not np.asarray(pool.cache["pos"]).any()  # warm-up: cursors 0
    prompt = np.asarray([5, 9, 2, 31, 7], np.int32)
    old = pool.cache
    logits, kwin, vwin = donating(
        lambda: pool.prefill(eng.params, prompt), old["k"], old["v"])
    snap = np.asarray(kwin).copy()
    rng = np.random.default_rng(0)
    for _ in range(40):
        pool.prefill(eng.params, rng.integers(1, cfg.vocab, 6,
                                              dtype=np.int32))
    assert not kwin.is_deleted() and not vwin.is_deleted()
    np.testing.assert_array_equal(np.asarray(kwin), snap)
    # The backend's own publish-then-submit, without the gateway.
    eng._prefix_cache[prompt.tobytes()] = {
        "k": kwin, "v": vwin, "logits": logits, "plen": len(prompt)}
    rid = eng.submit(prompt, 8)
    done = {}
    while eng.has_work():
        done.update((c.request_id, c.tokens) for c in eng.step())
    gold = jax.jit(make_generate(cfg, 8, temperature=0.0))(
        eng.params, jnp.asarray(prompt)[None, :], jax.random.PRNGKey(1))
    assert done[rid] == [int(t) for t in np.asarray(gold)[0]]
    assert eng.prefill_count == 0  # installed, never prefilled here


# -- disarmed goldens --------------------------------------------------------

#: The PR 15 constants (also pinned in test_gateway_chaos.py /
#: test_federation_chaos.py): serve=None must keep them byte-identical.
GOLDEN_GATEWAY_DIGEST = (
    "4ef79af3bcb1dcf7b03cad1cd27a91b61f6560f6ea6db0085e504bb08eff5737")
GOLDEN_FED_TRACE_DIGEST = (
    "71a188673b85cf80a67a721b247443d22e3776a09ad491fc6a5356553218d6de")
GOLDEN_FED_REPORT_DIGEST = (
    "1ba265a705067e8d8761aaa8d57c23b30e38c25839b29c9f1debf380b5667242")


def test_disarmed_gateway_golden_byte_identical():
    r = run_gateway_chaos(workload="mixed", seed=0, n_backends=3,
                          n_tenants=4, ticks=160, serve=None)
    assert r["trace_digest"] == GOLDEN_GATEWAY_DIGEST
    assert "serve" not in r  # report shape untouched when disarmed


def test_disarmed_federation_golden_byte_identical():
    from pbs_tpu.gateway import run_federation_chaos

    r = run_federation_chaos(workload="mixed", seed=0, n_gateways=3,
                             n_tenants=4, ticks=240, serve=None)
    assert r["trace_digest"] == GOLDEN_FED_TRACE_DIGEST
    assert r["report_digest"] == GOLDEN_FED_REPORT_DIGEST
    assert "serve" not in r


def test_serve_crash_plan_mutually_exclusive(cfg):
    from pbs_tpu.gateway import run_federation_chaos

    with pytest.raises(ValueError, match="serve"):
        run_federation_chaos(serve=sharded_factory_for(cfg),
                             crash_plan=[{"tick": 5}])


# -- synthesis, knobs, CLI ---------------------------------------------------


def test_synth_payload_deterministic_and_bounded():
    class R:
        rid = "gw0-17"
        cost = 9

    a = synth_payload(R(), bucket=8, max_len=32, vocab=64)
    b = synth_payload(R(), bucket=8, max_len=32, vocab=64)
    assert a == b
    prompt, max_new = a
    assert 1 <= len(prompt) <= 8
    assert all(1 <= t < 64 for t in prompt)
    assert 1 <= max_new <= 32 - 8 - 1
    assert len(prompt) + max_new <= 32


def test_serve_knobs_declared():
    from pbs_tpu.knobs import registry as knobs

    assert knobs.default("serve.backend.decode_slots") == 4
    assert 0.05 <= knobs.default("serve.disagg.pool_split_ratio") <= 0.75
    assert knobs.default("serve.disagg.prefill_chunk_tokens") >= 8
    assert knobs.default("serve.disagg.kv_handoff_batch") >= 1


def test_cli_serve_stats_and_demo(capsys):
    import json

    from pbs_tpu.cli.pbst import main

    assert main(["serve", "stats"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["audit"] == {"dead": [], "shadowed": [], "uncovered": []}
    assert len(out["rules"]) == len(PARTITION_RULES)

    assert main(["serve", "demo", "--requests", "4"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["completions"] == 4
    assert out["serve"]["bypass_submits"] == 0

    assert main(["serve", "demo", "--requests", "4", "--disagg"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["completions"] == 4
    assert out["serve"]["decode_pool_prefills"] == 0


# -- full-size soak (slow) ---------------------------------------------------


@pytest.mark.slow
def test_disagg_full_size_soak():
    """The bench-shaped model through federation chaos with the
    disaggregated backend behind gw0: a longer run with pool pressure,
    every invariant (books, mint bound, span continuity) gated by the
    harness, zero decode-pool prefills throughout."""
    from pbs_tpu.gateway import run_federation_chaos

    big = TransformerConfig(
        vocab=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq=128, dtype=jnp.float32)

    def factory(name, seed):
        return DisaggServeBackend(name, big, tp=1, dp=1, n_slots=8,
                                  prompt_bucket=16, max_len=64,
                                  seed=seed, clock="virtual")

    r = run_federation_chaos(workload="mixed", seed=0, n_gateways=3,
                             n_tenants=4, ticks=240, serve=factory)
    assert r["problems"] == []
    assert r["ok"] is True
    st = r["stats"]
    assert st["admitted"] == st["completed"] > 0
    sv = r["serve"][0]
    assert sv["completed"] > 0
    assert sv["decode_pool_prefills"] == 0
    # Determinism at full size too.
    again = run_federation_chaos(workload="mixed", seed=0, n_gateways=3,
                                 n_tenants=4, ticks=240, serve=factory)
    assert again["report_digest"] == r["report_digest"]
