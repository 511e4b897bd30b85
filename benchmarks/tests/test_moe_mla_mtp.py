"""The ``moe-mla-mtp`` family's benchmark files: the cost table against
a count by hand at the published widths and against the parameter
tree's and the cache's own byte counts, the configuration against the
guide's catalog row and its own arithmetic, the reference's YaRN and
router against their written forms, the new cell's rehearsal end to end
through the harness (``correct`` true, the int8 control not), and the
new metrics on a hand-made trace."""
import json
import math
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import measure
from benchmarks.harness.spec import Spec
from benchmarks.readers import _route

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SPEC = Spec()
CELL = "serve-mtp-reasoning-surge"
CONFIG = "deepseek-v3"
NEW_METRICS = ("spec.accept_pct", "spec.draft_ms_p50",
               "model.prefill_ms_p50.mtp",
               "kernel.decode_tick_hbm_roofline.mtp",
               "kernel.prefill_mxu_roofline.mtp")
D = 7168
EXPERT = 3 * D * 2048                 # 44,040,192 parameters
MIXER = (D * 1536 + 1536 * 128 * 192 + D * 576 + 512 * 128 * 256
         + 128 * 128 * D)            # 187,105,280 in its five matrices
DENSE = 3 * D * 18432
ROW = (512 + 64) * 2                  # a position's bytes, all heads


def nbytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def test_costs_against_a_count_by_hand():
    c = SPEC.config(CONFIG)
    fam = SPEC.family(c["family"])
    k = fam.costs
    assert k.layer_kinds(c, 5) == {"dense": 1, "sparse": 5, "mixers": 6,
                                   "draft": 1}
    assert k.latent_row_bytes(c) == ROW == 1152
    # ENG_SELECT counts a row a query, two a lane: the rows are read once
    assert k.latent_read_bytes(c, 5, 300_000) == 150_000 * ROW * 6
    assert k.expert_params(c) == EXPERT == 44_040_192
    assert k.expert_bytes(c, 70) == 70 * EXPERT * 2
    assert k.mixer_matrix_params(c) == MIXER == 187_105_280
    assert k.mixer_params(c) == MIXER + D + 1536 + 512
    assert k.shared_params(c) == EXPERT and k.dense_params(c) == DENSE
    assert k.draft_join_params(c) == 2 * D * D + 3 * D
    # everything a tick reads once: six mixers, the dense layer, five
    # routers and shared experts with their norms, the drafting
    # module's projection and norms, the final norm and the head, two
    # embedding rows a slot, in bfloat16; five selection biases in
    # float32; two new rows a slot and latent layer
    params = 6 * (MIXER + D + 2048) + (D + DENSE) \
        + 5 * (D + D * 256 + EXPERT) + 2 * D * D + 3 * D \
        + D + D * 16160 + 2 * 128 * D
    assert k.other_tick_bytes(c, 5, 128) == params * 2 + 5 * 256 * 4 \
        + 6 * 2 * 128 * ROW
    # a prompt's attention: the causal triangle, a head at a time
    assert k.mla_prefill_flops(c, 5, 700) == 6 * (700 * 701 / 2) \
        * 2 * 128 * (192 + 128)
    # a forward of 700 tokens: two operations a matrix weight (half a
    # token's eight choices' sixteenth falls on held experts), the
    # attention above, the logits of the last position twice
    weights = 6 * MIXER + DENSE \
        + 5 * (D * 256 + EXPERT + 8 * 16 / 256 * EXPERT) + 2 * D * D
    assert k.prefill_flops(c, 5, 700) == 2 * weights * 700 \
        + k.mla_prefill_flops(c, 5, 700) + 2 * 2 * D * 16160
    sizes = {"experts_touched": 70.0, "chosen_positions": 300_000.0,
             "prompt_tokens": 700.0}
    assert fam.COSTS["expert_matmul"](c, sizes) == {
        "bytes": 70 * EXPERT * 2}
    assert fam.COSTS["latent_read"](c, sizes) == {
        "bytes": 150_000 * ROW * 6}
    assert fam.COSTS["mla_prefill"](c, sizes) == {
        "flops": k.mla_prefill_flops(c, 5, 700)}
    assert fam.COSTS["prefill_mxu"](c, sizes) == {
        "flops": k.prefill_flops(c, 5, 700)}
    assert fam.COSTS["decode_tick_mtp"](c, sizes) == {
        "bytes": 70 * EXPERT * 2 + 150_000 * ROW * 6
        + k.other_tick_bytes(c, 5, 128)}
    for name, missing in (("expert_matmul", "experts_touched"),
                          ("latent_read", "chosen_positions"),
                          ("mla_prefill", "prompt_tokens"),
                          ("prefill_mxu", "prompt_tokens"),
                          ("decode_tick_mtp", "experts_touched"),
                          ("decode_tick_mtp", "chosen_positions")):
        assert fam.COSTS[name](c, {**sizes, missing: None}) is None


def test_costs_against_the_trees_own_bytes():
    """What the cost table counts is what the program holds: the
    parameter tree the family serves (the drafting module among it) and
    the cache the program makes, byte for byte; and both are what the
    configuration file's sizing says."""
    from pbs_tpu.models.serving import slot_program

    c = SPEC.config(CONFIG)
    fam = SPEC.family(c["family"])
    k, sv = fam.costs, c["serve"]
    tree = jax.eval_shape(lambda: fam.reference.init_tree(
        c, fam.reference.seed_word(0), 5, jnp.bfloat16))
    assert set(tree["blocks"]) == {"00", "01", "02", "03", "04", "mtp"}
    assert set(tree["blocks"]["mtp"]) == {
        "enorm", "hnorm", "eh_proj", "head_norm", "attn", "mlp"}
    assert "wi_q" not in tree["blocks"]["01"]["attn"]
    sparse = [b["mlp"] for b in tree["blocks"].values()
              if "we1" in b["mlp"]]
    assert len(sparse) == 5
    experts = sum(nbytes(m[n]) for m in sparse
                  for n in ("we1", "we3", "we2"))
    assert experts == 5 * k.expert_bytes(c, 16)
    slots = sv["slots"]
    # a tick reads two embedding rows a slot, not the embedding
    assert k.other_tick_bytes(c, 5, slots) == nbytes(tree) - experts \
        - nbytes(tree["embed"]) + 2 * slots * D * 2 \
        + 6 * 2 * slots * ROW
    assert nbytes(tree) == c["sizing"]["weights_bytes"] == 11_212_289_024
    assert sum(x.size for x in jax.tree.leaves(tree)) == 5_606_143_232
    cache = jax.eval_shape(lambda: slot_program(fam.program_config(
        c, 5, sv["max_len"])).init_cache(slots, sv["max_len"]))
    assert set(cache["ckv"]) == set(cache["kr"]) == set(tree["blocks"])
    assert "ik" not in cache and not cache["k"] and not cache["v"]
    assert c["sizing"]["bytes_a_position"] == 6 * ROW
    assert nbytes(cache["ckv"]) + nbytes(cache["kr"]) \
        == slots * sv["max_len"] * 6 * ROW == c["sizing"]["cache_bytes"]
    # and the lanes' own state: cursor, last token, draft
    assert nbytes(cache) == c["sizing"]["cache_bytes"] + 3 * slots * 4


def test_the_configuration_is_the_catalog_row_but_for_the_cut():
    c = SPEC.config(CONFIG)
    entry = next(e for e in SPEC.bench["configs"] if e["name"] == CONFIG)
    reduced = ["first_k_dense_replace", "max_position_embeddings",
               "n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert sorted(entry["reduced"]) == sorted(c["reduced"]) == reduced
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    for key, published in (("num_hidden_layers", "61"),
                           ("first_k_dense_replace", "3"),
                           ("n_routed_experts", "256"),
                           ("vocab_size", "129280"),
                           ("max_position_embeddings", "163840")):
        assert published in c["reduced"][key], key
    assert (c["hidden_size"], c["num_attention_heads"], c["q_lora_rank"],
            c["kv_lora_rank"], c["qk_nope_head_dim"],
            c["qk_rope_head_dim"], c["v_head_dim"], c["intermediate_size"],
            c["moe_intermediate_size"], c["num_experts_per_tok"],
            c["n_group"], c["topk_group"], c["n_shared_experts"],
            c["routed_scaling_factor"], c["rope_theta"],
            c["rms_norm_eps"]) == (
                7168, 128, 1536, 512, 128, 64, 128, 18432, 2048, 8, 8, 4,
                1, 2.5, 10000, 1e-6)
    assert c["num_nextn_predict_layers"] == 1   # run, not reduced
    assert c["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    assert (c["num_hidden_layers"], c["first_k_dense_replace"],
            c["n_routed_experts"], c["vocab_size"]) == (5, 1, 16, 16160)
    assert c["vocab_size"] * 8 == 129280
    d = c["deployment"]
    assert (d["chips_per_layer"], d["experts_total"],
            d["experts_first"]) == (16, 256, 0)
    assert d["chips_per_layer"] * c["n_routed_experts"] == 256
    assert "drafting" in d["what"] or "multi-token" in d["what"]
    # the harness's count of the expert layers a tick runs: four of the
    # stack and the drafting module's
    sv = c["serve"]
    assert c["mlp_layer_types"][:sv["num_hidden_layers"]].count(
        "sparse") == 5 and c["num_experts"] == 16
    assert len(c["assumed"]) >= 7
    assert any("by chance" in a for a in c["assumed"])
    assert (sv["slots"], sv["max_len"], sv["prompt_bucket"],
            sv["weights_dtype"], sv["num_hidden_layers"]) == (
                128, 2560, 1024, "bfloat16", 5)
    assert c["max_position_embeddings"] == sv["max_len"]
    held = c["sizing"]["weights_bytes"] + c["sizing"]["cache_bytes"]
    assert 0.75 < held / 2 ** 34 < 0.85
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "DeepSeek-V3")
    assert entry["source"] == c["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in reduced:
            assert c[key] == value, key
    assert row["config"]["num_hidden_layers"] == 61


def test_the_references_yarn_is_the_programs_and_the_written_form():
    """The reference's frequencies and scale against the program's own
    table and against the numbers the issue writes."""
    from pbs_tpu.models import plan as P

    c = SPEC.config(CONFIG)
    fam = SPEC.family(c["family"])
    ref = fam.reference
    kind = fam.layer_plan(c, 5).attn[0]
    assert not kind.selects and kind.rows == ("ckv", "kr")
    assert np.allclose(ref.yarn_inv_freq(c), P.inv_freq(kind.rope, 192),
                       rtol=1e-12)
    m = 0.1 * math.log(40) + 1
    assert abs(m - 1.3689) < 1e-4 and kind.mscale == m
    assert abs(kind.scale - 192 ** -0.5 * m * m) < 1e-15
    assert abs(ref.softmax_scale(c) - kind.scale) < 1e-15
    assert kind.rope.attention_factor == 1.0 and kind.rope.interleave
    # the slowest pairs are stretched forty times, the fastest not at all
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    got = ref.yarn_inv_freq(c)
    assert got[0] == plain[0] and abs(got[-1] * 40 - plain[-1]) < 1e-12
    plan = fam.layer_plan(c, 5)
    assert plan.draft == (0, 1) and plan.takes_window
    experts = plan.mlp[1]
    assert (experts.n_group, experts.topk_group, experts.top_k,
            experts.held, experts.n_experts) == (8, 4, 8, (0, 16), 256)


JOINED_METRICS = (
    "gateway.backlog_growth_rps", "engine.occupancy_pct",
    "engine.ttft_p95_ms.surge", "model.decode_tick_ms_p50.surge",
    "device.idle_pct.serve", "engine.tick_host_inside_ms_p50.surge",
    "engine.idle_host_pct.surge", "engine.prefill_pad_pct.surge",
    "moe.experts_ms_p50", "moe.route_ms_p50", "moe.experts_touched_pct",
    "moe.absent_share_pct", "kernel.expert_matmul_hbm_roofline",
    "attn.mla_ms_p50", "attn.mla_prefill_ms_p50",
    "kernel.latent_read_hbm_roofline", "kernel.mla_prefill_mxu_roofline",
    "setup.import_s", "setup.runtime_init_s", "setup.programs_s",
    "setup.cache_hit_pct", "setup.construct_s", "setup.warmup_s",
    "setup.unaccounted_s")


def test_the_cell_lists_what_the_issue_listed():
    """Membership only: a later PR appends cells and metrics, to this
    cell's lists too, without touching this file."""
    bench = SPEC.bench
    cell = SPEC.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "reasoning-surge", 1) and len(cell["why"]) <= 200
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert len(entry["why"]) <= 200 and len(entry["source"]) <= 200
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in JOINED_METRICS:
        assert CELL in by_name[name]["workloads"], name
    for name in NEW_METRICS:
        m = by_name[name]
        assert m["workloads"] == [CELL] or m["workloads"][0] == CELL
        assert m["moves"] == "output_tokens_per_s"
        assert SPEC.metric_file(name)["layer"] == m["layer"]
        assert (m["unit"] == "%") == ("roofline" in name or "pct" in name)
    assert {"output_tokens_per_s", "setup_s"} <= {
        m["name"] for m in SPEC.metrics_of(CELL, "end_to_end")}
    sv = SPEC.traffic("reasoning-surge")["serve"]
    assert sv["prompt_len"] == {"dist": "lognormal", "median": 384,
                                "sigma": 0.7, "min": 64, "max": 1024}
    assert sv["output_len"] == {"dist": "lognormal", "median": 1024,
                                "sigma": 0.6, "min": 256, "max": 1500}
    serve = SPEC.config(CONFIG)["serve"]
    assert sv["prompt_len"]["max"] == serve["prompt_bucket"]
    # the window's two rows fit behind the longest request
    assert sv["prompt_len"]["max"] + sv["output_len"]["max"] + 2 \
        <= serve["max_len"]
    assert sv["burst"] == {"factor": 3.0, "on_s": 2.0, "period_s": 10.0}
    assert sv["warmup_s"] == 40 and sv["pool"] == 32 \
        and sv["order"] == "rotate" and sv["loop"] == "open"
    why = SPEC.traffic("reasoning-surge")["why"]
    assert f"{sv['rate_rps']:g} req/s" in why and "1.3 x" in why


def rehearse(trace_flag: str, *extra):
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELL, "--seed",
         "5000000021", "--seconds", "2", "--trace", trace_flag,
         "--rehearsal", *extra], cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    readings = {k: float(v) for k, _, v in (
        line[len("check-reading "):].partition(": ") for line in lines
        if line.startswith("check-reading "))}
    return json.loads(lines[-1]), readings, lines


def test_the_cell_rehearses_correct_and_its_int8_control_does_not():
    limits = SPEC.config(CONFIG)["rehearsal"]["check"]["serving"]
    out, control, lines = rehearse("1", "--control", "1")
    assert out["correct"] and out["failed"] == 0
    assert set(out["check"]) == set(limits)
    assert control["control_gap_max"] > 3 * limits["served_gap_max"]
    assert control["control_gap_mean"] > 3 * limits["served_gap_mean"]
    m = out["metrics"]
    assert 0 < m["rehearsal_moe.experts_touched_pct"]["value"] <= 100
    assert 0 < m["rehearsal_moe.absent_share_pct"]["value"] < 100
    assert m["rehearsal_engine.occupancy_pct"]["value"] > 0
    assert m["rehearsal_model.decode_tick_ms_p50.surge"]["value"] > 0
    # the acceptance is a counter: read on a CPU too
    assert 0 <= m["rehearsal_spec.accept_pct"]["value"] <= 100
    # a CPU names no scope in its trace and has no roofline: those
    # metrics are left out there, never 0
    assert not any(name in k for k in m for name in NEW_METRICS
                   if name not in ("model.prefill_ms_p50.mtp",
                                   "spec.accept_pct"))
    assert any(l.startswith("ring: drafting, decodes read") for l in lines)
    out, _, _ = rehearse("0")
    assert out["correct"]
    assert {"rehearsal_output_tokens_per_s",
            "rehearsal_setup_s"} <= set(out["metrics"])


def context(events, **over):
    c = SPEC.config(CONFIG)
    kw = dict(family=SPEC.family(c["family"]), config=c, traffic={},
              device_kind="TPU v5 lite", t0=0.0, t1=1.0, requests=[],
              ticks=[], train_steps=0, train_tokens_per_step=0,
              events=events, trace_span=(0.0, 1.0), ledger_trace={},
              backlog=None)
    kw.update(over)
    return measure.Context(**kw)


def test_every_new_metric_reads_a_hand_made_trace(monkeypatch):
    """One 25 ms drafting tick whose ops under ``mtp.draft`` take 4 ms
    (its own ``attn.mla`` and ``moe.experts`` inside), and four prompt
    forwards: three at the bucket's 1024 rows (prompts of 600, 800 and
    700 tokens; 40, 48 and 44 ms of ops) and one at 512 rows, which the prefill
    metrics leave out; 120 lanes busy (240 queries seeing 300,000
    positions), 70 held experts touched; two decodes read, which
    proposed 240 drafts and accepted 3. The ring's clock runs 1 ms
    behind the trace's."""
    from pbs_tpu.obs import trace as pt
    from pbs_tpu.obs.trace import Ev, TraceBuffer

    dev, ms = "/device:TPU:0", 1_000_000

    def ev(line, name, start, dur, scope=None, plane=dev):
        e = {"plane": plane, "line": line, "name": name, "start": start,
             "dur": dur}
        if scope:
            e["scope"] = scope
        return e

    dec = "jit(_decode_window)/"
    events = [
        ev("XLA Modules", "jit__decode_window(7)", 1_000, 25 * ms),
        ev("XLA Ops", "fusion.1", 2_000, 6 * ms,
           dec + "attn.mla/mla.attend/mla_attend_window"),
        ev("XLA Ops", "fusion.2", 7 * ms, 9 * ms,
           dec + "moe.experts/dot_general"),
        ev("XLA Ops", "fusion.3", 17 * ms, ms // 2,
           dec + "mtp.verify/argmax"),
        ev("XLA Ops", "fusion.4", 18 * ms, 1 * ms,
           dec + "mtp.draft/attn.mla/mla.attend/mla_attend_window"),
        ev("XLA Ops", "fusion.5", 19 * ms, 2 * ms,
           dec + "mtp.draft/moe.experts/dot_general"),
        ev("XLA Ops", "fusion.6", 21 * ms, 1 * ms,
           dec + "mtp.draft/dot_general")]
    ring = TraceBuffer(64)
    for rid, (at, rows, plen, whole) in enumerate((
            (50 * ms, 1024, 600, 40), (130 * ms, 512, 300, 20),
            (215 * ms, 1024, 800, 48), (290 * ms, 1024, 700, 44))):
        events += [
            ev("XLA Modules", f"jit__prefill({9 + rows})", at, 50 * ms),
            ev("XLA Ops", "fusion.8", at + ms, whole * ms,
               "jit(_prefill)/attn.mla/mla.attend/dot_general"),
            ev("threads", "bench.serve_step", at - 2 * ms, 55 * ms,
               plane="/host:CPU")]
        ring.emit(at - 3 * ms, Ev.ENG_TICK, 55 * ms, rid, 120, 1, 0, 0)
        ring.emit(at - 2 * ms, Ev.ENG_PREFILL, rid, rid, 0, 1 * ms,
                  51 * ms, rows)
        ring.emit(at - 2 * ms - 1000, Ev.ENG_ADMIT, rid, rid, 0, plen, 7,
                  53 * ms)
    ring.emit(400 * ms, Ev.ENG_DECODE, 5, 1000, 2000, 3000, 1, 0)
    ring.emit(400 * ms, Ev.ENG_SELECT, 5, 240, 300_000, 300_000, 2560, 0)
    ring.emit(401 * ms, Ev.ENG_DRAFT, 5, 120, 120, 1, 121, 0)
    ring.emit(426 * ms, Ev.ENG_DRAFT, 6, 120, 120, 2, 121, 1)
    monkeypatch.setattr(pt, "live_rings", lambda: [("engine", ring)])
    routes = np.array([[400 * ms, 0xA07, 5, 240, 120, 1800, 70, 9]])
    monkeypatch.setattr(_route, "decode_routes",
                        lambda ctx, traced=False: routes)
    ctx = context(events, ticks=[(0.4, 0.425, 120, 150_000)])
    got = {name: SPEC.reader(SPEC.metric_file(name)["reader"])(
        ctx, **SPEC.metric_file(name)["args"]) for name in NEW_METRICS}
    assert got["spec.accept_pct"] == 100 * 3 / 240
    assert got["spec.draft_ms_p50"] == 4.0
    # the three forwards at 1024 rows; not the one at 512
    assert got["model.prefill_ms_p50.mtp"] == 44.0
    k = ctx.family.costs
    assert abs(got["kernel.prefill_mxu_roofline.mtp"] - 100 * (
        k.prefill_flops(ctx.config, 5, 700) / 197e12) / 44e-3) < 1e-9
    need = 70 * EXPERT * 2 + 150_000 * ROW * 6 \
        + k.other_tick_bytes(ctx.config, 5, 128)
    assert abs(got["kernel.decode_tick_hbm_roofline.mtp"]
               - 100 * (need / 819e9) / 25e-3) < 1e-9
    assert 0 < got["kernel.decode_tick_hbm_roofline.mtp"] < 100
    # and the accepted readers this cell joins read the same trace
    # through this family's costs: the stack's and the drafting block's
    # ops under one scope name
    for name, want in (("attn.mla_ms_p50", 7.0),
                       ("moe.experts_ms_p50", 11.0)):
        mf = SPEC.metric_file(name)
        assert SPEC.reader(mf["reader"])(ctx, **mf["args"]) == want
    mf = SPEC.metric_file("kernel.latent_read_hbm_roofline")
    assert abs(SPEC.reader(mf["reader"])(ctx, **mf["args"]) - 100 * (
        150_000 * ROW * 6 / 819e9) / 7e-3) < 1e-9
    mf = SPEC.metric_file("kernel.mla_prefill_mxu_roofline")
    share = SPEC.reader(mf["reader"])(ctx, **mf["args"])
    assert 0 < share < 100
    # a program without the records gives nothing, and does not raise
    empty = TraceBuffer(8)
    monkeypatch.setattr(pt, "live_rings", lambda: [("engine", empty)])
    assert SPEC.reader("draft_accept_pct")(context(events)) is None
