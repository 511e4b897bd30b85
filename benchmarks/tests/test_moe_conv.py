"""The ``moe-conv-gqa`` family's benchmark files: the cost table against
a count by hand at the published widths and against the parameter
tree's and the cache's own byte counts, the configuration against the
guide's catalog row and its own arithmetic (layers read, bytes a lane,
what was reduced), the new cell's rehearsal end to end through the
harness (``correct`` true, the int8 control not), and the new metrics
on a hand-made trace."""
import json
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
CELL = "serve-conv-writing-surge"
CONFIG = "lfm2-24b-a2b"
NEW_METRICS = ("attn.conv_ms_p50", "attn.conv_prefill_ms_p50",
               "model.prefill_ms_p50.conv",
               "kernel.decode_tick_hbm_roofline.conv",
               "kernel.prefill_mxu_roofline.conv")
EXPERT = 3 * 2048 * 1536          # 9,437,184 parameters
CONV = 4 * 2048 * 2048            # in 2048 x 6144, out 2048 x 2048
ATTN = 2 * 2048 * 64 * (32 + 8)   # wq, wo 2048 x 2048; wk, wv 2048 x 512
DENSE = 3 * 2048 * 11776


def nbytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def test_costs_against_a_count_by_hand():
    c = SPEC.config(CONFIG)
    fam = SPEC.family(c["family"])
    k = fam.costs
    assert k.layer_kinds(c, 10) == {"conv": 8, "attention": 2, "dense": 2,
                                    "sparse": 8}
    assert k.layer_kinds(c, 40) == {"conv": 30, "attention": 10,
                                    "dense": 2, "sparse": 38}
    assert k.head_dim(c) == 64
    assert k.expert_params(c) == EXPERT == 9_437_184
    # a layer's 64 experts: 1.125 GiB in bfloat16
    assert k.expert_bytes(c, 64) == 64 * EXPERT * 2 == 9 * 2 ** 27
    # K and V of one position in an attention layer: 2 x 8 x 64 x 2 bytes
    assert k.kv_bytes_per_position(c) == 2048
    assert k.kv_read_bytes(c, 10, 200_000) == 2 * 2048 * 200_000
    # a lane's tail in one layer: two rows of 2048 in bfloat16
    assert k.tail_bytes_per_lane(c) == 8192
    # 250 busy lanes, 8 layers, read and written
    assert k.tail_bytes(c, 10, 250) == 250 * 8 * 2 * 8192 == 32_768_000
    assert k.conv_matrix_params(c) == CONV == 16_777_216
    # with the filter's three taps and the operator norm
    assert k.conv_mixer_params(c) == CONV + 4 * 2048
    assert k.attention_matrix_params(c) == ATTN == 10_485_760
    # with the operator norm and the two head norms of 64
    assert k.attention_mixer_params(c) == ATTN + 2048 + 128
    assert k.dense_params(c) == DENSE == 72_351_744
    # everything a tick reads once: 8 convolution and 2 attention
    # mixers, 2 dense MLPs and 8 routers with their norms, the final
    # norm and the tied embedding in bfloat16; 8 selection biases in
    # float32; 256 slots' new position in 2 attention layers
    params = 8 * (CONV + 8192) + 2 * (ATTN + 2176) + 2 * (DENSE + 2048) \
        + 8 * (2048 + 2048 * 64) + 2048 + 2048 * 65536
    assert k.other_weight_bytes(c, 10, 256) == params * 2 + 8 * 64 * 4 \
        + 2 * 256 * 2048
    # a forward of 700 tokens: two operations a matrix weight (all four
    # of a token's choices fall on held experts), the causal squares of
    # 2 attention layers, the gates and three taps of 8 convolutions,
    # one row of logits
    weights = 8 * CONV + 2 * ATTN + 2 * DENSE + 8 * (2048 * 64 + 4 * EXPERT)
    assert k.prefill_flops(c, 10, 700) == 2 * weights * 700 \
        + 2 * 2 * 32 * 64 * 700 ** 2 + 8 * 8 * 2048 * 700 \
        + 2 * 2048 * 65536
    sizes = {"experts_touched": 500.0, "live_positions": 200_000.0,
             "busy_lanes": 250.0, "prompt_tokens": 700.0}
    assert fam.COSTS["expert_matmul"](c, sizes) == {
        "bytes": 500 * EXPERT * 2}
    assert fam.COSTS["kv_read"](c, sizes) == {"bytes": 819_200_000}
    assert fam.COSTS["prefill_mxu"](c, sizes) == {
        "flops": k.prefill_flops(c, 10, 700)}
    assert fam.COSTS["decode_tick_conv"](c, sizes) == {
        "bytes": 500 * EXPERT * 2 + 819_200_000 + 32_768_000
        + k.other_weight_bytes(c, 10, 256)}
    for name, missing in (("expert_matmul", "experts_touched"),
                          ("kv_read", "live_positions"),
                          ("prefill_mxu", "prompt_tokens"),
                          ("decode_tick_conv", "busy_lanes"),
                          ("decode_tick_conv", "experts_touched"),
                          ("decode_tick_conv", "live_positions")):
        assert fam.COSTS[name](c, {**sizes, missing: None}) is None


def test_costs_against_the_trees_own_bytes():
    """What the cost table counts is what the program holds: the
    parameter tree the family serves and the cache the program makes,
    byte for byte; and both are what the configuration file's sizing
    says."""
    from pbs_tpu.models.serving import slot_program

    c = SPEC.config(CONFIG)
    fam = SPEC.family(c["family"])
    k, sv = fam.costs, c["serve"]
    tree = jax.eval_shape(lambda: fam.reference.init_tree(
        c, fam.reference.seed_word(0), 10, jnp.bfloat16))
    assert "head" not in tree                       # tied
    sparse = [b["mlp"] for b in tree["blocks"].values()
              if "we1" in b["mlp"]]
    assert len(sparse) == 8 and all("ws1" not in m for m in sparse)
    experts = sum(nbytes(m[n]) for m in sparse
                  for n in ("we1", "we3", "we2"))
    assert experts == 8 * k.expert_bytes(c, c["num_experts"]) \
        == 9 * 2 ** 30                              # 9.00 GiB
    slots = sv["slots"]
    assert k.other_weight_bytes(c, 10, slots) == nbytes(tree) - experts \
        + 2 * slots * k.kv_bytes_per_position(c)
    assert nbytes(tree) == c["sizing"]["weights_bytes"] == 10_534_181_376
    cache = jax.eval_shape(lambda: slot_program(fam.program_config(
        c, 10, sv["max_len"])).init_cache(slots, sv["max_len"]))
    assert len(cache["conv"]) == 8 and "ssm" not in cache
    assert set(cache["k"]) == {"02", "06"} == set(cache["v"])
    assert nbytes(cache["conv"]) == k.tail_bytes(c, 10, slots) // 2
    assert nbytes(cache["k"]) + nbytes(cache["v"]) \
        == k.kv_read_bytes(c, 10, slots * sv["max_len"]) == 3 * 2 ** 30
    lane = c["sizing"]["bytes_a_lane"]
    assert lane == 2 * sv["max_len"] * k.kv_bytes_per_position(c) \
        + 8 * k.tail_bytes_per_lane(c) == 12_648_448
    assert nbytes(cache) == slots * lane + slots * 4 \
        == c["sizing"]["cache_bytes"] + slots * 4


def test_the_configuration_is_the_catalog_row_but_for_the_cut():
    c = SPEC.config(CONFIG)
    entry = next(e for e in SPEC.bench["configs"] if e["name"] == CONFIG)
    reduced = ["max_position_embeddings", "num_hidden_layers"]
    assert sorted(entry["reduced"]) == sorted(c["reduced"]) == reduced
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    # the published values stand beside the reduced ones
    assert "40" in c["reduced"]["num_hidden_layers"]
    assert "128000" in c["reduced"]["max_position_embeddings"]
    assert (c["hidden_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["intermediate_size"],
            c["moe_intermediate_size"], c["num_experts"],
            c["num_experts_per_tok"], c["num_dense_layers"],
            c["conv_L_cache"], c["vocab_size"], c["routed_scaling_factor"],
            c["rope_parameters"]["rope_theta"], c["norm_eps"]) == (
                2048, 32, 8, 11776, 1536, 64, 4, 2, 3, 65536, 1, 1_000_000,
                1e-5)
    assert c["norm_topk_prob"] and c["use_expert_bias"] \
        and not c["conv_bias"] and c["tie_word_embeddings"]
    # layers 0-9 as published: one pipeline stage of four
    assert len(c["layer_types"]) == 40
    assert c["layer_types"][:10] == [
        "conv", "conv", "full_attention", "conv", "conv", "conv",
        "full_attention", "conv", "conv", "conv"]
    assert c["num_hidden_layers"] == c["serve"]["num_hidden_layers"] == 10
    d = c["deployment"]
    assert (d["chips_per_layer"], d["experts_total"], d["experts_first"],
            d["stages"], d["stage"], d["layers_per_stage"]) == (
                1, 64, 0, 4, 0, 10)
    assert d["stages"] * d["layers_per_stage"] == 40
    assert c["mlp_layer_types"] == ["dense"] * 2 + ["sparse"] * 38
    assert len(c["assumed"]) >= 8
    sv = c["serve"]
    assert (sv["slots"], sv["max_len"], sv["prompt_bucket"],
            sv["weights_dtype"]) == (256, 3072, 1024, "bfloat16")
    assert c["max_position_embeddings"] == sv["max_len"]
    # what the chip holds: well over a quarter of its 16 GiB
    held = c["sizing"]["weights_bytes"] + c["sizing"]["cache_bytes"]
    assert held == 10_534_181_376 + 256 * 12_648_448
    assert 0.75 < held / 2 ** 34 < 0.85
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "LFM2-24B-A2B")
    assert entry["source"] == c["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in reduced:
            assert c[key] == value, key
    assert row["config"]["num_hidden_layers"] == 40
    assert row["config"]["max_position_embeddings"] == 128000


JOINED_METRICS = (
    "gateway.backlog_growth_rps", "engine.occupancy_pct",
    "engine.ttft_p95_ms.surge", "model.decode_tick_ms_p50.surge",
    "device.idle_pct.serve", "engine.tick_host_inside_ms_p50.surge",
    "engine.idle_host_pct.surge", "engine.prefill_pad_pct.surge",
    "moe.experts_ms_p50", "moe.route_ms_p50", "moe.experts_touched_pct",
    "moe.absent_share_pct", "attn.full_ms_p50",
    "kernel.expert_matmul_hbm_roofline", "kernel.kv_read_hbm_roofline",
    "setup.import_s", "setup.runtime_init_s", "setup.programs_s",
    "setup.cache_hit_pct", "setup.construct_s", "setup.warmup_s",
    "setup.unaccounted_s")


def test_the_cell_lists_what_the_issue_listed():
    """Membership only: a later PR appends cells and metrics, to this
    cell's lists too, without touching this file."""
    bench = SPEC.bench
    cell = SPEC.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "writing-surge", 1) and len(cell["why"]) <= 200
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in JOINED_METRICS:
        assert CELL in by_name[name]["workloads"], name
    for name in NEW_METRICS:
        m = by_name[name]
        assert m["workloads"][0] == CELL
        assert m["moves"] == "output_tokens_per_s"
        assert SPEC.metric_file(name)["layer"] == m["layer"]
        assert (m["unit"] == "%") == ("roofline" in name)
    assert {"output_tokens_per_s", "setup_s"} <= {
        m["name"] for m in SPEC.metrics_of(CELL, "end_to_end")}
    sv = SPEC.traffic("writing-surge")["serve"]
    assert sv["prompt_len"] == {"dist": "lognormal", "median": 384,
                                "sigma": 0.8, "min": 64, "max": 1024}
    assert sv["output_len"] == {"dist": "lognormal", "median": 768,
                                "sigma": 0.7, "min": 128, "max": 2048}
    serve = SPEC.config(CONFIG)["serve"]
    assert sv["prompt_len"]["max"] == serve["prompt_bucket"]
    assert sv["prompt_len"]["max"] + sv["output_len"]["max"] \
        == serve["max_len"]
    assert sv["burst"] == {"factor": 3.0, "on_s": 2.0, "period_s": 10.0}
    assert sv["warmup_s"] == 30 and sv["pool"] == 32 \
        and sv["order"] == "rotate" and sv["loop"] == "open"
    # the rate is 1.3 x the knee the file's ``why`` names
    why = SPEC.traffic("writing-surge")["why"]
    assert f"{sv['rate_rps']:g} req/s" in why and "1.3 x" in why


def rehearse(trace_flag: str, *extra):
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELL, "--seed",
         "4700000021", "--seconds", "2", "--trace", trace_flag,
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
    # every expert is held: nothing a token chose is some other chip's
    assert m["rehearsal_moe.absent_share_pct"]["value"] == 0
    assert m["rehearsal_engine.occupancy_pct"]["value"] > 0
    assert m["rehearsal_model.decode_tick_ms_p50.surge"]["value"] > 0
    # a CPU names no scope in its trace and has no roofline: the new
    # metrics are left out there, never 0 (read on a hand-made trace
    # below, and on the chip), but for the whole forward's time, which
    # counts every op whatever its scope
    assert not any(name in k for k in m for name in NEW_METRICS
                   if name != "model.prefill_ms_p50.conv")
    assert any(l.startswith("ring: routing, decode ticks:") for l in lines)
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
    """One 20 ms decode tick whose ops under ``attn.conv`` take 1.5 ms,
    and four prompt forwards: three at the bucket's 1024 rows (prompts
    of 600, 800 and 700 tokens; 17, 21 and 19 ms of ops, 2, 4 and 3 of
    them under ``attn.conv``) and one at 512 rows, which the prefill
    metrics leave out; 250 lanes busy, 500 held experts touched,
    200,000 positions live. The ring's clock runs 1 ms behind the
    trace's."""
    from pbs_tpu.obs import trace as pt
    from pbs_tpu.obs.trace import Ev, TraceBuffer

    dev, ms = "/device:TPU:0", 1_000_000

    def ev(line, name, start, dur, scope=None, plane=dev):
        e = {"plane": plane, "line": line, "name": name, "start": start,
             "dur": dur}
        if scope:
            e["scope"] = scope
        return e

    dec, pre = "jit(_decode)/attn.conv/", "jit(_prefill)/attn.conv/"
    events = [
        ev("XLA Modules", "jit__decode(7)", 1_000, 20 * ms),
        ev("XLA Ops", "fusion.1", 2_000, 1 * ms, dec + "dot_general"),
        ev("XLA Ops", "fusion.2", 2 * ms, ms // 2,
           dec + "conv.step/reduce_sum"),
        ev("XLA Ops", "fusion.4", 3 * ms, 14 * ms,
           "jit(_decode)/moe.experts/grouped_matmul"),
        ev("XLA Ops", "fusion.5", 18 * ms, 1 * ms,
           "jit(_decode)/attn.full/kv_attend")]
    ring = TraceBuffer(64)
    # (start, rows, prompt tokens, ms under attn.conv, all)
    for rid, (at, rows, plen, mixer, whole) in enumerate((
            (50 * ms, 1024, 600, 2, 17),
            (130 * ms, 512, 300, 1, 15),
            (215 * ms, 1024, 800, 4, 21),
            (290 * ms, 1024, 700, 3, 19))):
        events += [
            ev("XLA Modules", f"jit__prefill({9 + rows})", at, 25 * ms),
            ev("XLA Ops", "fusion.6", at + ms, mixer * ms,
               pre + "conv.filter/mul"),
            ev("XLA Ops", "fusion.8", at + 5 * ms, (whole - mixer) * ms,
               "jit(_prefill)/moe.experts/grouped_matmul"),
            ev("threads", "bench.serve_step", at - 2 * ms, 30 * ms,
               plane="/host:CPU")]
        ring.emit(at - 3 * ms, Ev.ENG_TICK, 30 * ms, rid, 250, 1, 0, 0)
        ring.emit(at - 2 * ms, Ev.ENG_PREFILL, rid, rid, 0, 1 * ms,
                  26 * ms, rows)
        ring.emit(at - 2 * ms - 1000, Ev.ENG_ADMIT, rid, rid, 0, plen, 7,
                  28 * ms)
    monkeypatch.setattr(pt, "live_rings", lambda: [("engine", ring)])
    routes = np.array([[500, 0xA07, 3, 250, 8000, 0, 500, 30]])
    monkeypatch.setattr(_route, "decode_routes",
                        lambda ctx, traced=False: routes)
    reqs = [{"admit": 0.05, "done": None, "prompt": [0] * 199_000,
             "stamps": [(0.1, 1_000)]}]
    ctx = context(events, requests=reqs,
                  ticks=[(0.09, 0.1, 250, 200_000)])
    got = {name: SPEC.reader(SPEC.metric_file(name)["reader"])(
        ctx, **SPEC.metric_file(name)["args"]) for name in NEW_METRICS}
    assert got["attn.conv_ms_p50"] == 1.5
    # the three forwards at 1024 rows; not the one at 512
    assert got["attn.conv_prefill_ms_p50"] == 3.0
    assert got["model.prefill_ms_p50.conv"] == 19.0
    k = ctx.family.costs
    # their mean prompt of 700 tokens at 197 TFLOP/s, of 19 ms
    assert abs(got["kernel.prefill_mxu_roofline.conv"] - 100 * (
        k.prefill_flops(ctx.config, 10, 700) / 197e12) / 19e-3) < 1e-9
    # the whole tick: touched experts, live keys and values, the busy
    # lanes' tails and every other weight once, of 20 ms
    need = 500 * EXPERT * 2 + 819_200_000 + 32_768_000 \
        + k.other_weight_bytes(ctx.config, 10, 256)
    assert abs(got["kernel.decode_tick_hbm_roofline.conv"]
               - 100 * (need / 819e9) / 20e-3) < 1e-9
    assert 0 < got["kernel.decode_tick_hbm_roofline.conv"] < 100
    # and the accepted readers this cell joins read the same trace
    for name, want in (("attn.full_ms_p50", 1.0),
                       ("moe.experts_ms_p50", 14.0)):
        mf = SPEC.metric_file(name)
        assert SPEC.reader(mf["reader"])(ctx, **mf["args"]) == want
