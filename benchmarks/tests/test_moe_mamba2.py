"""The ``moe-mamba2-gqa`` family's benchmark files: the cost table
against hand values at the published widths and against the parameter
tree's and the cache's own byte counts, the configuration against the
guide's catalog row, the new cell's rehearsal (``correct`` true, the
int8 control not), the family's state control, and the new metrics on a
hand-made trace."""
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
CELL = "serve-mamba2-tooluse-surge"
CONFIG = "nemotron-3-nano-30b-a3b"
NEW_METRICS = ("attn.mamba2_ms_p50", "attn.mamba2_prefill_ms_p50",
               "model.prefill_ms_p50.mamba2",
               "kernel.mamba2_state_hbm_roofline",
               "kernel.mamba2_scan_mxu_roofline",
               "kernel.prefill_mxu_roofline.mamba2",
               "kernel.decode_tick_hbm_roofline.mamba2")
STATE = 100 * 12 * 2 * 2_134_016
SCAN = 1500 * 12 * 2_757_632


def nbytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def test_costs_against_hand_values():
    c = SPEC.config(CONFIG)
    fam = SPEC.family(c["family"])
    k = fam.costs
    assert k.layer_kinds(c, 26) == {"mamba": 12, "attention": 3,
                                    "experts": 11}
    assert k.layer_kinds(c, 52) == {"mamba": 23, "attention": 6,
                                    "experts": 23}
    # one routed expert: up and down of 2688 x 1856, no gate
    assert k.expert_params(c) == 2 * 2688 * 1856 == 9_977_856
    # K and V of one position in an attention block: 2 x 2 x 128 x 2 bytes
    assert k.kv_bytes_per_position(c) == 1024
    assert k.kv_read_bytes(c, 26, 150_000) == 3 * 1024 * 150_000
    # a lane's state in one block: 64 heads x 64 x 128 float32 = 2 MiB,
    # and the last 3 inputs of 6144 channels in bf16
    assert k.conv_channels(c) == 6144
    assert k.state_bytes_per_lane(c) == 2_097_152 + 3 * 6144 * 2 \
        == 2_134_016
    # 100 busy lanes, 12 blocks, read and written
    assert k.mamba2_state_bytes(c, 26, 100) == STATE == 5_121_638_400
    # in-projection 2688 x (4096 + 6144 + 64), out-projection 4096 x 2688
    assert k.mamba2_matrix_params(c) == 2688 * 10_304 + 4096 * 2688 \
        == 38_707_200
    # with the filter and its bias (5 x 6144), the gated norm, the norm
    assert k.mamba2_mixer_params(c) == 38_707_200 + 30_720 + 4096 + 2688
    assert k.mamba2_float32_params(c) == 3 * 64
    # wq, wo 2688 x 4096; wk, wv 2688 x 256
    assert k.attention_matrix_params(c) == 2 * 11_010_048 + 2 * 688_128
    assert k.shared_params(c) == 2 * 2688 * 3712
    # the scan at chunks of 128, a position a block: against its 64.5
    # pairs C . B in 8 groups (2 x 128) and the input in 64 heads (2 x
    # 64); into and out of the state 4 x 64 x 128 a head
    assert k.mamba2_scan_flops(c, 26, 1, 128) == 12 * (
        64.5 * (8 * 256 + 64 * 128) + 64 * 4 * 64 * 128) == 12 * 2_757_632
    assert k.mamba2_scan_flops(c, 26, 1500, 128) == SCAN
    # the whole forward of 1500 tokens: two operations a matrix weight
    # (1.5 of a token's 6 choices fall on the 32 held of 128), the
    # causal squares of 3 attention blocks, the scans, one row of logits
    weights = 12 * 38_707_200 + 3 * 23_396_352 + 11 * (
        2688 * 128 + 19_955_712 + 1.5 * 9_977_856)
    assert k.prefill_flops(c, 26, 1500, 128) == 2 * weights * 1500 \
        + 3 * 2 * 32 * 128 * 1500 ** 2 + SCAN + 2 * 2688 * 32768
    sizes = {"experts_touched": 300.0, "live_positions": 150_000.0,
             "busy_lanes": 100.0, "prompt_tokens": 1500.0}
    assert fam._chunk() == 128
    assert fam.COSTS["expert_matmul"](c, sizes) == {
        "bytes": 300 * 9_977_856 * 2}
    assert fam.COSTS["kv_read"](c, sizes) == {"bytes": 460_800_000}
    assert fam.COSTS["mamba2_state"](c, sizes) == {"bytes": STATE}
    assert fam.COSTS["mamba2_scan"](c, sizes) == {"flops": SCAN}
    assert fam.COSTS["prefill_mxu"](c, sizes) == {
        "flops": k.prefill_flops(c, 26, 1500, 128)}
    assert fam.COSTS["decode_tick_mamba2"](c, sizes) == {
        "bytes": 5_986_713_600 + 460_800_000 + STATE
        + k.other_weight_bytes(c, 26, 128)}
    for name, missing in (("expert_matmul", "experts_touched"),
                          ("kv_read", "live_positions"),
                          ("mamba2_state", "busy_lanes"),
                          ("mamba2_scan", "prompt_tokens"),
                          ("prefill_mxu", "prompt_tokens"),
                          ("decode_tick_mamba2", "busy_lanes")):
        assert fam.COSTS[name](c, {**sizes, missing: None}) is None


def test_costs_against_the_trees_own_bytes():
    """What the cost table counts is what the program holds: the
    parameter tree the family serves and the cache the program makes,
    byte for byte."""
    from pbs_tpu.models.serving import slot_program

    c = SPEC.config(CONFIG)
    fam = SPEC.family(c["family"])
    k, sv = fam.costs, c["serve"]
    tree = jax.eval_shape(lambda: fam.reference.init_tree(
        c, fam.reference.seed_word(0), 26, jnp.bfloat16))
    sparse = [b["mlp"] for b in tree["blocks"].values() if "mlp" in b]
    assert len(sparse) == 11 and all(
        "we3" not in m and "ws3" not in m for m in sparse)
    experts = sum(nbytes(m[n]) for m in sparse for n in ("we1", "we2"))
    assert experts == 11 * k.expert_bytes(c, c["n_routed_experts"])
    # everything else once, but one embedding row a slot and not the
    # table, plus the tick's new position of keys and values
    rest = nbytes(tree) - experts - nbytes(tree["embed"])
    slots = sv["slots"]
    assert k.other_weight_bytes(c, 26, slots) == rest \
        + slots * 2688 * 2 + 3 * slots * k.kv_bytes_per_position(c)
    assert nbytes(tree) == 8_893_673_728  # 8.283 GiB
    cache = jax.eval_shape(lambda: slot_program(fam.program_config(
        c, 26, sv["max_len"])).init_cache(slots, sv["max_len"]))
    assert len(cache["ssm"]) == 12 == len(cache["conv"])
    assert set(cache["k"]) == {"05", "12", "19"} == set(cache["v"])
    assert nbytes(cache["ssm"]) + nbytes(cache["conv"]) \
        == k.mamba2_state_bytes(c, 26, slots) // 2
    assert nbytes(cache["k"]) + nbytes(cache["v"]) \
        == k.kv_read_bytes(c, 26, slots * sv["max_len"])
    assert nbytes(cache) == 4_485_808_640  # 4.178 GiB


def test_the_configuration_is_the_catalog_row_but_for_the_cut():
    c = SPEC.config(CONFIG)
    entry = next(e for e in SPEC.bench["configs"] if e["name"] == CONFIG)
    reduced = ["max_position_embeddings", "n_routed_experts",
               "num_hidden_layers", "vocab_size"]
    assert sorted(entry["reduced"]) == sorted(c["reduced"]) == reduced
    assert (c["hidden_size"], c["mamba_num_heads"], c["mamba_head_dim"],
            c["n_groups"], c["ssm_state_size"], c["conv_kernel"],
            c["num_attention_heads"], c["num_key_value_heads"],
            c["head_dim"], c["moe_intermediate_size"],
            c["moe_shared_expert_intermediate_size"],
            c["num_experts_per_tok"], c["routed_scaling_factor"]) == (
                2688, 64, 64, 8, 128, 4, 32, 2, 128, 1856, 3712, 6, 2.5)
    d = c["deployment"]
    assert d["experts_total"] == 128 == d["chips_per_layer"] \
        * c["n_routed_experts"] and d["experts_first"] == 0
    assert c["vocab_size"] * 4 == 131072 and c["num_hidden_layers"] == 26
    assert len(c["hybrid_override_pattern"]) == 52 and len(
        c["assumed"]) >= 8
    assert c["num_experts"] == c["n_routed_experts"]  # the harness's name
    assert c["mlp_layer_types"][:26].count("sparse") == 11
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
    assert entry["source"] == c["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in reduced:
            assert c[key] == value, key
    assert c["n_routed_experts"] < row["config"]["n_routed_experts"]


JOINED_METRICS = (
    "gateway.backlog_growth_rps", "engine.occupancy_pct",
    "engine.ttft_p95_ms.surge", "model.decode_tick_ms_p50.surge",
    "device.idle_pct.serve", "engine.tick_host_inside_ms_p50.surge",
    "engine.idle_host_pct.surge", "engine.prefill_pad_pct.surge",
    "moe.experts_ms_p50", "moe.route_ms_p50", "moe.experts_touched_pct",
    "moe.absent_share_pct", "attn.full_ms_p50",
    "kernel.expert_matmul_hbm_roofline", "setup.import_s",
    "setup.runtime_init_s", "setup.programs_s", "setup.cache_hit_pct",
    "setup.construct_s", "setup.warmup_s", "setup.unaccounted_s")


def test_the_cell_lists_what_the_issue_listed():
    """Membership only: a later PR appends cells and metrics, to this
    cell's lists too, without touching this file."""
    bench = SPEC.bench
    cell = SPEC.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "tooluse-surge", 1) and len(cell["why"]) <= 200
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in JOINED_METRICS:
        assert CELL in by_name[name]["workloads"], name
    for name in NEW_METRICS:
        m = by_name[name]
        assert CELL in m["workloads"]
        assert m["moves"] == "output_tokens_per_s"
        assert SPEC.metric_file(name)["layer"] == m["layer"]
        assert (m["unit"] == "%") == ("roofline" in name)
    assert {"output_tokens_per_s", "setup_s"} <= {
        m["name"] for m in SPEC.metrics_of(CELL, "end_to_end")}
    sv = SPEC.traffic("tooluse-surge")["serve"]
    assert (sv["prompt_len"]["min"], sv["prompt_len"]["max"],
            sv["output_len"]["min"], sv["output_len"]["max"]) == (
                256, 2048, 64, 1024)
    serve = SPEC.config(CONFIG)["serve"]
    assert sv["prompt_len"]["max"] == serve["prompt_bucket"]
    assert sv["prompt_len"]["max"] + sv["output_len"]["max"] \
        <= serve["max_len"]
    assert sv["burst"] == {"factor": 3.0, "on_s": 2.0, "period_s": 10.0}
    assert sv["warmup_s"] >= 12 and sv["pool"] == 32 \
        and sv["order"] == "rotate"


def rehearse(trace_flag: str, *extra):
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELL, "--seed",
         "4300000021", "--seconds", "2", "--trace", trace_flag,
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
    # half of the toy's experts are held, and the router does not know
    assert 35 < m["rehearsal_moe.absent_share_pct"]["value"] < 65
    assert m["rehearsal_engine.occupancy_pct"]["value"] > 0
    assert m["rehearsal_model.decode_tick_ms_p50.surge"]["value"] > 0
    # a CPU names no scope in its trace and has no roofline: the new
    # metrics are left out there, never 0 (read on a hand-made trace
    # below, and on the chip), but for the whole forward's time, which
    # counts every op whatever its scope
    assert not any(name in k for k in m for name in NEW_METRICS
                   if name != "model.prefill_ms_p50.mamba2")
    assert any(l.startswith("ring: routing, decode ticks:") for l in lines)
    out, _, _ = rehearse("0")
    assert out["correct"]
    assert {"rehearsal_output_tokens_per_s",
            "rehearsal_setup_s"} <= set(out["metrics"])


def test_the_state_control_reads_beside_the_int8_one(capsys):
    """``tools/mamba2_state_control.py`` at the rehearsal's sizes: the
    reference with its state held in bfloat16 between tokens and the
    harness's int8 control, each reading printed beside the limit; the
    int8 control is caught there."""
    tool = SPEC._module("tools", "mamba2_state_control")
    tool.main([CONFIG, "--rehearsal", "--rows", "4", "--len", "96"])
    lines = [l.split() for l in capsys.readouterr().out.splitlines()
             if l.startswith("check-reading ")]
    mean = {l[1]: float(l[3]) for l in lines if l[2] == "control_gap_mean:"}
    assert set(mean) == {"int8", "state"}
    assert 0 <= mean["state"] < mean["int8"]
    limit = SPEC.config(CONFIG)["rehearsal"]["check"]["serving"][
        "served_gap_mean"]
    assert mean["int8"] > 10 * limit
    assert all(l[5] == f"{limit})" for l in lines
               if l[2] == "control_gap_mean:")
    # the rounded state is another function than the float32 one
    ref = SPEC.family("moe-mamba2-gqa").reference
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 40, 4, 8))
    dt = jnp.full((1, 40, 4), 0.05)
    bc = jax.random.normal(jax.random.PRNGKey(1), (1, 40, 2, 8))
    a_log = jnp.zeros((4,))
    exact, rounded = (ref.recurrence(x, dt, bc, bc, a_log, r)
                      for r in (False, True))
    gap = float(jnp.abs(exact - rounded).max())
    assert 1e-4 < gap < 0.1 * float(jnp.abs(exact).max())


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
    """One 40 ms decode tick whose ops under ``attn.mamba2`` take 15 ms,
    11 of them under ``mamba2.step`` / ``mamba2.conv``, and four prompt
    forwards: three at the bucket's 2048 rows (prompts of 1400, 1600
    and 1500 tokens, 50, 60 and 55 ms of ops under a scope and 5 ms of
    a grouped product that keeps none, 20, 24 and 22 of them
    under ``attn.mamba2``, 10, 12 and 11 under ``mamba2.scan``) and one
    at 1024 rows, which the prefill metrics leave out; 100 lanes busy, 300 held
    experts touched, 150,000 positions live. The ring's clock runs 1 ms
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

    dec, pre = "jit(_decode)/attn.mamba2/", "jit(_prefill)/attn.mamba2/"
    events = [
        ev("XLA Modules", "jit__decode(7)", 1_000, 40 * ms),
        ev("XLA Ops", "fusion.1", 2_000, 4 * ms, dec + "dot_general"),
        ev("XLA Ops", "fusion.2", 5 * ms, 1 * ms,
           dec + "mamba2.conv/reduce_sum"),
        ev("XLA Ops", "fusion.4", 7 * ms, 10 * ms, dec + "mamba2.step/mul"),
        ev("XLA Ops", "fusion.5", 18 * ms, 3 * ms,
           "jit(_decode)/attn.full/dot_general")]
    ring = TraceBuffer(64)
    # (start, rows, prompt tokens, ms under mamba2.scan, attn.mamba2, all)
    for rid, (at, rows, plen, scan, mixer, whole) in enumerate((
            (50 * ms, 2048, 1400, 10, 20, 50),
            (130 * ms, 1024, 700, 5, 10, 25),
            (215 * ms, 2048, 1600, 12, 24, 60),
            (290 * ms, 2048, 1500, 11, 22, 55))):
        events += [
            ev("XLA Modules", f"jit__prefill({9 + rows})", at, 70 * ms),
            ev("XLA Ops", "fusion.6", at + ms, scan * ms,
               pre + "mamba2.scan/dot_general"),
            ev("XLA Ops", "fusion.7", at + 13 * ms, (mixer - scan) * ms,
               pre + "dot_general"),
            ev("XLA Ops", "fusion.8", at + 38 * ms, (whole - mixer) * ms,
               "jit(_prefill)/moe.experts/mul"),
            ev("XLA Ops", "ragged-dot-none.1", at + 64 * ms, 5 * ms),
            ev("threads", "bench.serve_step", at - 2 * ms, 75 * ms,
               plane="/host:CPU")]
        ring.emit(at - 3 * ms, Ev.ENG_TICK, 75 * ms, rid, 100, 1, 0, 0)
        ring.emit(at - 2 * ms, Ev.ENG_PREFILL, rid, rid, 0, 1 * ms,
                  71 * ms, rows)
        ring.emit(at - 2 * ms - 1000, Ev.ENG_ADMIT, rid, rid, 0, plen, 7,
                  73 * ms)
    monkeypatch.setattr(pt, "live_rings", lambda: [("engine", ring)])
    routes = np.array([[500, 0xA07, 3, 100, 1650, 4950, 300, 20]])
    monkeypatch.setattr(_route, "decode_routes",
                        lambda ctx, traced=False: routes)
    reqs = [{"admit": 0.05, "done": None, "prompt": [0] * 149_000,
             "stamps": [(0.1, 1_000)]}]
    ctx = context(events, requests=reqs,
                  ticks=[(0.09, 0.1, 100, 150_000)])
    got = {name: SPEC.reader(SPEC.metric_file(name)["reader"])(
        ctx, **SPEC.metric_file(name)["args"]) for name in NEW_METRICS}
    assert got["attn.mamba2_ms_p50"] == 15.0
    # the three forwards at 2048 rows; not the one at 1024
    assert got["attn.mamba2_prefill_ms_p50"] == 22.0
    # every op of the forward, the grouped products XLA:TPU leaves
    # without a scope among them
    assert got["model.prefill_ms_p50.mamba2"] == 60.0
    # 5,121,638,400 bytes of state at 819 GB/s = 6.254 ms of 11
    assert abs(got["kernel.mamba2_state_hbm_roofline"]
               - 100 * (STATE / 819e9) / 11e-3) < 1e-9
    # their mean prompt of 1500 tokens at 197 TFLOP/s, of 11 and 60 ms
    k = ctx.family.costs
    assert abs(got["kernel.mamba2_scan_mxu_roofline"]
               - 100 * (SCAN / 197e12) / 11e-3) < 1e-9
    assert abs(got["kernel.prefill_mxu_roofline.mamba2"] - 100 * (
        k.prefill_flops(ctx.config, 26, 1500, 128) / 197e12) / 60e-3) < 1e-9
    # the whole tick: state, touched experts (two matrices each), live
    # keys and values and every other weight once, of 40 ms
    need = STATE + 300 * 9_977_856 * 2 + 460_800_000 \
        + k.other_weight_bytes(ctx.config, 26, 128)
    assert abs(got["kernel.decode_tick_hbm_roofline.mamba2"]
               - 100 * (need / 819e9) / 40e-3) < 1e-9
