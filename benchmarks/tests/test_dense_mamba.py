"""The ``dense-mamba-mqa`` family's benchmark files: the cost table
against hand values at the published widths and against the parameter
tree's and the cache's own byte counts, the configuration against the
guide's catalog row, the new cell's rehearsal (``correct`` true, the
int8 control not), the new metrics on a hand-made trace, and the
compiled programs' own names for what the new metrics look for."""
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from benchmarks.harness import measure
from benchmarks.harness.spec import Spec

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SPEC = Spec()
CELL = "serve-ssm-docqa-surge"
CONFIG = "ai21-jamba2-3b"
NEW_METRICS = ("attn.mamba_ms_p50", "attn.mamba_prefill_ms_p50",
               "model.prefill_ms_p50.ssm",
               "kernel.mamba_state_hbm_roofline",
               "kernel.mamba_scan_hbm_roofline",
               "kernel.prefill_mxu_roofline.ssm",
               "kernel.decode_tick_hbm_roofline.ssm")


def nbytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def test_costs_against_hand_values():
    c = SPEC.config(CONFIG)
    fam = SPEC.family(c["family"])
    k = fam.costs
    assert k.layer_kinds(c, 28) == {"attention": 2, "mamba": 26}
    assert k.layer_kinds(c, 14) == {"attention": 1, "mamba": 13}
    assert k.mamba_sizes(c) == (5120, 16, 160, 4) and k.head_dim(c) == 128
    # K and V of one position in one attention layer: 2 x 1 x 128 x 2 B
    assert k.kv_bytes_per_position(c) == 512
    assert k.kv_read_bytes(c, 28, 100_000) == 2 * 512 * 100_000
    # a lane's state in one layer: 5120 x 16 float32, and the last 3
    # inputs of 5120 channels in bf16: 8.89 MiB a slot over 26 layers
    assert k.state_bytes_per_lane(c) == 327_680 + 30_720 == 358_400
    assert 26 * 358_400 == 9_318_400
    # 60 busy lanes, 26 layers, read and written
    assert k.mamba_state_bytes(c, 28, 60) == 60 * 26 * 2 * 358_400 \
        == 1_118_208_000
    # a fused scan over 1,500 real positions: x, dt, z, y at 5120 and B,
    # C at 16 in bf16 a position, the float32 state once, 26 layers
    assert k.mamba_scan_bytes(c, 28, 1500) == 26 * (
        1500 * 41_024 + 327_680) == 1_608_455_680
    # the mixer's matrices: 2560 x 10240, 5120 x 192, 160 x 5120,
    # 5120 x 2560
    assert k.mamba_matrix_params(c) == 26_214_400 + 983_040 + 819_200 \
        + 13_107_200 == 41_123_840
    # with the filter (4 x 5120), its bias, the layer's norm and the
    # three small ones (160 + 16 + 16)
    assert k.mamba_mixer_params(c) == 41_123_840 + 20_480 + 5_120 + 2_560 \
        + 192 == 41_152_192
    assert k.mamba_float32_params(c) == 81_920 + 2 * 5_120
    # attention: wq, wo 2560 x 2560; wk, wv 2560 x 128
    assert k.attention_matrix_params(c) == 13_107_200 + 655_360
    assert k.mlp_matrix_params(c) == 3 * 2560 * 8192 == 62_914_560
    # 3.03 B parameters: 26 x 104.1 M + 2 x 76.7 M + 167.8 M (tied)
    params = 26 * (41_152_192 + 92_160) + 2 * (13_762_560 + 2_560) \
        + 28 * (62_914_560 + 2_560) + 65_536 * 2_560 + 2_560
    assert params == 3_029_337_472
    assert k.weight_bytes(c, 28) == 2 * params + 2 * 26 * 92_160 \
        == 6_063_467_264
    # a prompt of 1,500 tokens: two operations a matrix weight, the
    # causal scores of two attention layers, the last position's logits
    weights = 26 * 41_123_840 + 2 * 13_762_560 + 28 * 62_914_560
    assert k.prefill_flops(c, 28, 1500) == 2 * weights * 1500 \
        + 2 * 2 * 2560 * 1500 ** 2 + 2 * 2560 * 65_536
    assert k.other_tick_bytes(c, 28, 64) == 6_063_467_264 + 2 * 64 * 512
    sizes = {"live_positions": 100_000.0, "busy_lanes": 60.0,
             "prompt_tokens": 1500.0}
    assert fam.COSTS["kv_read"](c, sizes) == {"bytes": 102_400_000}
    assert fam.COSTS["mamba_state"](c, sizes) == {"bytes": 1_118_208_000}
    assert fam.COSTS["mamba_scan"](c, sizes) == {"bytes": 1_608_455_680}
    assert fam.COSTS["prefill_mxu"](c, sizes) == {
        "flops": k.prefill_flops(c, 28, 1500)}
    assert fam.COSTS["decode_tick_ssm"](c, sizes) == {
        "bytes": 1_118_208_000 + 102_400_000 + 6_063_532_800}
    for name, missing in (("kv_read", "live_positions"),
                          ("mamba_state", "busy_lanes"),
                          ("mamba_scan", "prompt_tokens"),
                          ("prefill_mxu", "prompt_tokens"),
                          ("decode_tick_ssm", "busy_lanes"),
                          ("decode_tick_ssm", "live_positions")):
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
        c, fam.reference.seed_word(0), 28, jnp.bfloat16))
    assert "head" not in tree                       # tied
    assert nbytes(tree) == k.weight_bytes(c, 28) == 6_063_467_264
    mixer = tree["blocks"]["00"]["attn"]
    assert nbytes(mixer) == 2 * k.mamba_mixer_params(c) \
        + 4 * k.mamba_float32_params(c)
    assert mixer["a_log"].shape == (16, 5120)       # lane-dense
    slots = sv["slots"]
    cache = jax.eval_shape(lambda: slot_program(fam.program_config(
        c, 28, sv["max_len"])).init_cache(slots, sv["max_len"]))
    softmax = {"07", "21"}
    assert set(cache["k"]) == softmax == set(cache["v"])
    assert set(cache["ssm"]) == set(cache["conv"]) == {
        f"{l:02d}" for l in range(28)} - softmax
    assert cache["ssm"]["00"].shape == (slots, 16, 5120)
    assert nbytes(cache["ssm"]) + nbytes(cache["conv"]) \
        == k.mamba_state_bytes(c, 28, slots) // 2
    assert nbytes(cache["k"]) + nbytes(cache["v"]) \
        == k.kv_read_bytes(c, 28, slots * sv["max_len"])
    assert nbytes(cache) == 764_150_016             # 0.712 GiB


def test_the_configuration_is_the_catalog_row_but_for_the_positions():
    c = SPEC.config(CONFIG)
    entry = next(e for e in SPEC.bench["configs"] if e["name"] == CONFIG)
    reduced = ["max_position_embeddings"]
    assert entry["reduced"] == sorted(c["reduced"]) == reduced
    assert (c["hidden_size"], c["intermediate_size"],
            c["num_attention_heads"], c["num_key_value_heads"],
            c["mamba_expand"], c["mamba_d_state"], c["mamba_d_conv"],
            c["mamba_dt_rank"]) == (2560, 8192, 20, 1, 2, 16, 4, 160)
    assert (c["num_hidden_layers"], c["vocab_size"],
            c["tie_word_embeddings"]) == (28, 65536, True)
    sv = c["serve"]
    assert sv["num_hidden_layers"] == 28 and sv["slots"] == 64
    assert c["max_position_embeddings"] == sv["max_len"] == 2560
    assert c["deployment"]["chips_per_layer"] == 1 and len(c["assumed"]) >= 6
    r = c["rehearsal"]
    assert r["num_hidden_layers"] == r["serve"]["num_hidden_layers"] == 14
    assert "attn_layer_period" not in r and "attn_layer_offset" not in r
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "AI21-Jamba2-3B")
    assert entry["source"] == c["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in reduced:
            assert c[key] == value, key
    assert c["max_position_embeddings"] < row["config"][
        "max_position_embeddings"]


JOINED_METRICS = (
    "gateway.backlog_growth_rps", "engine.occupancy_pct",
    "engine.ttft_p95_ms.surge", "model.decode_tick_ms_p50.surge",
    "device.idle_pct.serve", "engine.tick_host_inside_ms_p50.surge",
    "engine.idle_host_pct.surge", "engine.prefill_pad_pct.surge",
    "attn.full_ms_p50")


def test_the_cell_lists_what_the_issue_listed():
    """Membership only: a later PR appends cells and metrics, to this
    cell's lists too, without touching this file."""
    bench = SPEC.bench
    cell = SPEC.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "docqa-surge", 1) and len(cell["why"]) <= 200
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in JOINED_METRICS:
        assert CELL in by_name[name]["workloads"], name
        assert by_name[name]["moves"] == "output_tokens_per_s", name
    for name in NEW_METRICS:
        m = by_name[name]
        assert m["workloads"][0] == CELL
        assert m["moves"] == "output_tokens_per_s"
        assert SPEC.metric_file(name)["layer"] == m["layer"]
        assert ("roofline" in name) == (m["unit"] == "%")
    # it routes nothing
    assert not any(CELL in m["workloads"] for m in bench["per_layer"]
                   if m["name"].startswith("moe."))
    assert {"output_tokens_per_s", "setup_s"} <= {
        m["name"] for m in SPEC.metrics_of(CELL, "end_to_end")}
    sv = SPEC.traffic("docqa-surge")["serve"]
    assert sv["prompt_len"] == {"dist": "lognormal", "median": 1024,
                                "sigma": 0.7, "min": 256, "max": 2048}
    assert sv["output_len"] == {"dist": "lognormal", "median": 96,
                                "sigma": 0.7, "min": 32, "max": 384}
    assert sv["burst"] == {"factor": 3.0, "on_s": 2.0, "period_s": 10.0}
    assert (sv["loop"], sv["pool"], sv["order"]) == ("open", 32, "rotate")
    serve = SPEC.config(CONFIG)["serve"]
    assert sv["prompt_len"]["max"] == serve["prompt_bucket"]
    assert sv["prompt_len"]["max"] + sv["output_len"]["max"] \
        <= serve["max_len"]


def rehearse(trace_flag: str, *extra):
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELL, "--seed",
         "3700000021", "--seconds", "2", "--trace", trace_flag,
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
    assert m["rehearsal_engine.occupancy_pct"]["value"] > 0
    assert m["rehearsal_model.decode_tick_ms_p50.surge"]["value"] > 0
    assert 0 < m["rehearsal_engine.prefill_pad_pct.surge"]["value"] < 100
    # a CPU names no scope in its trace and has no roofline: the new
    # metrics are left out there, never 0 (read on a hand-made trace
    # below, and on the chip)
    assert not any(name in k for k in m for name in NEW_METRICS)
    assert not any(k.startswith("rehearsal_moe.") for k in m)
    assert any(l.startswith("ring: prompt forwards by rows:")
               for l in lines)
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
    """One 10 ms decode tick whose ops under ``attn.mamba`` take 4 ms,
    2 of them under ``mamba.state`` / ``mamba.conv``, and four prompt
    forwards: three at the bucket's 2048 rows (prompts of 1400, 1600
    and 1500 tokens; 140, 160 and 150 ms of ops, 60, 80 and 70 of them
    under ``attn.mamba``, 30, 40 and 35 under ``mamba.scan``; a
    ``while`` that holds the scan is a container and not counted twice)
    and one at 1024 rows, which the prefill metrics leave out; 60 lanes
    busy, 100,000 positions live. The ring's clock runs 1 ms behind the
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

    dec, pre = "jit(_decode)/jit(main)/", "jit(_prefill)/jit(main)/"
    events = [
        ev("XLA Modules", "jit__decode(7)", 1_000, 10 * ms),
        ev("XLA Ops", "fusion.1", 2_000, 2 * ms,
           dec + "attn.mamba/dot_general"),
        ev("XLA Ops", "fusion.2", 3 * ms, ms // 2,
           dec + "attn.mamba/mamba.conv/reduce_sum"),
        ev("XLA Ops", "fusion.3", 4 * ms, 3 * ms // 2,
           dec + "attn.mamba/mamba.state/mul"),
        ev("XLA Ops", "fusion.4", 6 * ms, 1 * ms,
           dec + "attn.full/dot_general"),
        ev("XLA Ops", "fusion.5", 7 * ms, 2 * ms,
           dec + "mlp.dense/dot_general")]
    ring = TraceBuffer(64)
    # (start on the trace's clock, rows, prompt tokens, ms under
    # attn.mamba, of which under mamba.scan, ms elsewhere)
    for rid, (at, rows, plen, mix, scan, rest) in enumerate((
            (40 * ms, 2048, 1400, 60, 30, 80),
            (300 * ms, 1024, 700, 30, 15, 40),
            (500 * ms, 2048, 1600, 80, 40, 80),
            (750 * ms, 2048, 1500, 70, 35, 80))):
        events += [
            ev("XLA Modules", f"jit__prefill({9 + rows})", at, 200 * ms),
            ev("XLA Ops", "fusion.6", at + ms, (mix - scan) * ms,
               pre + "attn.mamba/dot_general"),
            ev("XLA Ops", "while.7", at + 90 * ms, scan * ms,
               pre + "attn.mamba/mamba.scan/while"),
            ev("XLA Ops", "fusion.8", at + 90 * ms, scan * ms,
               pre + "attn.mamba/mamba.scan/while/body/closed_call/mul"),
            ev("XLA Ops", "fusion.9", at + 135 * ms, rest * ms,
               pre + "mlp.dense/dot_general"),
            ev("threads", "bench.serve_step", at - 2 * ms, 210 * ms,
               plane="/host:CPU")]
        ring.emit(at - 3 * ms, Ev.ENG_TICK, 210 * ms, rid, 60, 1, 0, 0)
        ring.emit(at - 2 * ms, Ev.ENG_PREFILL, rid, rid, 0, 1 * ms,
                  205 * ms, rows)
        ring.emit(at - 2 * ms - 1000, Ev.ENG_ADMIT, rid, rid, 0, plen, 7,
                  208 * ms)
    monkeypatch.setattr(pt, "live_rings", lambda: [("engine", ring)])
    reqs = [{"admit": 0.005, "done": None, "prompt": [0] * 99_000,
             "stamps": [(0.008, 1_000)]}]
    ctx = context(events, requests=reqs,
                  ticks=[(0.009, 0.01, 60, 100_000)])
    c = ctx.config
    k = ctx.family.costs
    got = {name: SPEC.reader(SPEC.metric_file(name)["reader"])(
        ctx, **SPEC.metric_file(name)["args"]) for name in NEW_METRICS}
    assert got["attn.mamba_ms_p50"] == 4.0
    # the three forwards at 2048 rows; not the one at 1024
    assert got["attn.mamba_prefill_ms_p50"] == 70.0
    assert got["model.prefill_ms_p50.ssm"] == 150.0
    # 1,118,208,000 bytes of state at 819 GB/s = 1.365 ms of 2
    assert abs(got["kernel.mamba_state_hbm_roofline"]
               - 100 * (1_118_208_000 / 819e9) / 2e-3) < 1e-9
    # their mean prompt of 1,500 tokens: 1,608,455,680 bytes = 1.96 ms
    # of the scan's 35
    assert abs(got["kernel.mamba_scan_hbm_roofline"]
               - 100 * (1_608_455_680 / 819e9) / 35e-3) < 1e-9
    # and its matrix products at 197 TFLOP/s, of the forward's 150 ms
    assert abs(got["kernel.prefill_mxu_roofline.ssm"] - 100 * (
        k.prefill_flops(c, 28, 1500) / 197e12) / 150e-3) < 1e-9
    assert 25 < got["kernel.prefill_mxu_roofline.ssm"] < 35
    # the whole tick: state twice, live keys and values and every
    # weight once, of 10 ms
    need = 1_118_208_000 + 102_400_000 + 6_063_532_800
    assert abs(got["kernel.decode_tick_hbm_roofline.ssm"]
               - 100 * (need / 819e9) / 10e-3) < 1e-9
    assert all(0 < got[n] < 100 for n in NEW_METRICS if "roofline" in n)
    # the accepted reader the cell joins reads the same trace
    full = SPEC.metric_file("attn.full_ms_p50")
    assert SPEC.reader(full["reader"])(ctx, **full["args"]) == 1.0
    # nothing to read: no trace, a CPU, a program that names no scope
    read = SPEC.reader("kda_roofline_pct")
    args = ("jit__decode", "mamba_state", ["mamba.state"])
    assert read(context(None), *args) is None
    assert read(context(events, rehearsal=True), *args) is None
    bare = [dict(e, scope="fused") for e in events]
    assert read(context(bare), *args) is None
    # no busy lanes known (no tick in the traced part)
    assert read(context(events), *args) is None
    # the parent's program (no state-space scope, and this family's
    # module ends before it is asked): a reader finds no op
    ms_read = SPEC.reader("bucket_prefill_ms")
    assert ms_read(context(bare), "jit__prefill", ["attn.mamba"]) is None
    monkeypatch.delattr(pt, "live_rings")
    assert ms_read(context(events), "jit__prefill", ["attn.mamba"]) is None


def test_the_parent_program_ends_the_cell_at_once(monkeypatch):
    """A program whose plan has no state-space kind (the parent of the
    PR that added it) leaves the cell with a message and a non-zero exit
    code before any weight is made."""
    from pbs_tpu.models import plan

    c = SPEC.config(CONFIG)
    fam = SPEC.family(c["family"])
    monkeypatch.delattr(plan, "MambaKind")
    with pytest.raises(SystemExit, match="no state-space kind"):
        fam.program_config(c, 28, 2560)


def test_what_holds_the_state_is_found_by_the_state_metrics():
    """Compiled for a described v5e at the cell's sizes (no chip, as
    ``tools/size_cells.py``; the trace names a device op after its HLO
    instruction and gives it that instruction's ``op_name``): every
    instruction of the decode program that reads or writes a layer's
    recurrent state is found by ``kernel.mamba_state_hbm_roofline``'s
    own ``scopes``, every scope a new metric names is carried by some
    instruction of its program, and the state is updated in place (what
    the decode program needs beyond its arguments is a fraction of the
    cache)."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu to compile with
        pytest.skip(f"no described v5e to compile for: {e}")
    dev = SingleDeviceSharding(topo.devices[0])
    c = SPEC.config(CONFIG)
    decode, prefill = SPEC.family(c["family"]).sizing(
        c, lambda tree: jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=dev), tree))
    compiled = {"jit__decode": decode["fn"].lower(*decode["args"]).compile()}
    compiled["jit__prefill"] = prefill["fn"].lower(*prefill["args"]).compile()
    hlo = {k: v.as_text() for k, v in compiled.items()}
    for name in NEW_METRICS:
        args = SPEC.metric_file(name)["args"]
        for scope in args["scopes"] or []:
            # the harness's own programs are jit(decode) / jit(prefill)
            scope = scope.replace("jit(_", "jit(")
            assert re.search(rf'op_name="[^"]*{re.escape(scope)}/',
                             hlo[args["match"]]), (name, scope)
    entry = hlo["jit__decode"][hlo["jit__decode"].index("ENTRY "):]
    touching = [line for line in entry.splitlines()
                if re.search(r"\(.*%cache__ssm____\d+__", line)
                and " parameter(" not in line]
    assert len(touching) >= 26
    scopes = SPEC.metric_file("kernel.mamba_state_hbm_roofline")["args"][
        "scopes"]
    for line in touching:
        scope = re.search(r'op_name="([^"]*)"', line)
        assert scope and any(s in scope.group(1) for s in scopes), line[:200]
    m = compiled["jit__decode"].memory_analysis()
    assert m.alias_size_in_bytes >= 764_150_016
    assert m.temp_size_in_bytes + m.output_size_in_bytes \
        - m.alias_size_in_bytes < 764_150_016 // 4
