"""The ``moe-kda-gqa`` family's benchmark files: the cost table against
hand values at the published widths and against the parameter tree's
and the cache's own byte counts, the configuration against the guide's
catalog row, the new cell's rehearsal (``correct`` true, the int8
control not), the new reader on a hand-made trace, and the compiled
programs' own names for what the new metrics look for."""
import json
import os
import re
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
CELL = "serve-kda-longform-surge"
CONFIG = "solar-open2-250b"
NEW_METRICS = ("attn.kda_ms_p50", "attn.kda_prefill_ms_p50",
               "kernel.kda_state_hbm_roofline",
               "kernel.kda_prefill_mxu_roofline",
               "kernel.decode_tick_hbm_roofline.kda")


def nbytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def test_costs_against_hand_values():
    c = SPEC.config(CONFIG)
    fam = SPEC.family(c["family"])
    k = fam.costs
    assert k.layer_kinds(c, 4) == {"softmax": 1, "kda": 3}
    assert k.layer_kinds(c, 48) == {"softmax": 12, "kda": 36}
    # one routed expert: gate, up, down of 4096 x 1280
    assert k.expert_params(c) == 3 * 4096 * 1280 == 15_728_640
    # K and V of one position in the softmax layer: 2 x 8 x 128 x 2 bytes
    assert k.kv_bytes_per_position(c) == 4096
    assert k.kv_read_bytes(c, 4, 150_000) == 4096 * 150_000
    # a lane's state in one layer: 64 heads x 128 x 128 float32 = 4 MiB,
    # and the last 3 inputs of 3 x 8192 channels in bf16
    assert k.state_bytes_per_lane(c) == 4_194_304 + 3 * 24_576 * 2 \
        == 4_341_760
    # 200 busy lanes, 3 layers, read and written
    assert k.kda_state_bytes(c, 4, 200) == 200 * 3 * 2 * 4_341_760 \
        == 5_210_112_000
    # the mixer's matrices: q, k, v, o 4096 x 8192; two low-rank pairs
    # 4096 x 128 + 128 x 8192; beta 4096 x 64
    assert k.kda_matrix_params(c) == 4 * 33_554_432 + 2 * 1_572_864 \
        + 262_144 == 137_625_600
    # with three filters of 4 x 8192, the head norm and the layer norm
    assert k.kda_mixer_params(c) == 137_625_600 + 98_304 + 128 + 4096
    assert k.kda_float32_params(c) == 64 + 8192
    # softmax: wq, wo, wg 4096 x 8192; wk, wv 4096 x 1024; one norm
    assert k.softmax_mixer_params(c) == 3 * 33_554_432 + 2 * 4_194_304 \
        + 4096 == 109_056_000
    # a prompt of 300 tokens through 3 layers: two operations a matrix
    # weight and 6 x 128 x 128 x 64 for the recurrence, a token a layer
    assert k.kda_prefill_flops(c, 4, 300) == 300 * 3 * (
        2 * 137_625_600 + 6_291_456) == 253_388_390_400
    sizes = {"experts_touched": 150.0, "live_positions": 150_000.0,
             "busy_lanes": 200.0, "prompt_tokens": 300.0}
    assert fam.COSTS["expert_matmul"](c, sizes) == {
        "bytes": 150 * 15_728_640 * 2}
    assert fam.COSTS["kv_read"](c, sizes) == {"bytes": 614_400_000}
    assert fam.COSTS["kda_state"](c, sizes) == {"bytes": 5_210_112_000}
    assert fam.COSTS["kda_prefill"](c, sizes) == {"flops": 253_388_390_400}
    assert fam.COSTS["decode_tick_kda"](c, sizes) == {
        "bytes": 4_718_592_000 + 614_400_000 + 5_210_112_000
        + k.other_weight_bytes(c, 4, 256)}
    for name, missing in (("expert_matmul", "experts_touched"),
                          ("kv_read", "live_positions"),
                          ("kda_state", "busy_lanes"),
                          ("kda_prefill", "prompt_tokens"),
                          ("decode_tick_kda", "busy_lanes")):
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
        c, fam.reference.seed_word(0), 4, jnp.bfloat16))
    experts = sum(nbytes(b["mlp"][n]) for b in tree["blocks"].values()
                  for n in ("we1", "we3", "we2"))
    assert experts == 4 * k.expert_bytes(c, c["n_routed_experts"])
    # everything else once, but one embedding row a slot and not the
    # table, plus the tick's new position of keys and values
    rest = nbytes(tree) - experts - nbytes(tree["embed"])
    slots = sv["slots"]
    assert k.other_weight_bytes(c, 4, slots) == rest \
        + slots * 4096 * 2 + slots * k.kv_bytes_per_position(c)
    assert nbytes(tree) == 6_616_758_784  # 6.162 GiB
    cache = jax.eval_shape(lambda: slot_program(fam.program_config(
        c, 4, sv["max_len"])).init_cache(slots, sv["max_len"]))
    assert set(cache["state"]) == {"01", "02", "03"} == set(cache["conv"])
    assert set(cache["k"]) == {"00"} == set(cache["v"])
    assert nbytes(cache["state"]) + nbytes(cache["conv"]) \
        == k.kda_state_bytes(c, 4, slots) // 2
    assert nbytes(cache["k"]) + nbytes(cache["v"]) \
        == k.kv_read_bytes(c, 4, slots * sv["max_len"])
    assert nbytes(cache) == 5_481_956_352  # 5.105 GiB


def test_the_configuration_is_the_catalog_row_but_for_the_cut():
    c = SPEC.config(CONFIG)
    entry = next(e for e in SPEC.bench["configs"] if e["name"] == CONFIG)
    reduced = ["max_position_embeddings", "n_routed_experts",
               "num_hidden_layers", "vocab_size"]
    assert sorted(entry["reduced"]) == sorted(c["reduced"]) == reduced
    assert (c["hidden_size"], c["head_dim"], c["num_attention_heads"],
            c["num_key_value_heads"], c["moe_intermediate_size"],
            c["num_experts_per_tok"], c["n_shared_experts"]) == (
                4096, 128, 64, 8, 1280, 8, 1)
    assert c["linear_attn_config"] == {
        "short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64,
        "num_kv_heads": None}
    d = c["deployment"]
    assert d["experts_total"] == 320 == d["chips_per_layer"] \
        * c["n_routed_experts"] and d["experts_first"] == 0
    assert c["vocab_size"] * 8 == 196608 and c["num_hidden_layers"] == 4
    assert c["gqa_layers"][:2] == [0, 4] and len(c["assumed"]) >= 8
    assert c["num_experts"] == c["n_routed_experts"]  # the harness's name
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Solar-Open2-250B")
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
    "kernel.expert_matmul_hbm_roofline", "kernel.kv_read_hbm_roofline")


def test_the_cell_lists_what_the_issue_listed():
    """Membership only: a later PR appends cells and metrics, to this
    cell's lists too, without touching this file."""
    bench = SPEC.bench
    cell = SPEC.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "longform-surge", 1) and len(cell["why"]) <= 200
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in JOINED_METRICS:
        assert CELL in by_name[name]["workloads"], name
        assert by_name[name]["moves"] == "output_tokens_per_s", name
    for name in NEW_METRICS:
        m = by_name[name]
        assert CELL in m["workloads"]
        assert m["moves"] == "output_tokens_per_s"
        assert SPEC.metric_file(name)["layer"] == m["layer"]
    assert {"output_tokens_per_s", "setup_s"} <= {
        m["name"] for m in SPEC.metrics_of(CELL, "end_to_end")}
    sv = SPEC.traffic("longform-surge")["serve"]
    assert (sv["prompt_len"]["max"], sv["output_len"]["max"]) == (512, 1536)
    assert sv["prompt_len"]["max"] == SPEC.config(CONFIG)["serve"][
        "prompt_bucket"]
    assert sv["prompt_len"]["max"] + sv["output_len"]["max"] \
        <= SPEC.config(CONFIG)["serve"]["max_len"]


def rehearse(trace_flag: str, *extra):
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELL, "--seed",
         "3300000021", "--seconds", "2", "--trace", trace_flag,
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
    # below, and on the chip)
    assert not any(name in k for k in m for name in NEW_METRICS)
    assert any(l.startswith("ring: routing, decode ticks:") for l in lines)
    out, _, _ = rehearse("0")
    assert out["correct"]
    assert {"rehearsal_output_tokens_per_s",
            "rehearsal_setup_s"} <= set(out["metrics"])


def test_the_state_control_reads_beside_the_int8_one(capsys):
    """``tools/kda_state_control.py`` at the rehearsal's sizes: the
    reference with the state's products on bfloat16 operands, and with
    the state held in bfloat16 as well, picks other tokens than the
    float32 reference does (a gap above zero), the harness's int8
    control far more; each reading is printed beside the limit."""
    tool = SPEC._module("tools", "kda_state_control")
    tool.main([CONFIG, "--rehearsal", "--rows", "2", "--len", "96"])
    lines = [l.split() for l in capsys.readouterr().out.splitlines()
             if l.startswith("check-reading ")]
    mean = {l[1]: float(l[3]) for l in lines if l[2] == "control_gap_mean:"}
    assert set(mean) == {"int8", "dots", "state"}
    assert 0 < mean["dots"] and 0 < mean["state"]
    assert mean["int8"] > 10 * max(mean["dots"], mean["state"])
    limit = SPEC.config(CONFIG)["rehearsal"]["check"]["serving"][
        "served_gap_mean"]
    assert all(l[5] == f"{limit})" for l in lines
               if l[2] == "control_gap_mean:")


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
    """One 30 ms decode tick whose ops under ``attn.kda`` take 14 ms,
    10 of them under ``kda.state`` / ``kda.conv`` (a ``while`` that
    holds one is a container and not counted twice), and four prompt
    forwards: three at the bucket's 512 rows (prompts of 280, 320 and
    300 tokens, 6, 8 and 7 ms under ``attn.kda``) and one at 256 rows
    (3 ms), which the prefill metrics leave out; 200 lanes busy, 150 held
    experts touched, 150,000 positions live. The ring's clock runs
    1 ms behind the trace's."""
    from pbs_tpu.obs import trace as pt
    from pbs_tpu.obs.trace import Ev, TraceBuffer

    dev, ms = "/device:TPU:0", 1_000_000

    def ev(line, name, start, dur, scope=None, plane=dev):
        e = {"plane": plane, "line": line, "name": name, "start": start,
             "dur": dur}
        if scope:
            e["scope"] = scope
        return e

    dec, pre = "jit(_decode)/attn.kda/", "jit(_prefill)/attn.kda/"
    events = [
        ev("XLA Modules", "jit__decode(7)", 1_000, 30 * ms),
        ev("XLA Ops", "fusion.1", 2_000, 4 * ms, dec + "dot_general"),
        ev("XLA Ops", "fusion.2", 5 * ms, 1 * ms,
           dec + "kda.conv/reduce_sum"),
        ev("XLA Ops", "while.3", 7 * ms, 9 * ms, dec + "kda.state/while"),
        ev("XLA Ops", "fusion.4", 7 * ms, 9 * ms, dec + "kda.state/mul"),
        ev("XLA Ops", "fusion.5", 17 * ms, 3 * ms,
           "jit(_decode)/attn.full/dot_general")]
    ring = TraceBuffer(64)
    # (start on the trace's clock, rows, prompt tokens, ms under attn.kda)
    for rid, (at, rows, plen, kda_ms) in enumerate((
            (40 * ms, 512, 280, 6), (85 * ms, 256, 100, 3),
            (140 * ms, 512, 320, 8), (200 * ms, 512, 300, 7))):
        events += [
            ev("XLA Modules", f"jit__prefill({9 + rows})", at, 25 * ms),
            ev("XLA Ops", "fusion.6", at + ms, kda_ms * ms,
               pre + "kda.state/while/body/dot_general"),
            ev("XLA Ops", "fusion.7", at + 10 * ms, 2 * ms,
               "jit(_prefill)/attn.full/dot_general"),
            ev("threads", "bench.serve_step", at - 2 * ms, 30 * ms,
               plane="/host:CPU")]
        ring.emit(at - 3 * ms, Ev.ENG_TICK, 30 * ms, rid, 200, 1, 0, 0)
        ring.emit(at - 2 * ms, Ev.ENG_PREFILL, rid, rid, 0, 1 * ms,
                  26 * ms, rows)
        ring.emit(at - 2 * ms - 1000, Ev.ENG_ADMIT, rid, rid, 0, plen, 7,
                  28 * ms)
    monkeypatch.setattr(pt, "live_rings", lambda: [("engine", ring)])
    routes = np.array([[500, 0xA07, 3, 200, 2400, 10_400, 150, 30]])
    monkeypatch.setattr(_route, "decode_routes",
                        lambda ctx, traced=False: routes)
    reqs = [{"admit": 0.05, "done": None, "prompt": [0] * 149_000,
             "stamps": [(0.1, 1_000)]}]
    ctx = context(events, requests=reqs,
                  ticks=[(0.09, 0.1, 200, 150_000)])
    got = {name: SPEC.reader(SPEC.metric_file(name)["reader"])(
        ctx, **SPEC.metric_file(name)["args"]) for name in NEW_METRICS}
    assert got["attn.kda_ms_p50"] == 14.0
    # the three forwards at 512 rows; not the 3 ms at 256
    assert got["attn.kda_prefill_ms_p50"] == 7.0
    # 5,210,112,000 bytes of state at 819 GB/s = 6.362 ms of 10
    assert abs(got["kernel.kda_state_hbm_roofline"]
               - 100 * (5_210_112_000 / 819e9) / 10e-3) < 1e-9
    # their mean prompt of 300 tokens: 253,388,390,400 operations at
    # 197 TFLOP/s = 1.286 ms of 7
    assert abs(got["kernel.kda_prefill_mxu_roofline"]
               - 100 * (253_388_390_400 / 197e12) / 7e-3) < 1e-9
    # the whole tick: state, touched experts, live keys and values and
    # every other weight once, of 30 ms
    need = 5_210_112_000 + 4_718_592_000 + 614_400_000 + 1_385_413_120
    assert abs(got["kernel.decode_tick_hbm_roofline.kda"]
               - 100 * (need / 819e9) / 30e-3) < 1e-9
    assert all(0 < got[n] < 100 for n in NEW_METRICS if "roofline" in n)
    # the accepted readers the cell joins read the same trace
    kv = SPEC.metric_file("kernel.kv_read_hbm_roofline")
    assert abs(SPEC.reader(kv["reader"])(ctx, **kv["args"])
               - 100 * (614_400_000 / 819e9) / 3e-3) < 1e-9
    # nothing to read: no trace, a CPU, a program that names no scope
    read = SPEC.reader("kda_roofline_pct")
    assert read(context(None), "jit__decode", "kda_state",
                ["kda.state"]) is None
    assert read(context(events, rehearsal=True), "jit__decode",
                "kda_state", ["kda.state"]) is None
    bare = [dict(e, scope="jit(_decode)/fused") for e in events]
    assert read(context(bare), "jit__decode", "kda_state",
                ["kda.state"]) is None
    # no busy lanes known (no tick in the traced part): nothing to read
    assert read(context(events), "jit__decode", "kda_state",
                ["kda.state", "kda.conv"]) is None
    # the prefill metrics: a program that writes no rows (the hit flag
    # of the program before the ladder), and one without rings
    ms_read = SPEC.reader("bucket_prefill_ms")
    flags = TraceBuffer(64)
    for r in ring.peek(ring.capacity).tolist():
        flags.emit(r[0], r[1], *r[2:7], 0 if r[1] == Ev.ENG_PREFILL else r[7])
    monkeypatch.setattr(pt, "live_rings", lambda: [("engine", flags)])
    assert ms_read(context(events), "jit__prefill", ["attn.kda"]) is None
    assert read(context(events), "jit__prefill", "kda_prefill",
                ["attn.kda"], "bucket") is None
    monkeypatch.delattr(pt, "live_rings")
    assert ms_read(context(events), "jit__prefill", ["attn.kda"]) is None


def test_the_parent_program_ends_the_cell_at_once(monkeypatch):
    """A program whose plan has no delta-rule kind (the parent of the PR
    that added it) leaves the cell with a message and a non-zero exit
    code before any weight is made."""
    from pbs_tpu.models import plan

    c = SPEC.config(CONFIG)
    fam = SPEC.family(c["family"])
    monkeypatch.delattr(plan, "KdaKind")
    with pytest.raises(SystemExit, match="no delta-rule attention kind"):
        fam.program_config(c, 4, 2048)


def test_what_holds_the_state_is_found_by_the_state_metrics():
    """Compiled for a described v5e at the cell's sizes (no chip, as
    ``tools/size_cells.py``; the trace names a device op after its HLO
    instruction and gives it that instruction's ``op_name``): every
    instruction of the decode program that reads or writes a layer's
    recurrent state is found by ``kernel.kda_state_hbm_roofline``'s own
    ``scopes``, every scope a new metric names is carried by some fusion
    of its program, and the state is updated in place (what the decode
    program needs beyond its arguments is a fraction of one layer's
    state)."""
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
            assert re.search(rf'op_name="[^"]*/{re.escape(scope)}/',
                             hlo[args["match"]]), (name, scope)
    entry = hlo["jit__decode"][hlo["jit__decode"].index("ENTRY "):]
    touching = [line for line in entry.splitlines()
                if re.search(r"\(.*%cache__state____\d+__", line)
                and " parameter(" not in line]
    assert len(touching) >= 3
    scopes = SPEC.metric_file("kernel.kda_state_hbm_roofline")["args"][
        "scopes"]
    for line in touching:
        scope = re.search(r'op_name="([^"]*)"', line)
        assert scope and any(s in scope.group(1) for s in scopes), line[:200]
    m = compiled["jit__decode"].memory_analysis()
    state_layer = 256 * 64 * 128 * 128 * 4
    assert m.temp_size_in_bytes + m.output_size_in_bytes \
        - m.alias_size_in_bytes < state_layer // 2
