"""The ``moe-mixed-gqa`` family's benchmark files: cost functions
against hand values at the published widths, the new cell's rehearsal
(``correct`` true, the int8 control not), the new readers on a
recorded trace and on a hand-made one, and the compiled decode
program's own names for what the expert metrics look for."""
import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

from benchmarks.harness import measure, trace
from benchmarks.harness.spec import Spec
from benchmarks.readers import _route

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DATA = os.path.join(ROOT, "benchmarks", "data")
SPEC = Spec()
CELL = "serve-moe-codegen-surge"


def test_costs_against_hand_values():
    c = SPEC.config("laguna-s-2.1")
    fam = SPEC.family(c["family"])
    k = fam.costs
    assert k.layer_kinds(c, 5) == {"full": 2, "window": 3, "dense": 1,
                                   "sparse": 4}
    # one routed expert: gate, up, down of 3072 x 1024
    assert k.expert_params(c) == 3 * 3072 * 1024 == 9_437_184
    # 468 touched experts in a tick (4 layers x 117), bf16
    assert k.expert_bytes(c, 468) == 468 * 9_437_184 * 2 == 8_833_204_224
    # K and V of one position in one layer: 2 x 8 heads x 128 x 2 bytes
    assert k.kv_bytes_per_position(c) == 4096
    # 60,000 live positions, 30,000 of them inside the windows:
    # 2 full layers read the first, 3 window layers the second
    assert k.kv_read_bytes(c, 5, 60_000, 30_000) == 4096 * 210_000
    # a full layer: wq, wo 3072 x 6144; wk, wv 3072 x 1024; gate
    # 3072 x 48; two norms. A sliding layer: 72 heads.
    assert k.attention_params(c, 0) == 44_193_792
    assert k.attention_params(c, 1) == 63_141_888
    # everything else, once: 2 full + 3 sliding layers' attention
    # 277,813,248; the dense MLP 3 x 3072 x 12288 = 113,246,208; four
    # routers (3072 x 256) and shared experts (3 x 3072 x 1024)
    # 40,894,464; final norm 3,072; head 3072 x 50176 = 154,140,672;
    # 64 embedding rows 196,608: 586,294,272 parameters in bf16, and
    # one new position of K and V a slot a layer (5 x 64 x 4096).
    assert k.other_weight_bytes(c, 5, 64) == 586_294_272 * 2 + 1_310_720
    sizes = {"experts_touched": 468.0, "live_positions": 60_000.0,
             "live_window_positions": 30_000.0}
    assert fam.COSTS["expert_matmul"](c, sizes) == {"bytes": 8_833_204_224}
    assert fam.COSTS["kv_read"](c, sizes) == {"bytes": 860_160_000}
    assert fam.COSTS["decode_tick_moe"](c, sizes) == {
        "bytes": 8_833_204_224 + 860_160_000 + 1_173_899_264}
    assert fam.COSTS["expert_matmul"](c, {}) is None
    assert fam.COSTS["decode_tick_moe"](c, {"experts_touched": 1.0}) is None


def test_the_configuration_keeps_every_published_width():
    c = SPEC.config("laguna-s-2.1")
    entry = next(e for e in SPEC.bench["configs"]
                 if e["name"] == "laguna-s-2.1")
    assert sorted(entry["reduced"]) == sorted(c["reduced"]) == [
        "max_position_embeddings", "num_experts", "num_hidden_layers",
        "vocab_size"]
    assert (c["hidden_size"], c["head_dim"], c["intermediate_size"],
            c["moe_intermediate_size"], c["num_experts_per_tok"],
            c["shared_expert_intermediate_size"], c["sliding_window"],
            c["num_key_value_heads"]) == (3072, 128, 12288, 1024, 10,
                                          1024, 512, 8)
    assert c["deployment"]["experts_total"] == 256 == 2 * c["num_experts"]
    assert c["num_attention_heads_per_layer"][:5] == [48, 72, 72, 72, 48]
    assert len(c["layer_types"]) == 48 and len(c["assumed"]) >= 4


def rehearse(trace_flag: str, *extra):
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELL, "--seed",
         "2800000021", "--seconds", "2", "--trace", trace_flag,
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
    limits = SPEC.config("laguna-s-2.1")["rehearsal"]["check"]["serving"]
    out, control, lines = rehearse("1", "--control", "1")
    assert out["correct"] and out["failed"] == 0
    assert set(out["check"]) == set(limits)
    assert control["control_gap_max"] > 3 * limits["served_gap_max"]
    assert control["control_gap_mean"] > 3 * limits["served_gap_mean"]
    m = out["metrics"]
    assert 0 < m["rehearsal_moe.experts_touched_pct"]["value"] <= 100
    # half of the experts are held, and the router does not know which
    assert 35 < m["rehearsal_moe.absent_share_pct"]["value"] < 65
    assert m["rehearsal_engine.occupancy_pct"]["value"] > 0
    # a CPU names no scope in its trace and has no roofline: left out
    assert not any("roofline" in k or "_ms_p50" in k and "moe." in k
                   for k in m)
    assert any(l.startswith("ring: routing, decode ticks:") for l in lines)
    out, _, _ = rehearse("0")
    assert out["correct"]
    assert set(out["metrics"]) == {"rehearsal_output_tokens_per_s",
                                   "rehearsal_setup_s"}


def context(events, **over):
    c = SPEC.config("laguna-s-2.1")
    kw = dict(family=SPEC.family(c["family"]), config=c, traffic={},
              device_kind="TPU v5 lite", t0=0.0, t1=1.0, requests=[],
              ticks=[], train_steps=0, train_tokens_per_step=0,
              events=events, trace_span=(0.0, 1.0), ledger_trace={},
              backlog=None)
    kw.update(over)
    return measure.Context(**kw)


def test_scope_times_on_the_recorded_trace(tmp_path):
    """Three executions of a four-layer ``jit(_decode)`` whose scanned
    matmul sits under ``attn``: the reader's time an execution is the
    sum of that execution's four ops, and a scope nothing carries reads
    nothing."""
    log = tmp_path / "plugins" / "profile" / "once"
    log.mkdir(parents=True)
    shutil.copy(os.path.join(DATA, "named-scopes.xplane.pb"), log)
    events = trace.load_xplane(str(tmp_path))
    ctx = context(events)
    progs = [p for p in ctx.programs if "jit__decode" in p["name"]]
    assert len(progs) == 3
    want = []
    for p in progs:
        inside = [e for e in events if e["line"] == "XLA Ops"
                  and "/attn/dot_general" in e.get("scope", "")
                  and p["start"] <= e["start"] < p["start"] + p["dur"]]
        assert len(inside) == 4
        want.append(sum(e["dur"] for e in inside))
    assert _route.scope_times(ctx, "jit__decode", ["/attn/dot_general"]) == want
    read = SPEC.reader("scope_ms_per_program")
    assert read(ctx, "jit__decode", ["/attn/dot_general"]) == sorted(want)[1] / 1e6
    assert read(ctx, "jit__decode", ["moe.experts"]) is None
    assert read(context(None), "jit__decode", ["/attn/"]) is None


def test_what_reads_the_held_experts_is_found_by_the_expert_metrics():
    """``moe.experts_ms_p50`` and ``kernel.expert_matmul_hbm_roofline``
    find 99% of their time by an op's name, because XLA:TPU turns
    ``ragged_dot`` into ``ragged-dot-*`` custom calls that keep no
    ``moe.experts`` scope. Compiled here for a described v5e at the
    cell's sizes (no chip, as ``tools/size_cells.py``; the trace names
    a device op after its HLO instruction), every instruction of the
    decode program that reads a held expert's weights has to be found
    by each metric's own ``scopes`` or ``ops``: another compiler, or a
    kernel put in ``ragged_dot``'s place, that names them otherwise
    fails here and not as a silent 0.13 ms on the chip."""
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu to compile with
        pytest.skip(f"no described v5e to compile for: {e}")
    dev = SingleDeviceSharding(topo.devices[0])
    c = SPEC.config("laguna-s-2.1")
    decode = SPEC.family(c["family"]).sizing(c, lambda tree: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=dev),
        tree))[0]
    assert decode["name"].startswith("decode")
    hlo = decode["fn"].lower(*decode["args"]).compile().as_text()
    entry = hlo[hlo.index("ENTRY "):]
    readers = [line for line in entry.splitlines()
               if re.search(r"\(.*%params__blocks____\d+____mlp____we[123]__",
                            line) and " parameter(" not in line]
    sparse = c["mlp_layer_types"][:c["serve"]["num_hidden_layers"]].count(
        "sparse")
    assert len(readers) >= 3 * sparse == 12
    for metric in ("moe.experts_ms_p50", "kernel.expert_matmul_hbm_roofline"):
        args = SPEC.metric_file(metric)["args"]
        assert args["match"] == "jit__decode"
        for line in readers:
            name = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = ", line).group(1)
            scope = re.search(r'op_name="([^"]*)"', line)
            assert name.startswith(tuple(args["ops"])) or any(
                s in (scope.group(1) if scope else "")
                for s in args["scopes"]), (metric, line[:200])


def test_routed_roofline_on_a_hand_made_trace(monkeypatch):
    """One 20 ms decode tick on a v5e whose ops under ``moe.experts``
    take 12 ms (the ``while`` that holds one of them is a container and
    is not counted twice); ``ENG_ROUTE`` says 468 held experts were
    touched: 8,833,204,224 bytes, 10.785 ms at 819 GB/s, 89.88%."""
    dev = "/device:TPU:0"

    def ev(line, name, start, dur, scope=None):
        e = {"plane": dev, "line": line, "name": name, "start": start,
             "dur": dur}
        if scope:
            e["scope"] = scope
        return e

    experts = "jit(_decode)/moe.experts/ragged_dot_general"
    events = [
        ev("XLA Modules", "jit__decode(7)", 1_000, 20_000_000),
        ev("XLA Ops", "custom-call.1", 2_000, 7_000_000, experts),
        ev("XLA Ops", "while.3", 8_000_000, 5_000_000,
           "jit(_decode)/moe.experts/while"),
        ev("XLA Ops", "fusion.9", 8_000_000, 5_000_000, experts),
        ev("XLA Ops", "fusion.2", 14_000_000, 3_000_000,
           "jit(_decode)/attn.full/dot_general"),
        ev("XLA Ops", "custom-call.1", 30_000_000, 1_000_000, experts)]
    routes = np.array([[500, 0xA07, 3, 64, 320, 320, 468, 9]])
    monkeypatch.setattr(_route, "decode_routes",
                        lambda ctx, traced=False: routes)
    ctx = context(events)
    assert _route.scope_times(ctx, "jit__decode", ["moe.experts"]) == [
        12_000_000]
    read = SPEC.reader("routed_roofline_pct")
    share = read(ctx, "jit__decode", "expert_matmul", ["moe.experts"])
    assert abs(share - 100 * (8_833_204_224 / 819e9) / 12e-3) < 1e-9
    assert 89.87 < share < 89.89
    # no live positions in a context without ticks: nothing to read
    assert read(ctx, "jit__decode", "decode_tick_moe", None) is None
    ctx = context(events, rehearsal=True)
    assert read(ctx, "jit__decode", "expert_matmul", ["moe.experts"]) is None
    ratio = SPEC.reader("ring_ratio_pct")
    assert ratio(ctx, "absent", ["held", "absent"]) == 50.0
    # 468 of 4 expert layers x 128 held
    assert abs(ratio(ctx, "touched", ["capacity"]) - 100 * 468 / 512) < 1e-9


def test_live_sizes_clip_each_slot_to_the_window():
    """Two requests in their slots over two traced ticks: 600 and 100
    positions held, then 601 and 101: as they are, and clipped to 512."""
    reqs = [{"admit": 0.05, "done": None, "prompt": [0] * 590,
             "stamps": [(0.1, 10), (0.2, 11)]},
            {"admit": 0.05, "done": None, "prompt": [0] * 95,
             "stamps": [(0.1, 5), (0.2, 6)]},
            {"admit": None, "done": None, "prompt": [0] * 9, "stamps": []}]
    ticks = [(0.09, 0.1, 2, 700), (0.19, 0.2, 2, 702)]
    ctx = context([], requests=reqs, ticks=ticks)
    assert _route.live_sizes(ctx, 512) == {
        "live_positions": 701.0, "live_window_positions": 612.5}
