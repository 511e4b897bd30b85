"""The ``moe-mla-dsa`` family's benchmark files: the cost table against
hand counts at the published widths and against the parameter tree's
and the cache's own byte counts, the configuration against the guide's
catalog row, the new cell's rehearsal (``correct`` true, the int8
control and the dense control not), the new metrics on a hand-made
trace, and what the parent's program does with the cell."""
import enum
import json
import os
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
CELL = "serve-dsa-agentcode-surge"
CONFIG = "glm-5"
REDUCED = ["first_k_dense_replace", "max_position_embeddings",
           "n_routed_experts", "num_hidden_layers", "vocab_size"]
NEW_METRICS = ("attn.mla_ms_p50", "attn.index_ms_p50",
               "attn.mla_prefill_ms_p50", "model.prefill_ms_p50.dsa",
               "kernel.latent_read_hbm_roofline",
               "kernel.index_read_hbm_roofline",
               "kernel.mla_prefill_mxu_roofline",
               "kernel.decode_tick_hbm_roofline.dsa",
               "attn.selected_share_pct")


def nbytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def test_costs_against_hand_counts():
    c = SPEC.config(CONFIG)
    fam = SPEC.family(c["family"])
    k = fam.costs
    assert k.layer_kinds(c, 5) == {"dense": 1, "sparse": 4}
    # a position for all 64 heads: (512 + 64) x 2 B; the indexer's key
    assert k.latent_row_bytes(c) == 1152 and k.index_row_bytes(c) == 256
    # 64 busy lanes past 2,048 positions, five layers: the chosen rows
    # once a lane and layer, never once a head
    assert k.latent_read_bytes(c, 5, 64 * 2048) == 64 * 2048 * 1152 * 5 \
        == 754_974_720
    # 448,000 live positions (64 lanes of 7,000)
    assert k.index_read_bytes(c, 5, 448_000) == 573_440_000
    assert k.expert_params(c) == 3 * 6144 * 2048 == 37_748_736
    assert k.expert_bytes(c, 50) == 3_774_873_600
    # the mixer: 6144 x 2048, 2048 x 16384, 6144 x 576, 512 x 28672,
    # 16384 x 6144 = 165.0 M; the indexer 2048 x 4096 + 6144 x 128 +
    # 6144 x 32 = 9.4 M; norms 6144 + 2048 + 512 + 128, the bias 128
    assert k.mixer_params(c) == 12_582_912 + 33_554_432 + 3_538_944 \
        + 14_680_064 + 100_663_296 + 8_388_608 + 786_432 + 196_608 \
        + 8_960 == 174_400_256
    # a prompt of 8,192: queries 2,048 .. 8,191 score all they see;
    # every query attends min(t + 1, 2,048)
    assert k.pairs_scored(c, 8192) == (8192 * 8193 - 2048 * 2049) // 2 \
        == 31_460_352
    assert k.pairs_attended(c, 8192) == 2048 * 2049 // 2 + 6144 * 2048 \
        == 14_681_088
    assert k.pairs_scored(c, 1000) == 0
    assert k.pairs_attended(c, 1000) == 500_500
    # per pair scored 2 x 32 x 128, per pair attended 2 x 64 x (576 + 512)
    assert k.mla_prefill_flops(c, 5, 8192) == 5 * (
        31_460_352 * 8192 + 14_681_088 * 139_264) == 11_511_351_214_080
    # everything but the routed experts once, one embedding row and the
    # three new rows a slot and layer
    assert k.other_tick_bytes(c, 5, 64) == 2_750_770_688
    sizes = {"live_positions": 448_000.0, "chosen_positions": 131_072.0,
             "experts_touched": 50.0, "prompt_tokens": 8192.0}
    assert fam.COSTS["latent_read"](c, sizes) == {"bytes": 754_974_720}
    assert fam.COSTS["index_read"](c, sizes) == {"bytes": 573_440_000}
    assert fam.COSTS["expert_matmul"](c, sizes) == {"bytes": 3_774_873_600}
    assert fam.COSTS["mla_prefill"](c, sizes) == {
        "flops": 11_511_351_214_080}
    assert fam.COSTS["decode_tick_dsa"](c, sizes) == {
        "bytes": 754_974_720 + 573_440_000 + 3_774_873_600
        + 2_750_770_688}
    for name, missing in (("latent_read", "chosen_positions"),
                          ("index_read", "live_positions"),
                          ("expert_matmul", "experts_touched"),
                          ("mla_prefill", "prompt_tokens"),
                          ("decode_tick_dsa", "chosen_positions"),
                          ("decode_tick_dsa", "experts_touched")):
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
        c, fam.reference.seed_word(0), 5, jnp.bfloat16))
    assert nbytes(tree) == 7_819_267_584             # 7.28 GiB
    assert nbytes(tree["blocks"]["00"]["attn"]) == 2 * k.mixer_params(c)
    assert "router" not in tree["blocks"]["00"]["mlp"]
    experts = sum(nbytes(tree["blocks"][f"{l:02d}"]["mlp"][w])
                  for l in range(1, 5) for w in ("we1", "we3", "we2"))
    assert experts == k.expert_bytes(c, 4 * 16) == 4_831_838_208
    slots = sv["slots"]
    # everything else: the tick's bytes less its per-slot rows, plus the
    # embedding rows no tick reads
    assert nbytes(tree) - experts == k.other_tick_bytes(c, 5, slots) \
        - slots * 6144 * 2 - 5 * slots * (1152 + 256) \
        + 19360 * 6144 * 2
    cache = jax.eval_shape(lambda: slot_program(fam.program_config(
        c, 5, sv["max_len"])).init_cache(slots, sv["max_len"]))
    assert cache["k"] == {} == cache["v"]
    assert cache["ckv"]["00"].shape == (64, 10240, 512)
    assert cache["kr"]["04"].shape == (64, 10240, 64)
    assert cache["ik"]["02"].shape == (64, 10240, 128)
    assert nbytes(cache) - 64 * 4 == 5 * 64 * 10240 * 1408 \
        == k.latent_read_bytes(c, 5, 64 * 10240) \
        + k.index_read_bytes(c, 5, 64 * 10240) == 4_613_734_400


def test_the_configuration_is_the_catalog_row_but_for_the_cut():
    c = SPEC.config(CONFIG)
    entry = next(e for e in SPEC.bench["configs"] if e["name"] == CONFIG)
    assert sorted(entry["reduced"]) == sorted(c["reduced"]) == REDUCED
    assert (c["hidden_size"], c["num_attention_heads"], c["q_lora_rank"],
            c["kv_lora_rank"], c["qk_nope_head_dim"], c["qk_rope_head_dim"],
            c["v_head_dim"], c["index_n_heads"], c["index_head_dim"],
            c["index_topk"], c["intermediate_size"],
            c["moe_intermediate_size"], c["num_experts_per_tok"],
            c["routed_scaling_factor"]) == (
        6144, 64, 2048, 512, 192, 64, 256, 32, 128, 2048, 12288, 2048, 8,
        2.5)
    assert (c["num_hidden_layers"], c["first_k_dense_replace"],
            c["n_routed_experts"], c["vocab_size"]) == (5, 1, 16, 19360)
    d, sv = c["deployment"], c["serve"]
    assert (d["chips_per_layer"], d["experts_total"], d["experts_first"]) \
        == (16, 256, 0)
    assert c["num_experts"] == c["n_routed_experts"] \
        == d["experts_total"] // d["chips_per_layer"]
    assert c["mlp_layer_types"][:5] == ["dense"] + ["sparse"] * 4
    assert sv["num_hidden_layers"] == 5 and sv["prompt_bucket"] == 8192
    assert c["max_position_embeddings"] == sv["max_len"] == 10240
    assert 32 <= sv["slots"] <= 64 and len(c["assumed"]) >= 8
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "GLM-5")
    assert entry["source"] == c["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in REDUCED:
            assert c[key] == value, key
        else:
            assert c[key] < value, key
    assert c["vocab_size"] * 8 == row["config"]["vocab_size"]


JOINED_METRICS = (
    "gateway.backlog_growth_rps", "engine.occupancy_pct",
    "engine.ttft_p95_ms.surge", "model.decode_tick_ms_p50.surge",
    "device.idle_pct.serve", "engine.tick_host_inside_ms_p50.surge",
    "engine.idle_host_pct.surge", "engine.prefill_pad_pct.surge",
    "moe.experts_ms_p50", "moe.route_ms_p50", "moe.experts_touched_pct",
    "moe.absent_share_pct", "kernel.expert_matmul_hbm_roofline",
    "setup.import_s", "setup.runtime_init_s", "setup.programs_s",
    "setup.cache_hit_pct", "setup.construct_s", "setup.warmup_s",
    "setup.unaccounted_s")


def test_the_cell_lists_what_the_issue_listed():
    """Membership only: a later PR appends cells and metrics, to this
    cell's lists too, without touching this file."""
    bench = SPEC.bench
    cell = SPEC.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "agentcode-surge", 1) and len(cell["why"]) <= 200
    entry = next(e for e in bench["configs"] if e["name"] == CONFIG)
    assert len(entry["why"]) <= 200
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in JOINED_METRICS:
        assert CELL in by_name[name]["workloads"], name
    for name in NEW_METRICS:
        m = by_name[name]
        assert m["workloads"][0] == CELL
        assert m["moves"] == "output_tokens_per_s"
        assert SPEC.metric_file(name)["layer"] == m["layer"]
        assert SPEC.metric_file(name)["source"] == m["source"]
        assert ("roofline" in name) == (m["unit"] == "%"
                                        and m["better"] == "higher")
    # no softmax layer over keys and values a head, no ring, no state
    assert not any(CELL in m["workloads"] for m in bench["per_layer"]
                   if m["name"] in ("attn.full_ms_p50", "attn.window_ms_p50",
                                    "kernel.kv_read_hbm_roofline"))
    assert {"output_tokens_per_s", "setup_s"} == {
        m["name"] for m in SPEC.metrics_of(CELL, "end_to_end")}
    sv = SPEC.traffic("agentcode-surge")["serve"]
    assert sv["prompt_len"] == {"dist": "lognormal", "median": 6144,
                                "sigma": 0.5, "min": 3072, "max": 8192}
    assert sv["output_len"] == {"dist": "lognormal", "median": 512,
                                "sigma": 0.7, "min": 128, "max": 1536}
    assert sv["burst"] == {"factor": 3.0, "on_s": 2.0, "period_s": 10.0}
    assert (sv["loop"], sv["pool"], sv["order"]) == ("open", 32, "rotate")
    assert sv["warmup_s"] >= 12
    serve = SPEC.config(CONFIG)["serve"]
    assert sv["prompt_len"]["max"] == serve["prompt_bucket"]
    assert sv["prompt_len"]["min"] > SPEC.config(CONFIG)["index_topk"]
    assert sv["prompt_len"]["max"] + sv["output_len"]["max"] \
        <= serve["max_len"]


def rehearse(trace_flag: str, *extra):
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELL, "--seed",
         "4100000021", "--seconds", "2", "--trace", trace_flag,
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
    assert 0 < m["rehearsal_moe.absent_share_pct"]["value"] < 100
    # the traffic's prompts run past the toy indexer's 16 positions
    assert 0 < m["rehearsal_attn.selected_share_pct"]["value"] < 100
    # a CPU names no scope in its trace and has no roofline: those
    # metrics are left out there, never 0 (read on a hand-made trace
    # below, and on the chip)
    assert not any(name in k for k in m for name in NEW_METRICS[:-1])
    assert any(l.startswith("ring: routing, decode ticks:") for l in lines)
    out, _, _ = rehearse("0")
    assert out["correct"]
    assert {"rehearsal_output_tokens_per_s",
            "rehearsal_setup_s"} == set(out["metrics"])


def test_the_dense_control_reads_beside_the_int8_one(capsys):
    """``tools/dsa_dense_control.py`` at the rehearsal's sizes: the
    reference with the choice left out fails both of the rehearsal's
    limits, as the int8 control does, and reads nothing up to
    ``index_topk`` positions; each reading is printed beside the
    limit."""
    tool = SPEC._module("tools", "dsa_dense_control")
    tool.main(["--rehearsal", "--rows", "4", "--len", "64", "--seed", "7"])
    lines = [l.split() for l in capsys.readouterr().out.splitlines()
             if l.startswith("check-reading ")]
    mean = {l[1]: float(l[3]) for l in lines if l[2] == "control_gap_mean:"}
    widest = {l[1]: float(l[3]) for l in lines
              if l[2] == "control_gap_max:"}
    limits = SPEC.config(CONFIG)["rehearsal"]["check"]["serving"]
    assert set(mean) == {"int8", "dense"}
    for name in mean:
        assert mean[name] > 100 * limits["served_gap_mean"]
        assert widest[name] > 100 * limits["served_gap_max"]
    assert all(l[-1] == "caught" for l in lines
               if l[2] == "control_gap_mean:")
    tool.main(["--rehearsal", "--rows", "4", "--len", "16", "--seed", "7"])
    short = {l.split()[1]: float(l.split()[3])
             for l in capsys.readouterr().out.splitlines()
             if " control_gap_mean: " in l}
    assert short["dense"] == 0.0 < short["int8"]


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
    """One 20 ms decode tick whose ops under ``attn.mla`` take 9 ms (2
    under ``mla.index``, 1 under ``mla.select``, 5 under ``mla.attend``)
    with 60 lanes busy that see 420,000 positions and choose 122,880,
    and 50 held experts touched; four prompt forwards: three at the
    bucket's 8,192 rows (prompts of 8,000, 6,000 and 7,000 tokens; 200,
    150 and 175 ms of ops, 150, 110 and 130 of them under ``attn.mla``,
    of those 100, 75 and 88 under ``mla.index`` + ``mla.attend``; a
    ``while`` that holds the blocks is a container and not counted
    twice) and one at 4,096 rows, which the prefill metrics leave out. The ring's clock runs 1 ms
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

    dec, pre = "jit(_decode)/jit(main)/", "jit(_prefill)/jit(main)/"
    t_dec = 5 * ms
    events = [
        ev("XLA Modules", "jit__decode(7)", t_dec, 20 * ms),
        ev("XLA Ops", "fusion.1", t_dec + ms, 1 * ms,
           dec + "attn.mla/dot_general"),
        ev("XLA Ops", "fusion.2", t_dec + 2 * ms, 2 * ms,
           dec + "attn.mla/mla.index/bjd,btd->bjt/dot_general"),
        ev("XLA Ops", "fusion.3", t_dec + 4 * ms, 1 * ms,
           dec + "attn.mla/mla.select/while/body/reduce_sum"),
        ev("XLA Ops", "fusion.4", t_dec + 5 * ms, 5 * ms,
           dec + "attn.mla/mla.attend/bht,btr->bhr/dot_general"),
        ev("XLA Ops", "fusion.5", t_dec + 11 * ms, 6 * ms,
           dec + "moe.experts/ragged_dot"),
        ev("XLA Ops", "fusion.6", t_dec + 17 * ms, 2 * ms,
           dec + "mlp.dense/dot_general"),
        ev("threads", "bench.serve_step", t_dec - 2 * ms, 24 * ms,
           plane="/host:CPU")]
    ring = TraceBuffer(64)
    ring.emit(t_dec - 3 * ms, Ev.ENG_TICK, 24 * ms, 100, 60, 0, 0, 0)
    ring.emit(t_dec - 2 * ms, Ev.ENG_DECODE, 100, ms, 20 * ms, ms, 1)
    ring.emit(t_dec - 2 * ms, Ev.ENG_ROUTE, 100, 60, 30, 450, 50, 3)
    ring.emit(t_dec - 2 * ms, Ev.ENG_SELECT, 100, 60, 420_000, 122_880,
              2048)
    # (start on the trace's clock, rows, prompt tokens, ms under
    # attn.mla, of which under mla.index and mla.attend, ms elsewhere)
    for rid, (at, rows, plen, mix, index, attend, rest) in enumerate((
            (40 * ms, 8192, 8000, 150, 20, 80, 50),
            (272 * ms, 4096, 3500, 40, 5, 20, 25),
            (515 * ms, 8192, 6000, 110, 15, 60, 40),
            (762 * ms, 8192, 7000, 130, 18, 70, 45))):
        other = mix - index - attend
        events += [
            ev("XLA Modules", f"jit__prefill({9 + rows})", at, 228 * ms),
            ev("XLA Ops", "fusion.7", at + ms, other * ms,
               pre + "attn.mla/dot_general"),
            ev("XLA Ops", "while.8", at + 60 * ms, (index + attend) * ms,
               pre + "attn.mla/while"),
            ev("XLA Ops", "fusion.9", at + 60 * ms, index * ms,
               pre + "attn.mla/while/body/mla.index/dot_general"),
            ev("XLA Ops", "fusion.10", at + (60 + index) * ms,
               attend * ms, pre + "attn.mla/while/body/mla.attend/exp"),
            ev("XLA Ops", "fusion.11", at + 170 * ms, rest * ms,
               pre + "moe.experts/ragged_dot"),
            ev("threads", "bench.serve_step", at - 2 * ms, 231 * ms,
               plane="/host:CPU")]
        ring.emit(at - 3 * ms, Ev.ENG_TICK, 231 * ms, rid, 60, 1, 0, 0)
        ring.emit(at - 2 * ms, Ev.ENG_PREFILL, rid, rid, 0, 1 * ms,
                  229 * ms, rows)
        ring.emit(at - 2 * ms, Ev.ENG_SELECT, rid, plen,
                  plen * (plen + 1) // 2, 1, 2048)
        ring.emit(at - 2 * ms - 1000, Ev.ENG_ADMIT, rid, rid, 0, plen, 7,
                  230 * ms)
    monkeypatch.setattr(pt, "live_rings", lambda: [("engine", ring)])
    ctx = context(events, ticks=[(0.002, 0.03, 60, 420_000)])
    c, k = ctx.config, ctx.family.costs
    got = {name: SPEC.reader(SPEC.metric_file(name)["reader"])(
        ctx, **SPEC.metric_file(name)["args"]) for name in NEW_METRICS}
    assert got["attn.mla_ms_p50"] == 9.0
    assert got["attn.index_ms_p50"] == 3.0
    # the three forwards at 8,192 rows; not the one at 4,096
    assert got["attn.mla_prefill_ms_p50"] == 130.0
    assert got["model.prefill_ms_p50.dsa"] == 175.0
    # the decode's record alone: 122,880 of 420,000
    assert abs(got["attn.selected_share_pct"] - 100 * 122_880 / 420_000) \
        < 1e-9
    # 122,880 rows x 1,152 B x 5 layers at 819 GB/s = 0.864 ms of 5
    assert abs(got["kernel.latent_read_hbm_roofline"]
               - 100 * (122_880 * 1152 * 5 / 819e9) / 5e-3) < 1e-9
    # 420,000 rows x 256 B x 5 = 0.656 ms of 2
    assert abs(got["kernel.index_read_hbm_roofline"]
               - 100 * (420_000 * 256 * 5 / 819e9) / 2e-3) < 1e-9
    # each forward against its own prompt's pairs; the median share
    shares = sorted(
        100 * (k.mla_prefill_flops(c, 5, n) / 197e12) / (t * 1e-3)
        for n, t in ((8000, 100), (6000, 75), (7000, 88)))
    assert abs(got["kernel.mla_prefill_mxu_roofline"] - shares[1]) < 1e-9
    need = 122_880 * 1152 * 5 + 420_000 * 256 * 5 + 50 * 37_748_736 * 2 \
        + 2_750_770_688
    assert abs(got["kernel.decode_tick_hbm_roofline.dsa"]
               - 100 * (need / 819e9) / 20e-3) < 1e-9
    assert all(0 < got[n] < 100 for n in NEW_METRICS if "roofline" in n)
    # the accepted readers the cell joins read the same trace
    for name, want in (("moe.experts_ms_p50", 6.0),
                       ("moe.absent_share_pct", 100 * 450 / 480)):
        mf = SPEC.metric_file(name)
        assert SPEC.reader(mf["reader"])(ctx, **mf["args"]) == want
    # nothing to read: no trace, a CPU, a program that names no scope
    read = SPEC.reader("select_roofline_pct")
    args = ("jit__decode", "latent_read", ["mla.attend"])
    assert read(context(None), *args) is None
    assert read(context(events, rehearsal=True), *args) is None
    bare = [dict(e, scope="fused") for e in events]
    assert read(context(bare), *args) is None
    # the parent's program: no ENG_SELECT in its event table, no ring
    share = SPEC.reader("select_share_pct")
    monkeypatch.setattr(pt, "Ev", enum.IntEnum("Ev", {
        k: int(v) for k, v in Ev.__members__.items()
        if k != "ENG_SELECT"}))
    assert share(context(events)) is None
    assert read(context(events), *args) is None
    monkeypatch.undo()
    monkeypatch.delattr(pt, "live_rings")
    assert share(context(events)) is None
    assert read(context(events), *args) is None


def test_the_parent_program_ends_the_cell_at_once(monkeypatch):
    """A program whose plan has no latent attention kind (the parent of
    the PR that added it) leaves the cell with a message and a non-zero
    exit code before any weight is made."""
    from pbs_tpu.models import plan

    c = SPEC.config(CONFIG)
    fam = SPEC.family(c["family"])
    monkeypatch.delattr(plan, "MlaKind")
    with pytest.raises(SystemExit, match="no latent attention kind"):
        fam.program_config(c, 5, 10240)
