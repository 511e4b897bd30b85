"""A later PR adds a cell, a configuration, a traffic mix, a per-layer
metric and a model *family* by adding files and entries, editing no file
that is there."""
import hashlib
import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
ENV = dict(os.environ, JAX_PLATFORMS="cpu")


def content_hashes(top) -> dict:
    out = {}
    for d, _, files in os.walk(top):
        for name in files:
            path = os.path.join(d, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, top)] = hashlib.sha256(
                    f.read()).hexdigest()
    return out


def checkout(tmp_path):
    """A copy of the benchmark beside the program, and what every file
    of the copy held."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "benchmarks"), root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "pbs_tpu"), root / "pbs_tpu")
    os.symlink(os.path.join(ROOT, "native"), root / "native")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    return root, bench, content_hashes(root / "benchmarks")


def untouched(root, before) -> bool:
    """Nothing that was there changed (new files and caches aside)."""
    after = content_hashes(root / "benchmarks")
    return all(after.get(p) == h for p, h in before.items())


def add_cell(bench, name, config, traffic, metrics):
    bench["workloads"].append({"name": name, "config": config,
                               "traffic": traffic, "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in metrics:
            m["workloads"].append(name)


def rehearse(root, cell, trace, *extra):
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", cell, "--seed",
         "12", "--seconds", "2", "--trace", trace, "--rehearsal", *extra],
        cwd=root, env=ENV, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    readings = {k: float(v) for k, _, v in (
        line[len("check-reading "):].partition(": ") for line in lines
        if line.startswith("check-reading "))}
    return json.loads(lines[-1]), readings


def test_throw_away_cell_by_files_only(tmp_path):
    root, bench, before = checkout(tmp_path)

    # New files: a configuration (another depth of the same family), a
    # traffic mix (short outputs), a reader and a metric over it.
    b = root / "benchmarks"
    cfg = json.load(open(b / "configs" / "mistral-7b-v0.3.json"))
    cfg["name"] = "throwaway"
    cfg["rehearsal"]["serve"]["num_hidden_layers"] = 1
    json.dump(cfg, open(b / "configs" / "throwaway.json", "w"))
    mix = json.load(open(b / "traffic" / "chat-steady.json"))
    mix["rehearsal"]["serve"]["output_len"] = {"dist": "fixed", "value": 5,
                                               "min": 5, "max": 5}
    json.dump(mix, open(b / "traffic" / "short-out.json", "w"))
    (b / "readers" / "completed_count.py").write_text(
        "def read(ctx):\n"
        "    return sum(1 for r in ctx.requests if r['done'] is not None)\n")
    json.dump({"layer": "engine (models/serving.py)",
               "source": "program_counter", "moves": "tpot_p95_ms",
               "reader": "completed_count", "args": {}},
              open(b / "metrics" / "engine.completed.json", "w"))
    # New entries, appended.
    bench["configs"].append({"name": "throwaway", "source": cfg["source"],
                             "file": "benchmarks/configs/throwaway.json",
                             "reduced": ["num_hidden_layers"], "why": "test"})
    bench["per_layer"].append({
        "name": "engine.completed", "unit": "requests", "better": "higher",
        "source": "program_counter", "layer": "engine (models/serving.py)",
        "moves": "tpot_p95_ms", "workloads": []})
    add_cell(bench, "throwaway.short", "throwaway", "short-out",
             {"tpot_p95_ms", "engine.completed"})
    json.dump(bench, open(root / "BENCHMARK.json", "w"))

    outs = [rehearse(root, "throwaway.short", t)[0] for t in ("0", "1")]
    assert outs[0]["correct"] and outs[1]["correct"]
    assert set(outs[0]["metrics"]) == {"rehearsal_tpot_p95_ms",
                                       "rehearsal_setup_s"}
    assert outs[1]["metrics"]["rehearsal_engine.completed"]["value"] > 0
    assert untouched(root, before)


ROOFLINE = """
import json, sys
sys.path.insert(0, ".")
from benchmarks.harness import measure
from benchmarks.harness.spec import Spec
spec = Spec(".")
c = spec.config("moe-top2-tiny")
dev, ev = "/device:TPU:0", lambda line, name, dur: {
    "plane": "/device:TPU:0", "line": line, "name": name, "start": 100,
    "dur": dur}
ctx = measure.Context(
    family=spec.family(c["family"]), config=c, traffic={},
    device_kind="TPU v5 lite", t0=0.0, t1=1.0, requests=[], ticks=[],
    train_steps=0, train_tokens_per_step=0,
    events=[ev("XLA Modules", "jit__decode(2)", 14404),
            ev("XLA Ops", "fusion.1", 14404)],
    trace_span=(0.0, 1.0), ledger_trace={}, backlog=None)
mf = spec.metric_file("kernel.expert_matmul_roofline")
print(json.dumps(spec.reader(mf["reader"])(ctx, **mf["args"])))
"""


def test_another_family_by_files_only(tmp_path):
    """The program's own mixture-of-experts decoder, which no cell
    runs: its family file, plain reference, configuration and a kernel
    metric over the family's own cost table are *added*; a serving cell
    and a training cell then run, the int8 control fails their limits,
    and no file that was there has changed."""
    root, bench, before = checkout(tmp_path)
    fixture = os.path.join(FIXTURES, "moe-top2")
    for d, _, files in os.walk(fixture):
        for name in files:
            dest = root / "benchmarks" / os.path.relpath(d, fixture) / name
            assert not dest.exists()
            shutil.copy(os.path.join(d, name), dest)
    cfg = json.load(open(root / "benchmarks/configs/moe-top2-tiny.json"))
    bench["configs"].append({
        "name": "moe-top2-tiny", "source": cfg["source"],
        "file": "benchmarks/configs/moe-top2-tiny.json", "reduced": [],
        "why": "test"})
    bench["per_layer"].append({
        "name": "kernel.expert_matmul_roofline", "unit": "%",
        "better": "higher", "source": "device_trace",
        "layer": "kernels (whole programs until the tracing issue names "
                 "kernels)", "moves": "tpot_p95_ms", "workloads": []})
    add_cell(bench, "moe.serve", "moe-top2-tiny", "chat-steady",
             {"tpot_p95_ms", "model.decode_tick_ms_p50",
              "kernel.expert_matmul_roofline"})
    add_cell(bench, "moe.train", "moe-top2-tiny", "train-repeat",
             {"train_tokens_per_s", "model.train_step_ms_p50"})
    json.dump(bench, open(root / "BENCHMARK.json", "w"))

    limits = cfg["check"]
    out, control = rehearse(root, "moe.serve", "0", "--control", "1")
    assert out["correct"] and out["failed"] == 0
    assert set(out["check"]) == set(limits["serving"])
    assert set(out["metrics"]) == {"rehearsal_tpot_p95_ms",
                                   "rehearsal_setup_s"}
    assert control["control_gap_max"] > 3 * limits["serving"][
        "served_gap_max"]
    out, _ = rehearse(root, "moe.serve", "1")
    assert out["correct"]
    assert out["metrics"]["rehearsal_model.decode_tick_ms_p50"]["value"] > 0
    # a CPU has no roofline: the metric is left out, never 0
    assert "rehearsal_kernel.expert_matmul_roofline" not in out["metrics"]

    out, control = rehearse(root, "moe.train", "0", "--control", "1")
    assert out["correct"]
    assert set(out["check"]) == set(limits["training"])
    assert control["control_grad_sketch_gap"] > 3 * limits["training"][
        "grad_sketch_gap"]
    out, _ = rehearse(root, "moe.train", "1")
    assert out["correct"]
    assert out["metrics"]["rehearsal_model.train_step_ms_p50"]["value"] > 0

    # The roofline reader over the new family's cost table, on a
    # hand-made trace of one 14,404 ns decode tick on a v5e. 2 layers,
    # 4 slots, top-2 of 8 experts, d 64, f 96, float32: 589,824 FLOP
    # (3.0 ns at 197 TFLOP/s) and 1,179,648 bytes of expert weights
    # (1,440.4 ns at 819 GB/s): the bytes bound it, at 10.0%.
    p = subprocess.run([sys.executable, "-c", ROOFLINE], cwd=root, env=ENV,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    share = json.loads(p.stdout.strip().splitlines()[-1])
    assert abs(share - 100 * (1179648 / 819e9) / 14404e-9) < 1e-9
    assert 9.99 < share < 10.01
    assert untouched(root, before)


def test_no_tpu_and_no_rehearsal_is_an_error():
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "train-solo",
         "--seed", "1", "--seconds", "1", "--trace", "0"], cwd=ROOT,
        env=ENV, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
