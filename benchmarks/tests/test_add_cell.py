"""A later PR adds a cell, a configuration, a traffic mix and a per-layer
metric by adding files and entries, editing no file that is there."""
import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def test_throw_away_cell_by_files_only(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "benchmarks"), root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "pbs_tpu"), root / "pbs_tpu")
    os.symlink(os.path.join(ROOT, "native"), root / "native")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    before = {p: os.path.getmtime(os.path.join(d, p))
              for d, _, fs in os.walk(root / "benchmarks") for p in fs}

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
    bench["workloads"].append({"name": "throwaway.short", "config":
                               "throwaway", "traffic": "short-out",
                               "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "tpot_p95_ms":
            m["workloads"].append("throwaway.short")
    bench["per_layer"].append({
        "name": "engine.completed", "unit": "requests", "better": "higher",
        "source": "program_counter", "layer": "engine (models/serving.py)",
        "moves": "tpot_p95_ms", "workloads": ["throwaway.short"]})
    json.dump(bench, open(root / "BENCHMARK.json", "w"))

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    outs = []
    for trace in ("0", "1"):
        p = subprocess.run(
            [sys.executable, "benchmarks/run.py", "--workload",
             "throwaway.short", "--seed", "12", "--seconds", "2",
             "--trace", trace, "--rehearsal"], cwd=root, env=env,
            capture_output=True, text=True, timeout=600)
        assert p.returncode == 0, p.stderr[-2000:]
        outs.append(json.loads(p.stdout.strip().splitlines()[-1]))
    assert outs[0]["correct"] and outs[1]["correct"]
    assert set(outs[0]["metrics"]) == {"rehearsal_tpot_p95_ms",
                                       "rehearsal_setup_s"}
    assert outs[1]["metrics"]["rehearsal_engine.completed"]["value"] > 0
    # Nothing that was there was touched.
    assert all(os.path.getmtime(os.path.join(d, p)) == before[p]
               for d, _, fs in os.walk(b) for p in fs if p in before)


def test_no_tpu_and_no_rehearsal_is_an_error():
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "train-solo",
         "--seed", "1", "--seconds", "1", "--trace", "0"], cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
