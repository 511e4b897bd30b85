"""Compile each cell's programs for a v5e that is described, not
attached, and add up what they need (no chip; nothing runs).

    JAX_PLATFORMS=cpu python3 benchmarks/tools/size_cells.py [config ...]

The train step is the program's own jitted ``make_train_step``; the
serving programs are the engine's two (slot prefill, slot decode),
rebuilt here from the engine's own ``_slot_forward`` /
``ingest_slot_prompt`` because the engine compiles them inside a
constructor that allocates on a device. Peak = everything resident
(train state, serving weights, KV cache) + the largest of the programs'
temporaries and un-aliased outputs; the sums are written into the
configuration files' sizing notes and PERF.md by hand.
"""

from __future__ import annotations

import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from benchmarks.harness import build  # noqa: E402
from benchmarks.harness.spec import Spec  # noqa: E402
from benchmarks.reference import model as ref  # noqa: E402
from pbs_tpu.models import make_train_step  # noqa: E402
from pbs_tpu.models.serving import (  # noqa: E402
    _slot_forward, ingest_slot_prompt, init_slot_cache)

GIB = float(1 << 30)


def on(tree, sharding):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=sharding), tree)


def report(name, compiled) -> dict:
    m = compiled.memory_analysis()
    out = {"args": m.argument_size_in_bytes, "out": m.output_size_in_bytes,
           "alias": m.alias_size_in_bytes, "temp": m.temp_size_in_bytes,
           "peak": m.peak_memory_in_bytes}
    print(f"  {name}: " + " ".join(f"{k}={v / GIB:.3f}GiB"
                                   for k, v in out.items()), flush=True)
    return out


def size_train(c: dict, dev, layers=None, batch=None) -> dict:
    tr = dict(c["train"])
    if layers is not None:
        tr["num_hidden_layers"] = layers
    if batch is not None:
        tr["batch"] = batch
    cfg = build.transformer_config(
        c, tr["num_hidden_layers"], tr["seq"], remat=tr["remat"],
        remat_policy=tr.get("remat_policy", "full"))
    init_opt, train_step = make_train_step(cfg, learning_rate=3e-4)
    params = jax.eval_shape(lambda: ref.init_tree(
        c, ref.seed_word(0), tr["num_hidden_layers"], jnp.float32))
    state = on((params, jax.eval_shape(init_opt, params),
                jax.ShapeDtypeStruct((), jnp.int32)), dev)
    tokens = jax.ShapeDtypeStruct((tr["batch"], tr["seq"]), jnp.int32,
                                  sharding=dev)
    step = jax.jit(train_step, donate_argnums=(0,))
    r = report(f"train L={tr['num_hidden_layers']} B={tr['batch']}",
               step.lower(state, tokens).compile())
    r["resident"] = sum(x.size * x.dtype.itemsize
                        for x in jax.tree.leaves(state))
    return r


def size_serve(c: dict, dev, layers=None) -> dict:
    sv = dict(c["serve"])
    if layers is not None:
        sv["num_hidden_layers"] = layers
    cfg = build.transformer_config(c, sv["num_hidden_layers"], sv["max_len"])
    params = on(jax.eval_shape(lambda: ref.init_tree(
        c, ref.seed_word(0), sv["num_hidden_layers"], jnp.bfloat16)), dev)
    cache = on(jax.eval_shape(lambda: init_slot_cache(
        cfg, sv["slots"], sv["max_len"])), dev)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=dev)  # noqa

    def decode(params, cache, last_tok, active):
        logits, new, _ = _slot_forward(cfg, params, last_tok[:, None],
                                       cache, cache["pos"])
        new["pos"] = cache["pos"] + active.astype(jnp.int32)
        return jnp.argmax(logits[:, 0], -1), new

    def prefill(params, cache, slot, prompt, plen):
        last, cache, _ = ingest_slot_prompt(cfg, params, cache, slot,
                                            prompt, plen)
        return jnp.argmax(last), last, cache

    tag = f"L={sv['num_hidden_layers']}"
    d = report(f"decode {tag}", jax.jit(decode).lower(
        params, cache, i32(sv["slots"]),
        jax.ShapeDtypeStruct((sv["slots"],), bool, sharding=dev)).compile())
    p = report(f"prefill {tag}", jax.jit(prefill).lower(
        params, cache, i32(), i32(sv["prompt_bucket"]), i32()).compile())
    nbytes = lambda t: sum(x.size * x.dtype.itemsize  # noqa: E731
                           for x in jax.tree.leaves(t))
    return {"weights": nbytes(params), "kv": nbytes(cache),
            "transient": max(x["temp"] + x["out"] - x["alias"]
                             for x in (d, p))}


def main(argv) -> None:
    spec = Spec()
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    dev = SingleDeviceSharding(topo.devices[0])
    for name in argv or [c["name"] for c in spec.bench["configs"]]:
        c = spec.config(name)
        print(name, flush=True)
        s = size_serve(c, dev)
        resident = s["weights"] + s["kv"]
        line = {"serve_weights": s["weights"], "serve_kv": s["kv"],
                "serve_transient": s["transient"]}
        transient = s["transient"]
        if "train" in c:
            t = size_train(c, dev)
            resident += t["resident"]
            transient = max(transient, t["temp"] + t["out"] - t["alias"])
            line.update(train_state=t["resident"],
                        train_transient=t["temp"] + t["out"] - t["alias"])
        line["sum"] = resident + transient
        print("  " + json.dumps({k: round(v / GIB, 3)
                                 for k, v in line.items()}) + " GiB")


if __name__ == "__main__":
    main(sys.argv[1:])
