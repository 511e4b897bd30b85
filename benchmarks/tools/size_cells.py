"""Compile each cell's programs for a v5e that is described, not
attached, and add up what they need (no chip; nothing runs).

    JAX_PLATFORMS=cpu python3 benchmarks/tools/size_cells.py [config ...]

Which programs, with what donation, is the configuration's family's to
say (``sizing`` in ``benchmarks/families/<family>.py``). Peak =
everything resident (train state, serving weights, KV cache) + the
largest of the programs' temporaries and un-aliased outputs; the sums
are written into the configuration files' sizing notes and PERF.md by
hand.
"""

from __future__ import annotations

import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from benchmarks.harness.spec import Spec  # noqa: E402

GIB = float(1 << 30)


def report(name, compiled) -> int:
    """Print a compiled program's memory, and return what it needs
    beyond its arguments: temporaries + outputs that alias nothing."""
    m = compiled.memory_analysis()
    out = {"args": m.argument_size_in_bytes, "out": m.output_size_in_bytes,
           "alias": m.alias_size_in_bytes, "temp": m.temp_size_in_bytes,
           "peak": m.peak_memory_in_bytes}
    print(f"  {name}: " + " ".join(f"{k}={v / GIB:.3f}GiB"
                                   for k, v in out.items()), flush=True)
    return out["temp"] + out["out"] - out["alias"]


def nbytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def main(argv) -> None:
    spec = Spec()
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    dev = SingleDeviceSharding(topo.devices[0])

    def on(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=dev), tree)

    for name in argv or [c["name"] for c in spec.bench["configs"]]:
        c = spec.config(name)
        print(name, flush=True)
        line, transient = {}, {}
        for prog in spec.family(c["family"]).sizing(c, on):
            need = report(prog["name"],
                          prog["fn"].lower(*prog["args"]).compile())
            kind = prog["name"].split()[0]
            transient[kind] = max(transient.get(kind, 0), need)
            line.update({k: nbytes(t) for k, t in prog["resident"].items()})
        resident = sum(line.values())
        line.update({f"{k}_transient": v for k, v in transient.items()})
        line["sum"] = resident + max(transient.values())
        print("  " + json.dumps({k: round(v / GIB, 3)
                                 for k, v in line.items()}) + " GiB")


if __name__ == "__main__":
    main(sys.argv[1:])
