"""Set-up from the inside (docs/TRACING.md "Where a start-up goes"): the
three records of the ``host`` ring that say when the process began
(``HOST_START``), what JAX traced, lowered and compiled or loaded
(``HOST_COMPILE``) and what each constructor itself ran
(``HOST_PHASE``)."""
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pbs_tpu.models import serving
from pbs_tpu.models.slot_programs import slot_program
from pbs_tpu.models.transformer import TransformerConfig
from pbs_tpu.obs import trace as T
from pbs_tpu.obs.trace import Ev
from pbs_tpu.runtime.job import Job
from pbs_tpu.telemetry.compile import CompileMeter
from pbs_tpu.telemetry.source import TpuBackend

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _child(script: str, tmp_path, **env) -> dict:
    """The JSON a fresh process prints last."""
    full = dict(os.environ, PYTHONPATH=REPO + os.pathsep
                + os.environ.get("PYTHONPATH", ""), **env)
    proc = subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, timeout=300,
                          cwd=str(tmp_path), env=full)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _host_records(event, since_ns: int) -> list[list[int]]:
    """Records of ``event`` that began at or after ``since_ns`` (by
    time, not by position: a worker that ran a thousand tests before
    this file has lapped the ring)."""
    ring = T.host_ring()
    recs = ring.peek(ring.capacity).astype(np.int64)
    return recs[(recs[:, 1] == int(event))
                & (recs[:, 0] >= since_ns)].tolist()


_now = time.monotonic_ns


START = """
import time
T0 = time.monotonic_ns()
import json
import jax
from pbs_tpu.utils.compile_cache import setup_compilation_cache
jax.devices()
setup_compilation_cache()
setup_compilation_cache()
from pbs_tpu.models import serving
from pbs_tpu.models.slot_programs import slot_program
from pbs_tpu.models.transformer import TransformerConfig
from pbs_tpu.obs import trace as T
cfg = TransformerConfig(vocab=64, d_model=16, n_heads=2, n_kv_heads=1,
                        n_layers=1, d_ff=32, max_seq=32)
params = slot_program(cfg).init_params(jax.random.PRNGKey(0))
serving.ContinuousBatcher(cfg, params, n_slots=2, prompt_bucket=8,
                          max_len=24)
from jax._src import monitoring
ours = [[getattr(f, "__module__", "").startswith("pbs_tpu")
         for f in get()].count(True)
        for get in (monitoring.get_event_listeners,
                    monitoring.get_event_duration_listeners,
                    monitoring.get_scalar_listeners)]
ring = T.host_ring()
recs = ring.peek(ring.capacity).astype("int64")
print(json.dumps({
    "t0": T0, "listeners": ours, "lost": int(ring.lost),
    "devices": jax.device_count(),
    "start": recs[recs[:, 1] == int(T.Ev.HOST_START)].tolist()}))
"""


def test_host_start_is_written_once_and_its_origin_is_the_processes(
        tmp_path):
    out = _child(START, tmp_path,
                 JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    assert len(out["start"]) == 1 and out["lost"] == 0
    origin, _ev, pkg, imported, answered, asked, devices, flags = \
        out["start"][0]
    # The process began before its first line ran, by the interpreter's
    # own start and no more.
    assert 0 <= out["t0"] - origin < 300_000_000, (out["t0"], origin)
    assert 0 <= pkg <= imported <= answered
    assert 0 <= asked <= answered
    assert devices == out["devices"]
    assert flags in (0, T.START_FROM_IMPORT)
    if flags:
        assert pkg == 0
    # One listener of this package on each of JAX's three streams after
    # setup_compilation_cache() and an engine's construction: the
    # meter's, which hears the cache's events too.
    assert out["listeners"] == [1, 1, 1]


def test_the_stand_in_origin_says_so(monkeypatch):
    import pbs_tpu

    start, flags = T.process_start_ns()
    assert flags == 0 and start <= pbs_tpu.T_IMPORT_NS
    real_open = open

    def no_proc(path, *a, **kw):
        if path == "/proc/self/stat":
            raise OSError("no /proc here")
        return real_open(path, *a, **kw)

    monkeypatch.setattr("builtins.open", no_proc)
    assert T.process_start_ns() == (pbs_tpu.T_IMPORT_NS,
                                    T.START_FROM_IMPORT)
    monkeypatch.undo()
    # A host that was suspended: CLOCK_BOOTTIME has run on ahead.
    monkeypatch.setattr(T.time, "clock_gettime_ns",
                        lambda _clk: T.time.monotonic_ns() + 10**9)
    assert T.process_start_ns() == (pbs_tpu.T_IMPORT_NS,
                                    T.START_FROM_IMPORT)


def test_one_record_of_each_kind_for_a_program_and_none_when_cached():
    def setup_records_once(x):
        return jnp.cos(x) * 1.0731

    fn = jax.jit(setup_records_once)
    x = jnp.ones((8, 8))
    jax.block_until_ready(x)
    n0 = _now()
    with CompileMeter.install().attribute("records-once"):
        jax.block_until_ready(fn(x))
    mine = [r for r in _host_records(Ev.HOST_COMPILE, n0)
            if r[5] == T.job_tag("records-once")]
    assert [T.COMPILE_KINDS[r[2]] for r in mine] == \
        ["trace", "lower", "backend"]
    # JAX names the function when it traces it, and the jit of it when
    # it lowers and compiles.
    assert [T.tag_name(r[4]) for r in mine] == [
        "setup_records_once", "jit(setup_records_once)",
        "jit(setup_records_once)"]
    assert all(r[3] > 0 for r in mine)
    # One after the other: each starts where the last one ended, or later.
    assert all(a[0] + a[3] <= b[0] for a, b in zip(mine, mine[1:]))
    lines = T.format_records(np.array(mine, dtype=np.int64))
    assert "HOST_COMPILE backend" in lines[2] and \
        "jit(setup_records_once) records-once" in lines[2]
    n1 = _now()
    jax.block_until_ready(fn(x))
    assert _host_records(Ev.HOST_COMPILE, n1) == []


CACHE = """
import json
import jax, jax.numpy as jnp
from pbs_tpu.utils.compile_cache import cache_counts, setup_compilation_cache
setup_compilation_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
from pbs_tpu.obs import trace as T
def cached_or_not(x):
    return jnp.tanh(x) * 1.0419
x = jax.block_until_ready(jnp.ones((8, 8)))
jax.block_until_ready(jax.jit(cached_or_not)(x))
before = cache_counts()
jax.clear_caches()
jax.block_until_ready(jax.jit(cached_or_not)(x))
ring = T.host_ring()
recs = ring.peek(ring.capacity).astype("int64")
tag = T.job_tag("jit(cached_or_not)")
print(json.dumps({
    "before": before, "after": cache_counts(),
    "backend": [r for r in recs[recs[:, 1] == int(T.Ev.HOST_COMPILE)]
                .tolist() if r[2] == 2 and r[4] == tag]}))
"""


def test_a_persistent_cache_miss_and_a_hit_are_flagged(tmp_path):
    out = _child(CACHE, tmp_path,
                 JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    first, second = out["backend"]
    assert (first[6], second[6]) == (T.CACHE_MISS, T.CACHE_HIT)
    assert first[7] == 0 and second[7] > 0   # a hit's read of the cache
    assert second[7] <= second[3]
    # cache_counts() reads the same meter (chip_smoke.py's third leg).
    assert out["after"]["hits"] - out["before"]["hits"] >= 1
    assert out["before"]["misses"] >= 1


CFG = TransformerConfig(vocab=96, d_model=16, n_heads=2, n_kv_heads=1,
                        n_layers=1, d_ff=32, max_seq=48)


@pytest.fixture(scope="module")
def params():
    return slot_program(CFG).init_params(jax.random.PRNGKey(3))


def _builds(since: int) -> list[tuple[str, int]]:
    return [(T.tag_name(r[6]), r[5])
            for r in _host_records(Ev.HOST_PHASE, since)
            if r[2] == T.job_tag("eng.build")]


def _drive(eng, prompts) -> None:
    done = eng.requests_completed
    for p in prompts:
        eng.submit(p, 3)
    for _ in range(12):
        eng.step()
    assert eng.requests_completed == done + len(prompts)


def test_an_engine_writes_a_build_for_every_program_and_none_later(
        params, monkeypatch):
    n0 = _now()
    eng = serving.ContinuousBatcher(CFG, params, n_slots=2,
                                    prompt_bucket=8, max_len=24)
    # the decode first: the prefills take the token vector it leaves
    assert _builds(n0) == [("eng.decode", 2), ("eng.prefill@8", 8),
                           ("eng.keysplit", 2)]
    phases = _host_records(Ev.HOST_PHASE, n0)
    cache = [r for r in phases if r[2] == T.job_tag("eng.cache")]
    assert len(cache) == 1 and cache[0][5] == sum(
        x.nbytes for x in jax.tree.leaves(eng.cache))
    # Every compile of a build lies inside its span and carries its
    # scope; the span's compile wall is the meter's, so wall less
    # compile is what ran.
    compiles = _host_records(Ev.HOST_COMPILE, n0)
    for r in phases:
        if r[2] != T.job_tag("eng.build"):
            continue
        inside = [c for c in compiles if r[0] <= c[0] < r[0] + r[3]]
        # (The key split's two programs are the process's: another
        # test's engine may have built them already.)
        assert inside or r[6] == T.job_tag("eng.keysplit")
        assert all(c[5] == r[6] for c in inside)
        assert all(c[0] + c[3] <= r[0] + r[3] for c in inside)
        assert sum(c[3] for c in inside) == r[4] <= r[3]
    assert {T.tag_name(c[4]) for c in compiles
            if c[5] == T.job_tag("eng.decode")} >= {"_decode",
                                                    "jit(_decode)"}
    # The docstring's promise: nothing compiles under a request.
    n1 = _now()
    _drive(eng, ([1, 2, 3], [4, 5, 6, 7, 8, 9, 10], [11]))
    assert _host_records(Ev.HOST_COMPILE, n1) == []

    # A second engine of another rung ladder in the same process: its
    # own builds, and still none under its requests.
    monkeypatch.setattr(serving, "prefill_rungs",
                        lambda bucket: (bucket // 2, bucket))
    n2 = _now()
    eng2 = serving.ContinuousBatcher(CFG, params, n_slots=3,
                                     prompt_bucket=16, max_len=40)
    assert _builds(n2) == [("eng.decode", 3), ("eng.prefill@8", 8),
                           ("eng.prefill@16", 16), ("eng.keysplit", 2)]
    n3 = _now()
    _drive(eng2, ([1, 2, 3], list(range(1, 13)), [5] * 8))
    _drive(eng, ([7, 8],))
    assert _host_records(Ev.HOST_COMPILE, n3) == []


def test_a_jobs_first_step_is_a_span_and_its_second_compiles_nothing():
    step = jax.jit(lambda x: jnp.tanh(x * 1.0457) + 0.5)
    job = Job("setup-first", step_fn=step, state=jnp.ones((24, 24)),
              max_steps=3)
    be = TpuBackend(profile_every=0)
    n0 = _now()
    be._invoke(job, job.step_fn)
    first = [r for r in _host_records(Ev.HOST_PHASE, n0)
             if r[2] == T.job_tag("exec.first")]
    assert len(first) == 1 and first[0][6] == T.job_tag("setup-first")
    compiles = _host_records(Ev.HOST_COMPILE, n0)
    assert [T.COMPILE_KINDS[c[2]] for c in compiles] == \
        ["trace", "lower", "backend"]
    assert all(c[5] == T.job_tag("setup-first") for c in compiles)
    span, step_rec = first[0], be.trace.peek().astype("int64").tolist()[-1]
    assert sum(c[3] for c in compiles) == span[4] == step_rec[5]
    n1 = _now()
    be._invoke(job, job.step_fn)
    assert _host_records(Ev.HOST_COMPILE, n1) == []
    assert _host_records(Ev.HOST_PHASE, n1) == []
