"""The prefill ladder: a prompt is padded to the smaller of two
compiled lengths that holds it (``slot_programs.prefill_rungs``: the bucket
and its half), not always to ``prompt_bucket``.

What has to hold: the ladder follows from the bucket alone; the tokens
served are the ones the single-bucket engine serves (float32, CPU:
padding rows were never read by a real query, nor routed); every rung
is compiled in the constructor and traffic compiles nothing; the
``ENG_PREFILL`` record and ``stats()`` say what each forward ran at.
Widths are toy, lengths are not: the floor of the ladder is 256 rows,
so these engines take buckets of 512."""

import copy
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness.spec import Spec
from benchmarks.reference import moe_mixed_attn as ref
from benchmarks.run import overlay
from pbs_tpu.models.serving import ContinuousBatcher
from pbs_tpu.models.slot_programs import prefill_rungs
from pbs_tpu.models.spec_serving import SpeculativeBatcher
from pbs_tpu.models.transformer import TransformerConfig, init_params
from pbs_tpu.obs.trace import Ev

BUCKET, MAX_LEN = 512, 640
CFG = TransformerConfig(vocab=128, d_model=32, n_layers=2, n_heads=4,
                        n_kv_heads=2, d_ff=64, max_seq=MAX_LEN,
                        dtype=jnp.float32)


def prompt_of(n: int, salt: int = 0) -> list[int]:
    return [int(t) for t in
            np.random.default_rng(1000 * salt + n).integers(1, 120, n)]


#: Both rungs and both of each rung's edges.
LENGTHS = (3, 120, 256, 257, 400, 512)


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.PRNGKey(0))


def serve(eng, prompts, max_new) -> list[list[int]]:
    """The completions of ``prompts`` in order, through whatever else
    the engine's slots hold meanwhile."""
    rids = [eng.submit(p, max_new) for p in prompts]
    done = {}
    while eng.has_work():
        done.update({c.request_id: c.tokens for c in eng.step()})
    return [done[r] for r in rids]


def flatten(eng) -> None:
    """The engine as it was before the ladder: one rung, the bucket."""
    eng.rungs = (eng.bucket,)


# -- (1) the ladder and the choice of a rung ---------------------------------


@pytest.mark.parametrize("bucket,rungs", [
    (8, (8,)), (48, (48,)), (64, (64,)), (256, (256,)), (257, (257,)),
    (510, (510,)), (511, (511,)), (512, (256, 512)), (768, (384, 768)),
    (1024, (512, 1024)), (2048, (1024, 2048))])
def test_the_ladder_follows_from_the_bucket(bucket, rungs):
    assert prefill_rungs(bucket) == rungs


@pytest.mark.parametrize("bucket,plen,rung", [
    (48, 1, 48), (48, 48, 48),
    (256, 17, 256), (256, 256, 256),
    (512, 1, 256), (512, 48, 256), (512, 256, 256), (512, 257, 512),
    (512, 512, 512),
    (1024, 48, 512), (1024, 256, 512), (1024, 257, 512), (1024, 512, 512),
    (1024, 513, 1024), (1024, 1024, 1024)])
def test_a_prompt_runs_at_the_smallest_rung_that_holds_it(bucket, plen,
                                                         rung):
    eng = types.SimpleNamespace(rungs=prefill_rungs(bucket))
    assert ContinuousBatcher._rung(eng, plen) == rung


# -- (2) the same tokens ------------------------------------------------------


def test_dense_scan_serves_the_same_tokens(params):
    eng = ContinuousBatcher(CFG, params, n_slots=3, prompt_bucket=BUCKET,
                            max_len=MAX_LEN)
    assert eng.rungs == (256, 512)
    prompts = [prompt_of(n) for n in LENGTHS]
    laddered = serve(eng, prompts, 12)
    flatten(eng)
    assert serve(eng, prompts, 12) == laddered
    assert all(len(t) == 12 for t in laddered)


WINDOW = 384  # a ring longer than the short rung, shorter than the bucket


@pytest.fixture(scope="module")
def planned():
    """The sparse family's toy preset with its window stretched over
    the short rung: window and full layers, held experts, one cache."""
    spec = Spec()
    full = spec.config("laguna-s-2.1")
    c = copy.deepcopy(overlay(full, full["rehearsal"]))
    c["sliding_window"] = WINDOW
    n = c["serve"]["num_hidden_layers"]
    cfg = spec.family(c["family"]).program_config(c, n, MAX_LEN)
    params = jax.jit(lambda s: ref.init_tree(c, s, n, jnp.float32))(
        ref.seed_word(11))
    return cfg, params


def test_planned_stack_with_a_ring_longer_than_the_rung(planned):
    """A window layer ingests a prompt shorter than its ring (S = 256
    rows for W = 384 entries: ``_plan_forward`` clips ``src`` to the
    rows it has, and entries past the cursor are not live), and decodes
    on from it past a lap of the ring, as after a prompt forward of the
    whole bucket (S > W)."""
    cfg, params = planned
    eng = ContinuousBatcher(cfg, params, n_slots=2, prompt_bucket=BUCKET,
                            max_len=MAX_LEN)
    assert eng.rungs == (256, 512)
    assert eng.cache["k"]["01"].shape[1] == WINDOW
    prompts = [prompt_of(n) for n in (5, 250, 256, 300)]
    laddered = serve(eng, prompts, WINDOW - 250 + 8)   # 250 + 142 > W
    flatten(eng)
    assert serve(eng, prompts, WINDOW - 250 + 8) == laddered


def test_prefix_hit_on_an_entry_a_short_rung_stored(params):
    eng = ContinuousBatcher(CFG, params, n_slots=2, prompt_bucket=BUCKET,
                            max_len=MAX_LEN, prefix_cache_size=4)
    # Slot 0 first holds a long tenant, so that the window cut after the
    # short-rung prefill carries another prompt's keys past the rung.
    serve(eng, [prompt_of(500, salt=1)], 2)
    short = prompt_of(90)
    miss = serve(eng, [short], 10)
    assert (eng.prefix_hits, eng.prefill_count) == (0, 2)
    assert eng.prefill_rows == 512 + 256
    hit = serve(eng, [short], 10)
    assert (eng.prefix_hits, eng.prefill_count) == (1, 2)
    flatten(eng)
    eng._prefix_cache.clear()
    assert serve(eng, [short], 10) == miss == hit
    assert eng.prefill_rows == 512 + 256 + 512


def test_speculative_engine_follows_by_shape(params):
    noise = jax.random.normal(jax.random.PRNGKey(7), params["head"].shape)
    dparams = dict(params, head=params["head"] + 0.01 * noise)
    kw = dict(n_slots=2, prompt_bucket=BUCKET, max_len=MAX_LEN)
    spec = SpeculativeBatcher(CFG, params, CFG, dparams, k=3, **kw)
    assert spec._draft_prefill_fn._cache_size() == 2
    prompts = [prompt_of(n) for n in (7, 256, 300)]
    laddered = serve(spec, prompts, 10)
    assert spec._draft_prefill_fn._cache_size() == 2
    assert spec.spec_accepted > 0
    plain = ContinuousBatcher(CFG, params, **kw)
    flatten(plain)
    assert serve(plain, prompts, 10) == laddered
    flatten(spec)
    assert serve(spec, prompts, 10) == laddered


# -- (3) every rung is compiled in the constructor, and nothing later --------


def test_one_compiled_instance_a_rung_and_none_under_traffic(params):
    eng = ContinuousBatcher(CFG, params, n_slots=2, prompt_bucket=BUCKET,
                            max_len=MAX_LEN)
    assert eng._prefill_fn._cache_size() == len(eng.rungs) == 2
    assert eng._decode_fn._cache_size() == 1
    serve(eng, [prompt_of(n) for n in LENGTHS], 3)
    assert eng._prefill_fn._cache_size() == 2
    assert eng._decode_fn._cache_size() == 1
    small = ContinuousBatcher(CFG, params, n_slots=2, prompt_bucket=16,
                              max_len=64)
    assert small.rungs == (16,) and small._prefill_fn._cache_size() == 1


# -- (4) the counter ----------------------------------------------------------


def test_prefill_record_carries_its_rung_and_stats_add_up(params):
    eng = ContinuousBatcher(CFG, params, n_slots=2, prompt_bucket=BUCKET,
                            max_len=MAX_LEN)
    serve(eng, [prompt_of(n) for n in LENGTHS], 2)
    recs = eng.trace.peek(eng.trace.capacity).astype(np.int64)
    plen = {int(r[3]): int(r[5]) for r in recs if r[1] == Ev.ENG_ADMIT}
    rows = {int(r[3]): int(r[7]) for r in recs if r[1] == Ev.ENG_PREFILL}
    assert [plen[i] for i in range(len(LENGTHS))] == list(LENGTHS)
    assert [rows[i] for i in range(len(LENGTHS))] == [
        256, 256, 256, 512, 512, 512]
    st = eng.stats()
    assert st["prefill_count"] == len(LENGTHS)
    assert st["prefill_rows"] == sum(rows.values()) == 3 * 256 + 3 * 512
    assert st["prefill_prompt_tokens"] == sum(LENGTHS)
