"""Continuous batching with speculative decoding: the slot engine
(``models/serving.py``) with a *second model* as the draft beside the
target.

No benchmark cell runs it and it takes uniform layer stacks only
(ROADMAP D14 decides its life). A model that drafts for *itself* (a
plan with a drafting block: ``plan.LayerPlan.draft``, DeepSeek-V3's
multi-token-prediction module) is not this engine's: that lives in
``ContinuousBatcher``'s own pipelined tick, switched by the plan, and
has a cell (docs/SERVING.md "The drafting tick"). The two share the
window's acceptance (``models/speculative.greedy_accept_window``,
which therefore stays whatever becomes of this file) and duplicate the
rest: the rows of room ``submit`` keeps, the greedy-only refusal, the
booking of several tokens a lane a tick (ROADMAP D14 lists it).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from pbs_tpu.models.serving import (
    Completion, ContinuousBatcher, _ns, _span)
from pbs_tpu.models.slot_programs import (
    _slot_forward, ingest_slot_prompt, init_slot_cache, slot_program)
from pbs_tpu.models.transformer import TransformerConfig


class SpeculativeBatcher(ContinuousBatcher):
    """Continuous batching WITH speculative decoding: every engine
    tick, a draft model proposes ``k`` tokens per slot and the target
    verifies all ``k+1`` positions in ONE forward; each slot advances
    by its own accepted prefix (the per-row cursors of
    ``speculative.make_per_row_speculative_generate``, which this
    engine shares its slot-cache machinery with).

    Combines the two serving accelerations that matter: continuous
    batching hides admission/retirement latency, speculation
    multiplies decode throughput by the acceptance rate — per
    engine tick a slot emits 1..k+1 tokens instead of exactly 1.
    Greedy-only (``temperature=0``): acceptance is exact token match,
    so outputs are bit-identical to the plain engine's (pinned by
    test). Static shapes throughout: the tick runs a fixed
    (n_slots, k) draft scan + one (n_slots, k+1) verify regardless of
    acceptance; finished/inactive lanes ride along masked.

    Truncation safety: a slot that hits EOS or its token budget
    mid-window retires immediately, so the device cursor (which
    advanced past the truncation) is never decoded from again — the
    next tenant's prefill rewrites it.
    """

    def __init__(self, cfg: TransformerConfig, params: dict,
                 draft_cfg: TransformerConfig, draft_params: dict,
                 k: int = 4, draft_mlp_fn=None, **kw):
        if kw.get("temperature", 0.0) != 0.0:
            raise ValueError(
                "SpeculativeBatcher is greedy-only (temperature=0): "
                "exact-match acceptance is the correctness contract")
        if k < 1:
            raise ValueError(f"k must be >= 1, got {k}")
        if cfg.vocab != draft_cfg.vocab:
            raise ValueError("draft vocab != target vocab")
        for c in (cfg, draft_cfg):
            if not slot_program(c).windows:
                raise NotImplementedError(
                    "speculation verifies k + 1 positions a tick, over "
                    "uniform layer stacks only: "
                    + slot_program(c).no_windows)
        super().__init__(cfg, params, **kw)
        self.draft_cfg = draft_cfg
        self.draft_params = draft_params
        self.draft_mlp_fn = draft_mlp_fn
        self.k = k
        self.dcache = init_slot_cache(draft_cfg, self.n_slots,
                                      self.max_len)
        if self.mesh is not None:
            # r5: speculative serving composes with the tp mesh — the
            # caller placed both trees; the draft's slot cache lies
            # like the target's. (The prefix cache also composes: a hit
            # installs the TARGET window, and the _admitted hook below
            # draft-prefills hits and misses alike, preserving the pos
            # invariant.)
            self.dcache = slot_program(draft_cfg).place_cache(
                self.dcache, self.mesh)
        self.spec_proposed = 0
        self.spec_accepted = 0
        # Draft-side FFN telemetry (a starved MoE draft collapses
        # acceptance silently; this is its alarm).
        self._draft_extra_sum = 0.0
        self._draft_extra_n = 0
        dcfg_, cfg_, n_slots = draft_cfg, cfg, self.n_slots

        @functools.partial(jax.jit, donate_argnums=(1,))
        def _draft_prefill(dparams, dcache, slot, prompt, plen):
            """Mirror of the target prefill for the draft cache: the
            shared ingest, logits discarded (the target picks tokens)."""
            _, dcache, extra = ingest_slot_prompt(
                dcfg_, dparams, dcache, slot, prompt, plen,
                mlp_fn=self.draft_mlp_fn)
            return dcache, extra

        kk = self.k

        @functools.partial(jax.jit, donate_argnums=(2, 3))
        def _spec_decode(params, dparams, tcache, dcache, cur, active):
            """One speculation round across all slots at their own
            cursors. Returns (toks (B, k+1), counts (B,), caches,
            n_proposed, n_accepted)."""
            pos = tcache["pos"]  # (B,), == dcache["pos"] by invariant

            def dstep(c, _):
                tok, dc, dp, de = c
                logits, dc, e = _slot_forward(
                    dcfg_, dparams, tok[:, None], dc, dp,
                    mlp_fn=self.draft_mlp_fn)
                nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
                return (nxt, dc, dp + 1, de + e), nxt

            zero_e = jnp.zeros((), jnp.float32)
            (last, dcache, dp, d_extra), props = jax.lax.scan(
                dstep, (cur, dcache, pos, zero_e), None, length=kk)
            t = props.T  # (B, k)
            # Ingest t_k so draft KV reaches pos+k whatever acceptance.
            _, dcache, e2 = _slot_forward(
                dcfg_, dparams, last[:, None], dcache, dp,
                mlp_fn=self.draft_mlp_fn)
            d_extra = d_extra + e2

            x = jnp.concatenate([cur[:, None], t], axis=1)  # (B, k+1)
            logits, tcache, extra = _slot_forward(
                cfg_, params, x, tcache, pos, mlp_fn=self.mlp_fn)
            g = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            from pbs_tpu.models.speculative import greedy_accept_window

            toks, m_row, _bonus = greedy_accept_window(t, g)
            adv = jnp.where(active, m_row + 1, 0)
            tcache = dict(tcache, pos=pos + adv)
            dcache = dict(dcache, pos=pos + adv)
            n_act = jnp.sum(active.astype(jnp.int32))
            return (toks, adv, tcache, dcache, kk * n_act,
                    jnp.sum(jnp.where(active, m_row, 0)), extra, d_extra)

        self._draft_prefill_fn = _draft_prefill
        self._spec_decode_fn = _spec_decode
        # Warm both programs at construction (same SLO reasoning, same
        # rebinding and same untouched cursors as the parent's warm-up).
        for rung in self.rungs:
            self.dcache = self._build(
                f"eng.draft_prefill@{rung}", rung, lambda: _draft_prefill(
                    self.draft_params, self.dcache, 0,
                    jnp.zeros((rung,), jnp.int32), 0)[0])
        self.cache, self.dcache = self._build(
            "eng.spec_decode", n_slots, lambda: _spec_decode(
                self.params, self.draft_params, self.cache, self.dcache,
                jnp.zeros((n_slots,), jnp.int32),
                jnp.zeros((n_slots,), bool))[2:4])

    def submit(self, prompt, max_new_tokens: int) -> int:
        # The verify window writes up to k+1 positions past the
        # accepted frontier; reserve that slack in the slab.
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if len(prompt) + max_new_tokens + self.k + 1 > self.max_len:
            raise ValueError(
                "prompt + max_new_tokens + k + 1 exceeds max_len "
                "(speculation needs overshoot room)")
        return super().submit(prompt, max_new_tokens)

    def _step(self) -> list[Completion]:
        # The round is fed ``last_tok`` from the host: every admission's
        # first token is read (and a request it finished retired) before
        # it, none rides behind a decode.
        unread: list = []
        done: list[Completion] = []
        self._admit(unread, done)
        self._land(unread, done)
        t_pre = _ns()
        for slot, padded, plen in self._admitted:
            self.dcache, d_extra = self._draft_prefill_fn(
                self.draft_params, self.dcache, slot,
                jnp.asarray(padded), plen)
            self._draft_extra_sum += \
                float(d_extra) / self.draft_cfg.n_layers
            self._draft_extra_n += 1
        if not self.active.any():
            self.steps += 1
            return done
        with _span("pbst.eng.decode"):
            (toks, counts, self.cache, self.dcache, prop, acc, extra,
             d_extra) = (
                self._spec_decode_fn(
                    self.params, self.draft_params, self.cache,
                    self.dcache, jnp.asarray(self.last_tok),
                    jnp.asarray(self.active)))
        t_enqueued = _ns()
        with _span("pbst.eng.sync"):
            self._mlp_extra_sum += float(extra) / self.cfg.n_layers
            self._mlp_extra_n += 1
            # kk+1 draft forwards per tick, each a per-layer sum.
            self._draft_extra_sum += (float(d_extra)
                                      / (self.draft_cfg.n_layers
                                         * (self.k + 1)))
            self._draft_extra_n += 1
            toks = np.asarray(toks)
            counts = np.asarray(counts)
            self.spec_proposed += int(prop)
            self.spec_accepted += int(acc)
        t_host = _ns()
        for slot in range(self.n_slots):
            if not self.active[slot]:
                continue
            for j in range(int(counts[slot])):
                if self._emit(slot, int(toks[slot, j])):
                    # Truncate mid-window: the device cursor is ahead,
                    # but this slot retires NOW, so it is never decoded
                    # from again.
                    done.append(self._retire(slot))
                    break
        self.steps += 1
        self._decoded(t_pre, t_enqueued, t_host)
        return done

    def stats(self) -> dict:
        st = super().stats()
        st["spec_proposed"] = self.spec_proposed
        st["spec_accepted"] = self.spec_accepted
        st["spec_acceptance"] = round(
            self.spec_accepted / self.spec_proposed, 4) \
            if self.spec_proposed else 0.0
        st["draft_mlp_extra_mean"] = round(
            self._draft_extra_sum / self._draft_extra_n, 6) \
            if self._draft_extra_n else 0.0
        return st
