"""Speculative decoding: draft-model propose-k, target verify-in-one.

Serving capability with no reference analog (the reference predates
LLM serving entirely — SURVEY.md §0); the TPU-first design constraint
is the same one the rest of the serving stack obeys: **static shapes
everywhere**. Each speculation round does a fixed amount of work —
k draft decode steps plus ONE target forward over k+1 tokens — and
advances a *traced* number of tokens (accepted prefix + bonus), so the
whole generate loop is a single compiled ``lax.while_loop`` with two
XLA programs (draft step, target verify) regardless of acceptance.

Greedy (temperature=0) semantics, and therefore **token-exact**: the
output is bit-identical to plain greedy decoding of the target model —
pinned by test. Acceptance across a batch is synchronized at the
batch-min (rows that verified further simply re-propose the same
deterministic tokens next round), which keeps the KV caches' scalar
``pos`` shared across rows — the price of static shapes, paid in
re-verification rather than in per-row bookkeeping.

Cache rollback is position arithmetic: ``pos`` is authoritative, the
slab tail past it is both masked in cached attention and overwritten
by later writes (``generate._cached_attention``), so "undo the
unaccepted tokens" is ``cache["pos"] = p`` — no data movement.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from pbs_tpu.models.generate import forward_with_cache, init_cache
from pbs_tpu.models.transformer import TransformerConfig


def make_speculative_generate(
    cfg: TransformerConfig,
    draft_cfg: TransformerConfig,
    max_new_tokens: int,
    k: int = 4,
    target_fwd=None,
    draft_fwd=None,
):
    """Returns ``spec_generate(params, draft_params, prompt) ->
    (toks (B, max_new_tokens), stats)`` — greedy, token-exact vs the
    target's own greedy decode. ``stats``: rounds, proposed, accepted
    (device scalars; acceptance_rate = accepted / proposed).

    ``target_fwd``/``draft_fwd`` generalize over model families:
    ``fwd(params, tokens, cache) -> (logits, cache[, extra])`` — the
    dense cached forward is the default; pass
    ``moe_forward_with_cache`` (via a closure binding its config) to
    speculate into an MoE target. Both families share the KV-cache
    layout (MoE changes the FFN, not attention), so ``init_cache``
    covers both.

    MoE caveat: token-exactness vs the plain decode loop requires the
    router to be **dropless** — use ``MoEConfig(dropless=True)``,
    which makes overflow structurally impossible (capacity = group
    tokens) rather than relying on an ample ``capacity_factor`` for
    the particular batch shapes. Capacity dropping makes MoE logits
    depend on which tokens share the forward, so a k+1-token verify
    can route — and therefore score — differently than
    one-token-at-a-time decode; with zero drops, routing is per-token
    and the exactness proof carries over unchanged (pinned by test).
    """
    if k < 1:
        raise ValueError(f"k must be >= 1, got {k}")
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
    if cfg.vocab != draft_cfg.vocab:
        raise ValueError(
            f"draft vocab {draft_cfg.vocab} != target vocab {cfg.vocab}")

    if target_fwd is None:
        def target_fwd(params, tokens, cache):  # noqa: F811
            return forward_with_cache(cfg, params, tokens, cache)
    if draft_fwd is None:
        def draft_fwd(params, tokens, cache):  # noqa: F811
            return forward_with_cache(draft_cfg, params, tokens, cache)

    def _call(fwd, params, tokens, cache):
        out = fwd(params, tokens, cache)
        return out[0], out[1]  # tolerate (logits, cache, extra)

    def spec_generate(params: dict, draft_params: dict,
                      prompt: jax.Array):
        B, P = prompt.shape
        # Room for the last round to overshoot by up to k+1 tokens.
        max_len = P + max_new_tokens + k + 1
        tcache = init_cache(cfg, B, max_len=max_len)
        dcache = init_cache(draft_cfg, B, max_len=max_len)

        tlogits, tcache = _call(target_fwd, params, prompt, tcache)
        tlogits = tlogits[:, -1, :]
        _dl, dcache = _call(draft_fwd, draft_params, prompt, dcache)
        first = jnp.argmax(tlogits, axis=-1).astype(jnp.int32)  # (B,)

        out = jnp.zeros((B, max_new_tokens + k + 1), jnp.int32)
        out = out.at[:, 0].set(first)

        def round_body(carry):
            (out, n_out, cur, tcache, dcache, rounds, proposed, accepted,
             reverified_tot) = carry
            p0 = tcache["pos"]

            # Draft proposes k tokens (consuming cur..t_{k-1}).
            def dstep(c, _):
                tok, dc = c
                logits, dc = _call(draft_fwd, draft_params,
                                   tok[:, None], dc)
                nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
                return (nxt, dc), nxt

            (last, dcache), props = jax.lax.scan(
                dstep, (cur, dcache), None, length=k)
            t = props.T  # (B, k): t_1..t_k
            # Ingest t_k too so the draft has KV through position p0+k
            # whatever the acceptance (its logits are discarded).
            _, dcache = _call(draft_fwd, draft_params,
                              last[:, None], dcache)

            # Target verifies all k+1 positions in one forward.
            x = jnp.concatenate([cur[:, None], t], axis=1)  # (B, k+1)
            logits, tcache = _call(target_fwd, params, x, tcache)
            g = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # (B, k+1)

            # Per-row accepted-prefix length; lockstep at the batch min.
            match = (t == g[:, :k]).astype(jnp.int32)
            m_row = jnp.sum(jnp.cumprod(match, axis=1), axis=1)  # (B,)
            m = jnp.min(m_row)
            bonus = jnp.take(g, m, axis=1)  # (B,): g_m per row
            # Tokens rows verified past the batch-min: they will be
            # re-proposed and re-verified next round — the lockstep
            # tax the per-row variant eliminates.
            reverified = jnp.sum(m_row - m)

            # Emit t_1..t_m then the bonus; the static-width window may
            # carry junk past m+1 — the next round's write (or the
            # final slice) covers it.
            round_toks = jnp.concatenate(
                [t, jnp.zeros((B, 1), jnp.int32)], axis=1)
            round_toks = jax.lax.dynamic_update_slice(
                round_toks, bonus[:, None], (0, m))
            out = jax.lax.dynamic_update_slice(out, round_toks, (0, n_out))

            # Roll both caches back to the accepted frontier.
            tcache = dict(tcache, pos=p0 + m + 1)
            dcache = dict(dcache, pos=p0 + m + 1)
            return (out, n_out + m + 1, bonus, tcache, dcache,
                    rounds + 1, proposed + k, accepted + m,
                    reverified_tot + reverified)

        def cond(carry):
            return carry[1] < max_new_tokens

        zero = jnp.zeros((), jnp.int32)
        carry = (out, jnp.ones((), jnp.int32), first, tcache, dcache,
                 zero, zero, zero, zero)
        out, n_out, _, _, _, rounds, proposed, accepted, reverified = (
            jax.lax.while_loop(cond, round_body, carry))
        stats = {"rounds": rounds, "proposed": proposed,
                 "accepted": accepted, "reverified": reverified}
        return out[:, :max_new_tokens], stats

    return spec_generate


def greedy_accept_window(t: jax.Array, g: jax.Array):
    """The ONE copy of greedy window acceptance, shared by the
    per-row generator and the speculative serving engine.

    ``t`` (B, k): draft proposals; ``g`` (B, k+1): target argmax over
    the verify window. Returns ``(toks (B, k+1), m_row (B,),
    bonus (B,))`` where row b of ``toks`` holds its accepted prefix
    t_1..t_{m_b} with the bonus token g_{m_b} packed at column m_b
    (columns past m_b carry junk the caller's cursor arithmetic never
    reads)."""
    B, k = t.shape
    match = (t == g[:, :k]).astype(jnp.int32)
    m_row = jnp.sum(jnp.cumprod(match, axis=1), axis=1)  # (B,)
    bonus = jnp.take_along_axis(g, m_row[:, None], axis=1)[:, 0]
    cols = jnp.arange(k + 1, dtype=jnp.int32)[None, :]
    toks = jnp.concatenate([t, jnp.zeros((B, 1), jnp.int32)], axis=1)
    toks = jnp.where(cols == m_row[:, None], bonus[:, None], toks)
    return toks, m_row, bonus


def make_per_row_speculative_generate(
    cfg: TransformerConfig,
    draft_cfg: TransformerConfig,
    max_new_tokens: int,
    k: int = 4,
):
    """Per-row acceptance cursors: every row advances by ITS OWN
    accepted prefix each round, instead of the batch minimum.

    The lockstep variant (:func:`make_speculative_generate`) pays for
    its shared scalar cache position by re-proposing — and
    re-verifying — tokens that faster rows already verified (its
    ``reverified`` stat). Here each row carries its own cache cursor,
    built on the continuous batcher's per-slot machinery
    (``slot_programs._slot_forward``: per-row rope gather, vmapped
    contiguous KV writes, per-row causal horizon), so re-verification
    is structurally zero and the round count is governed by each row's
    own acceptance, not the batch's worst.  Still greedy, still
    token-exact per row, still static shapes: a finished row is frozen
    by masking (advance 0), not by changing any shape.

    Dense family only — MoE speculation stays on the lockstep variant
    (its capacity semantics need batch-shaped forwards).
    """
    if k < 1:
        raise ValueError(f"k must be >= 1, got {k}")
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
    if cfg.vocab != draft_cfg.vocab:
        raise ValueError(
            f"draft vocab {draft_cfg.vocab} != target vocab {cfg.vocab}")

    from pbs_tpu.models.slot_programs import _slot_forward, init_slot_cache

    def spec_generate(params: dict, draft_params: dict,
                      prompt: jax.Array):
        B, P = prompt.shape
        W = max_new_tokens + k + 1  # output window incl. overshoot
        max_len = P + W
        tcache = init_slot_cache(cfg, B, max_len)
        dcache = init_slot_cache(draft_cfg, B, max_len)
        zerop = jnp.zeros((B,), jnp.int32)

        tlogits, tcache, _e = _slot_forward(cfg, params, prompt, tcache,
                                            zerop)
        _, dcache, _e = _slot_forward(draft_cfg, draft_params, prompt,
                                      dcache, zerop)
        first = jnp.argmax(tlogits[:, -1, :], axis=-1).astype(jnp.int32)

        out = jnp.zeros((B, W), jnp.int32)
        out = out.at[:, 0].set(first)

        write_rows = jax.vmap(
            lambda row, new, s: jax.lax.dynamic_update_slice(row, new, (s,)))

        def round_body(carry):
            out, n_out, cur, pos, tcache, dcache, rounds, proposed, \
                accepted = carry
            active = n_out < max_new_tokens  # (B,) — frozen rows mask out

            # Draft proposes k tokens per row from its own cursor.
            def dstep(c, _):
                tok, dc, dp = c
                logits, dc, _ = _slot_forward(draft_cfg, draft_params,
                                              tok[:, None], dc, dp)
                nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
                return (nxt, dc, dp + 1), nxt

            (last, dcache, dp), props = jax.lax.scan(
                dstep, (cur, dcache, pos), None, length=k)
            t = props.T  # (B, k)
            # Ingest t_k so the draft holds KV through pos+k whatever
            # the acceptance (logits discarded; overwritten on rollback).
            _, dcache, _e2 = _slot_forward(draft_cfg, draft_params,
                                           last[:, None], dcache, dp)

            # Target verifies k+1 positions per row at its own cursor;
            # per-row accepted prefix — NO batch-min.
            x = jnp.concatenate([cur[:, None], t], axis=1)  # (B, k+1)
            logits, tcache, _e3 = _slot_forward(cfg, params, x, tcache,
                                                pos)
            g = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # (B, k+1)
            round_toks, m_row, bonus = greedy_accept_window(t, g)
            out_new = write_rows(out, round_toks, n_out)
            out = jnp.where(active[:, None], out_new, out)

            # Frozen rows advance nothing: cursor, count, cur all hold.
            adv = jnp.where(active, m_row + 1, 0)
            pos = pos + adv
            cur = jnp.where(active, bonus, cur)
            n_act = jnp.sum(active.astype(jnp.int32))
            return (out, n_out + adv, cur, pos, tcache, dcache,
                    rounds + 1, proposed + k * n_act,
                    accepted + jnp.sum(jnp.where(active, m_row, 0)))

        def cond(carry):
            return jnp.min(carry[1]) < max_new_tokens

        zero = jnp.zeros((), jnp.int32)
        carry = (out, jnp.ones((B,), jnp.int32), first, zerop + P,
                 tcache, dcache, zero, zero, zero)
        out, n_out, _, _, _, _, rounds, proposed, accepted = (
            jax.lax.while_loop(cond, round_body, carry))
        stats = {"rounds": rounds, "proposed": proposed,
                 "accepted": accepted,
                 "reverified": jnp.zeros((), jnp.int32)}
        return out[:, :max_new_tokens], stats

    return spec_generate


def make_speculative_serve_step(
    cfg: TransformerConfig,
    draft_cfg: TransformerConfig,
    max_new_tokens: int,
    k: int = 4,
):
    """A Job-shaped speculative batch-inference loop (the spec-decode
    sibling of ``generate.make_serve_step``): ``state`` is
    (params, draft_params, requests_served); each step serves one
    prompt batch. Step metrics feed the telemetry ledger —
    ``tokens`` (Counter.TOKENS) and ``spec_proposed``
    (Counter.SPEC_PROPOSED), so ``pbst top``-class monitors can read
    the speculation efficiency of a serving tenant exactly like any
    other PMC-style rate. Uses the per-row variant: serving batches
    mix unrelated prompts, exactly where lockstep's batch-min
    re-verification tax is worst."""
    spec = make_per_row_speculative_generate(
        cfg, draft_cfg, max_new_tokens, k)

    def serve_step(state, prompts: jax.Array):
        params, draft_params, served = state
        toks, stats = spec(params, draft_params, prompts)
        ntok = toks.shape[0] * toks.shape[1]
        metrics = {
            "tokens": jnp.asarray(ntok, jnp.int32),
            "spec_proposed": stats["proposed"],
        }
        return (params, draft_params, served + 1), metrics

    return serve_step
