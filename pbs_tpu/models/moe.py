"""Mixture-of-Experts decoder: second model family, expert-parallel.

The reference has no ML models (SURVEY.md §0) — as with the dense
flagship, the MoE decoder is a *workload* the framework schedules, and
it exists specifically to exercise the parallelism axes the dense model
does not: expert parallelism (`ep`) with all-to-all token exchange, the
TPU-native seat of SURVEY.md §2e's "parallelism strategies to map".

TPU-first design:

- **Static-shape token-choice routing** (Switch/Mesh-TF lineage): top-k
  gating with a fixed per-expert capacity; dispatch/combine are dense
  one-hot tensors consumed by einsums, so the whole MoE layer is MXU
  matmuls — no gather/scatter, no dynamic shapes, nothing XLA cannot
  tile.
- **Experts as a leading array axis** (L, E, d, f): one compiled layer
  body under ``lax.scan``; sharding the E axis over the ``ep`` mesh axis
  turns the dispatch einsum into an XLA all-to-all (annotation-driven,
  no hand-rolled collectives).
- **Router overflow as contention telemetry**: the fraction of dropped
  tokens is returned in step metrics — the in-graph analog of the
  reference's spin-latency hint (``vcrd_op``, ``sched_credit.c:249-259``):
  a cheap, workload-reported congestion signal the feedback scheduler
  can consume.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from pbs_tpu.models.quant import wload
from pbs_tpu.models.transformer import (
    TransformerConfig,
    apply_rope,
    causal_attention,
    chunked_head_xent,
    default_optimizer,
    rms_norm,
    rope_tables,
    shift_targets_and_weights,
    token_xent,
)
from pbs_tpu.ops.grouped_matmul import grouped_matmul, grouped_matmul_tiles


@dataclasses.dataclass(frozen=True)
class MoEConfig(TransformerConfig):
    n_experts: int = 8
    top_k: int = 2
    # Per-expert slots = capacity_factor * top_k * group_tokens / n_experts.
    capacity_factor: float = 1.25
    aux_loss_weight: float = 1e-2
    # Tokens are routed within fixed-size groups (Mesh-TF style) so the
    # dense (g, E, C) dispatch tensors stay O(g) per group — memory
    # linear in total tokens, not quadratic. Groups that don't divide T
    # fall back to one group (tiny shapes / tests).
    router_group_size: int = 4096
    # Provably dropless routing: capacity = group token count, the
    # exact worst case (under top-k each token occupies at most one
    # slot per expert), so overflow is IMPOSSIBLE for any routing
    # pattern — not merely unlikely under an ample capacity_factor.
    # This is the mode speculative verification and engine/lockstep
    # parity need: token-exact regardless of how adversarially the
    # router concentrates.  Cost: the dispatch tensors become O(g²E)
    # per group and the expert compute is provisioned for E*g slots,
    # so it is a SERVING/VERIFY mode (decode steps route a handful of
    # tokens; prefill buckets are bounded); dropless_group_max guards
    # against accidentally training with it.  In dropless mode the
    # group size has no routing semantics at all — grouping degrades
    # to a pure memory-tiling choice.
    dropless: bool = False
    dropless_group_max: int = 1024

    def num_params(self) -> int:
        d, f, v = self.d_model, self.d_ff, self.vocab
        hd, E = self.head_dim, self.n_experts
        per_layer = (
            d * (self.n_heads * hd)
            + 2 * d * (self.n_kv_heads * hd)
            + (self.n_heads * hd) * d
            + d * E  # router
            + E * 3 * d * f  # we1, we3, we2
            + 2 * d  # norms
        )
        return v * d + self.n_layers * per_layer + d + d * v

    def capacity(self, n_tokens: int) -> int:
        if self.dropless:
            if n_tokens > self.dropless_group_max:
                raise ValueError(
                    f"dropless routing over a {n_tokens}-token group "
                    f"exceeds dropless_group_max="
                    f"{self.dropless_group_max} (dispatch memory is "
                    "O(g²·E)). Shrink router_group_size (grouping is "
                    "semantics-free in dropless mode — moe_mlp "
                    "auto-tiles this way), use capacity routing for "
                    "training/long-prefill scale, or raise the guard "
                    "knowingly"
                )
            return n_tokens
        per = self.capacity_factor * self.top_k * n_tokens / self.n_experts
        return max(1, int(np.ceil(per)))


def init_moe_params(cfg: MoEConfig, key: jax.Array) -> dict:
    """fp32 master params; layers stacked on axis 0, experts on axis 1."""
    k_emb, k_layers, k_head = jax.random.split(key, 3)
    d, f, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    hd, nh, nkv, L = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads, cfg.n_layers

    def dense(key, shape):
        fan_in = shape[-2] if len(shape) > 1 else shape[-1]
        return jax.random.normal(key, shape, jnp.float32) / np.sqrt(fan_in)

    ks = jax.random.split(k_layers, 9)
    layers = {
        "attn_norm": jnp.ones((L, d), jnp.float32),
        "wq": dense(ks[0], (L, d, nh * hd)),
        "wk": dense(ks[1], (L, d, nkv * hd)),
        "wv": dense(ks[2], (L, d, nkv * hd)),
        "wo": dense(ks[3], (L, nh * hd, d)),
        "mlp_norm": jnp.ones((L, d), jnp.float32),
        "router": dense(ks[4], (L, d, E)),
        "we1": dense(ks[5], (L, E, d, f)),  # gate
        "we3": dense(ks[6], (L, E, d, f)),  # up
        "we2": dense(ks[7], (L, E, f, d)),  # down
    }
    return {
        "embed": dense(k_emb, (cfg.vocab, d)) * np.sqrt(d),
        "layers": layers,
        "final_norm": jnp.ones((d,), jnp.float32),
        "head": dense(k_head, (d, cfg.vocab)),
    }


# -- routing ----------------------------------------------------------------


def top_k_dispatch(probs: jax.Array, k: int, capacity: int):
    """Static-shape top-k routing with capacity dropping.

    probs (T, E) fp32 -> dispatch/combine (T, E, C), plus (aux_loss,
    drop_frac). dispatch is 0/1 token->slot assignment; combine carries
    the renormalized gate weight. Tokens overflowing an expert's C slots
    are dropped for that choice (residual connection carries them).
    """
    T, E = probs.shape
    topv, topi = jax.lax.top_k(probs, k)  # (T, k)
    topv = topv / jnp.clip(jnp.sum(topv, axis=-1, keepdims=True), 1e-9)

    dispatch = jnp.zeros((T, E, capacity), probs.dtype)
    combine = jnp.zeros((T, E, capacity), probs.dtype)
    base = jnp.zeros((E,), jnp.int32)  # slots used by earlier choices
    for i in range(k):  # k is tiny and static: unrolled
        onehot = jax.nn.one_hot(topi[:, i], E, dtype=probs.dtype)  # (T, E)
        # Slot index within each expert: running count of earlier tokens
        # making the same choice, offset by slots burned by choice < i.
        pos = jnp.cumsum(onehot, axis=0) - onehot + base[None, :].astype(
            probs.dtype
        )
        pos_t = jnp.sum(pos * onehot, axis=1)  # (T,)
        keep = (pos_t < capacity).astype(probs.dtype)
        slot = jax.nn.one_hot(
            jnp.clip(pos_t.astype(jnp.int32), 0, capacity - 1),
            capacity,
            dtype=probs.dtype,
        )
        d_i = onehot[:, :, None] * slot[:, None, :] * keep[:, None, None]
        dispatch = dispatch + d_i
        combine = combine + topv[:, i][:, None, None] * d_i
        base = base + jnp.sum(
            onehot * keep[:, None], axis=0
        ).astype(jnp.int32)

    # Switch-style load-balance aux loss on the top-1 assignment:
    # E * mean_e(frac_tokens_e * mean_prob_e).
    top1 = jax.nn.one_hot(topi[:, 0], E, dtype=probs.dtype)
    frac = jnp.mean(top1, axis=0)
    mean_p = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(frac * mean_p)
    drop_frac = 1.0 - jnp.sum(dispatch) / (T * k)
    return dispatch, combine, aux, drop_frac


def routing_groups(cfg: MoEConfig, T: int) -> tuple[int, int, int]:
    """(group size g, group count G, capacity Cg) for T tokens — the
    one place the grouping/auto-tiling rules live."""
    g = cfg.router_group_size
    if cfg.dropless:
        # Grouping carries no routing semantics in dropless mode (every
        # token keeps every choice regardless of neighbors), so pick
        # the tiling HERE: the largest divisor of T within both the
        # configured group size and the memory guard. This keeps
        # MoEConfig(dropless=True) working at any T — including
        # non-multiples of router_group_size — without tripping the
        # O(g²·E) guard on the single-group fallback.
        bound = min(g if g > 0 else T, cfg.dropless_group_max, T)
        g = next(d_ for d_ in range(bound, 0, -1) if T % d_ == 0)
        # The divisor search is CORRECT at any T but degenerates for
        # token counts with no usable divisor (e.g. prime T > bound:
        # g collapses to 1 → T single-token routing groups, a severe
        # dispatch/vmap cliff). That tiling must never be silent: the
        # caller should pad/reshape its token count to something
        # composite (batch*seq is normally a power of two; odd T only
        # arises from unusual slicing).
        if g * 4 < bound:
            import warnings

            warnings.warn(
                f"dropless auto-tiling picked group size {g} for "
                f"T={T} tokens (bound {bound}): T has no divisor near "
                "the configured group size, so routing will run "
                f"{T // g} tiny groups — a large dispatch overhead. "
                "Pad the token count to a composite size (e.g. a "
                "multiple of router_group_size).",
                stacklevel=2,
            )
    elif g <= 0 or T % g != 0:
        g = T  # single group (tiny shapes / tests)
    return g, T // g, cfg.capacity(g)


def routed_expert_ffn(xg: jax.Array, dispatch: jax.Array,
                      combine: jax.Array, lp: dict, dt,
                      constrain_ec=lambda a: a) -> jax.Array:
    """Dense-dispatch expert SwiGLU on an EXPERT SLICE: xg (G, g, d),
    dispatch/combine (G, g, Ne, Cg) with Ne the experts whose weights
    ``lp`` holds — the full set in the single-program path, the local
    shard inside an ep ``shard_map`` (where the caller psums the
    returned partial combine over ``ep``)."""
    G, g, Ne, Cg = dispatch.shape
    d = xg.shape[-1]
    ein = jnp.einsum("gtec,gtd->egcd", dispatch.astype(dt), xg)
    ein = constrain_ec(ein.reshape(Ne, G * Cg, d))
    gate = jax.nn.silu(jnp.einsum("ecd,edf->ecf", ein,
                                  wload(lp["we1"], dt)))
    up = jnp.einsum("ecd,edf->ecf", ein, wload(lp["we3"], dt))
    eout = jnp.einsum("ecf,efd->ecd", constrain_ec(gate * up),
                      wload(lp["we2"], dt))
    eout = constrain_ec(eout).reshape(Ne, G, Cg, d)
    return jnp.einsum("gtec,egcd->gtd", combine.astype(dt), eout)


def moe_mlp(cfg: MoEConfig, x: jax.Array, lp: dict, constrain_ec):
    """Routed SwiGLU experts. x (B, S, d) -> (y, aux, drop_frac).

    Routing happens independently within fixed-size token groups, so the
    dense dispatch/combine tensors are (G, g, E, Cg) with Cg ∝ g/E —
    total memory O(T·g·k·cf), linear in T. The expert buffers flatten
    group slots into (E, G·Cg, d); ``constrain_ec`` pins them to the
    ``ep`` mesh axis, where the dispatch einsum (token-sharded in,
    expert-sharded out) becomes the all-to-all.
    """
    B, S, d = x.shape
    dt = cfg.dtype
    g, G, Cg = routing_groups(cfg, B * S)
    xg = x.reshape(G, g, d)

    logits = xg.astype(jnp.float32) @ lp["router"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)  # (G, g, E)
    dispatch, combine, aux, drop = jax.vmap(
        lambda p: top_k_dispatch(p, cfg.top_k, Cg)
    )(probs)

    y = routed_expert_ffn(xg, dispatch, combine, lp, dt, constrain_ec)
    return y.reshape(B, S, d), jnp.mean(aux), jnp.mean(drop)


# -- grouped experts: the share of a layer that this program holds -----------


def route_top_k(h: jax.Array, router: jax.Array, kind, bias=None):
    """Token-choice routing over ALL of a layer's experts: h (T, d) ->
    (weights (T, k) fp32, expert ids (T, k)). Scores are float32, by
    ``kind.scoring``: a ``softmax`` over the router's logits, or the
    ``sigmoid`` of each, where ``bias`` (one an expert) is added for
    the choice of the k largest and left out of their weights; under a
    group limit (``kind.n_group`` > 1: the experts are that many runs
    of consecutive ones) a run's score is the sum of its two largest
    ``score + bias``, the ``kind.topk_group`` best runs are kept and
    the k are chosen among their experts alone (float32, as the scores
    are; ties go to the lower number, a run's as an expert's). The
    chosen scores are renormalised to sum to one (the sum plus
    ``kind.renorm_eps`` where the kind has one) and scaled by
    ``kind.routed_scale``. No capacity: every token keeps every choice.
    ``kind`` is a ``models.plan.MlpKind``."""
    logits = h.astype(jnp.float32) @ router.astype(jnp.float32)
    if kind.scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        biased = scores + bias.astype(jnp.float32)
        if kind.n_group > 1:
            runs = biased.reshape(biased.shape[:-1] + (kind.n_group, -1))
            best = jnp.sum(jax.lax.top_k(runs, 2)[0], axis=-1)
            _, kept = jax.lax.top_k(best, kind.topk_group)
            keep = jnp.any(kept[..., None] == jnp.arange(kind.n_group),
                           axis=-2)
            biased = jnp.where(keep[..., None], runs,
                               -jnp.inf).reshape(biased.shape)
        _, topi = jax.lax.top_k(biased, kind.top_k)
        topv = jnp.take_along_axis(scores, topi, axis=-1)
    elif kind.scoring == "softmax":
        topv, topi = jax.lax.top_k(jax.nn.softmax(logits, axis=-1),
                                   kind.top_k)
    else:
        raise ValueError(f"unknown router scoring {kind.scoring!r}")
    total = jnp.sum(topv, axis=-1, keepdims=True)
    if kind.renorm_eps:
        total = total + kind.renorm_eps
    return topv / total * kind.routed_scale, topi


#: Rows of a prompt the held experts take at a time: the sorted buffer
#: has a row of ``d`` for every assignment (``top_k`` a token, held or
#: not), three times over, so a prompt of more rows goes through in
#: pieces of this many (8192 rows of 6144 at 8 a token: 2.4 GB whole).
EXPERT_ROWS = 2048


def held_expert_ffn(h: jax.Array, lp: dict, kind, valid: jax.Array, dt):
    """The routed part of an expert layer, as one holder of ``kind.held``
    = (first, count) of its experts computes it: route over all
    ``kind.n_experts``, keep the assignments that land on a held
    expert, and form each row's products with the experts it chose
    (three matrices an expert of the gated form, two of the ungated,
    :func:`mlp_ffn`) in one of three forms of the same sum, float32
    accumulated, chosen by the static shape (:func:`expert_form`):

    - ``every`` (:func:`_every_expert`): rows that make ``DENSE_PAIRS``
      (row, held expert) pairs or fewer (a decode tick's lanes) skip
      the sort and go through every held expert under a weight that is
      zero where a row did not choose it;
    - the assignments sorted by expert and one grouped product a matrix
      over the sorted buffer, rows of expert e against ``we*[e]``
      (:func:`_sorted_rows`), which is **the Pallas kernel**
      ``ops/grouped_matmul.py`` where the program is lowered for a TPU
      and :func:`grouped_kernel_takes` the shape (every product its
      tiling fits: a prompt forward's and a tick's alike, however few
      rows a held expert has), and
    - ``jax.lax.ragged_dot`` for the shapes the tiling refuses and on
      every other platform (the CPU tests, the reference's side).

    No (T, E, C) tensor exists and no token is dropped, however the
    router concentrates: the sorted buffer has a row for every
    assignment. What an absent expert would add is left out; with every
    share's result summed (``parallel/expert.py``) the layer is whole.

    h (T, d), ``valid`` (T,) marks rows that are tokens (idle decode
    lanes and bucket padding route nowhere and touch no expert). More
    than ``EXPERT_ROWS`` rows (whole multiples of it) go through that
    many at a time.
    Returns ``(y (T, d), counts)`` with ``counts`` int32
    [assignments to held experts, to absent ones, held experts touched,
    largest load of one held expert]."""
    T, d = h.shape
    piece = expert_piece(T)
    if piece < T:
        y, held, absent, sizes = jax.lax.map(
            lambda piece: _held_rows(*piece, lp, kind, dt),
            (h.reshape(-1, piece, d), valid.reshape(-1, piece)))
        y, held, absent, sizes = (y.reshape(T, d), held.sum(), absent.sum(),
                                  sizes.sum(0))
    else:
        y, held, absent, sizes = _held_rows(h, valid, lp, kind, dt)
    counts = jnp.stack([held, absent, jnp.sum(sizes > 0),
                        jnp.max(sizes)]).astype(jnp.int32)
    return y, counts


def expert_piece(rows: int) -> int:
    """Rows of ``rows`` the held experts take at a time: all of them,
    or ``EXPERT_ROWS`` where they are more and whole multiples of
    it."""
    return EXPERT_ROWS if rows > EXPERT_ROWS and rows % EXPERT_ROWS == 0 \
        else rows


def _held_rows(h: jax.Array, valid: jax.Array, lp: dict, kind, dt):
    """:func:`held_expert_ffn` on rows that go through at once: ``(y,
    assignments to held experts, to absent ones, rows each held expert
    got (count,))``. The products take the form :func:`expert_form`
    names: a static choice, by the shape."""
    first, n = kind.held
    with jax.named_scope("moe.route"):
        w, idx = route_top_k(h, lp["router"], kind, lp.get("router_bias"))
        local = idx - first
        ours = (local >= 0) & (local < n)
        here = ours & valid[:, None]
    form, _ = expert_form(*h.shape, kind, dt)
    experts = _every_expert if form == "every" else _sorted_rows
    y, sizes = experts(h, w, local, here, lp, kind, dt)
    return (y.astype(dt), jnp.sum(here), jnp.sum(valid[:, None] & ~ours),
            sizes)


def expert_form(rows: int, d: int, kind, dt) -> tuple[str, int]:
    """Which of the three forms the held experts' products take for
    ``rows`` rows of ``d`` at a time, and the rows one product is over:
    ``every`` (:func:`_every_expert`, the rows themselves) at or under
    ``DENSE_PAIRS`` (row, held expert) pairs; else the sorted buffer's
    ``rows x top_k`` through ``grouped-kernel`` (the Pallas kernel
    where the program is lowered for a TPU, ``ragged_dot`` anywhere
    else) where :func:`grouped_kernel_takes` the shape, or through
    ``ragged_dot`` on every platform where the kernel's tiling does
    not. A function of the static shape alone; the engine writes it
    into the host ring when it builds a program
    (``models/slot_programs.py::_plan_forward``)."""
    n = kind.held[1]
    if rows * n <= DENSE_PAIRS:
        return "every", rows
    m = rows * kind.top_k
    return ("grouped-kernel" if grouped_kernel_takes(
        m, n, d, kind.d_ff, dt) else "ragged_dot"), m


#: At or under this many (row, held expert) pairs (a decode tick's
#: lanes times a modest share of experts) every row goes through every
#: held expert, weighted by zero where it did not choose it: the held
#: weights are then read once each, in whole matrix products, and a
#: row's product with them hides under that read while the rows are
#: fewer than the chip's operations a byte (~240 on a v5e). A grouped
#: product over sorted rows reads only the experts touched. XLA:TPU's
#: own (``ragged-dot-*``) pays for that by the group and by the widths,
#: not by the bytes: 0.011 ms a touched group of 6 MiB at laguna's
#: widths, 0.04-0.05 of 10 MiB at solar's, 0.15 of 9.5 MiB at
#: nemotron's, where neither width is a multiple of 256 and ``we1`` is
#: copied whole first (PERF.md section 6, PR 44: 211-546 GB/s of the
#: chip's 819). The Pallas kernel (``ops/grouped_matmul.py``) streams
#: the touched experts at 520-730 GB/s whatever the widths, which at a
#: tick's load (nine experts of ten touched) is the read of every held
#: expert again: for these few pairs the batched products stay, and
#: the sort, the gathers and the scatter back are saved. The bound is
#: the activations' and the unchosen products' size, ``pairs x
#: width``; PERF.md section 6 (PR 43) says which cells it takes in and
#: why no wider.
DENSE_PAIRS = 4096


def _every_expert(h, w, local, here, lp: dict, kind, dt):
    """The held experts' part for few rows, without a sort: h (T, d)
    through every held expert, (n, T, f), and out again under each
    row's weight on each expert (0 where the row did not choose it),
    one product over experts and width together. Returns (y (T, d),
    rows each held expert got (n,))."""
    n = kind.held[1]
    with jax.named_scope("moe.route"):
        chosen = here[:, :, None] & (local[:, :, None] == jnp.arange(n))
        gate = jnp.sum(jnp.where(chosen, w[:, :, None], 0.0), axis=1)
        sizes = jnp.sum(chosen, axis=(0, 1), dtype=jnp.int32)
    with jax.named_scope("moe.experts"):
        def every(rows, wt):
            if rows.ndim == 2:
                return jnp.einsum("etd,edf->etf",
                                  jnp.broadcast_to(rows, (n,) + rows.shape),
                                  wload(wt, dt))
            return jnp.einsum("etf,efd->td",
                              rows * gate.T[:, :, None].astype(dt),
                              wload(wt, dt))

        y = mlp_ffn(h, lp["we1"], lp.get("we3"), lp["we2"], kind.form,
                    every)
    return y, sizes


def _sorted_rows(h, w, local, here, lp: dict, kind, dt):
    """The held experts' part for many rows: the assignments sorted by
    expert, one grouped product a weight over the sorted buffer.
    Returns (y (T, d), rows each held expert got (n,))."""
    T, d = h.shape
    k = kind.top_k
    n = kind.held[1]
    with jax.named_scope("moe.route"):
        # Held assignments sort to their expert's run; the rest behind.
        key = jnp.where(here, local, n).reshape(-1)
        order = jnp.argsort(key)
        back = jnp.zeros_like(order).at[order].set(
            jnp.arange(T * k, dtype=order.dtype))
        sizes = jnp.zeros((n + 1,), jnp.int32).at[key].add(1)[:n]
        xs = h[order // k]
    with jax.named_scope("moe.experts"):
        def grouped(rows, wt):
            return _grouped_product(rows, wload(wt, dt), sizes)

        out = mlp_ffn(xs, lp["we1"], lp.get("we3"), lp["we2"], kind.form,
                      grouped)
        # Back to (token, choice) order; rows behind the last run hold
        # whatever the grouped product left there and are never read.
        out = out[back].reshape(T, k, d)
        y = jnp.sum(jnp.where(here[:, :, None],
                              out * w[:, :, None].astype(dt), 0), axis=1)
    return y, sizes


# One trace and one lowered function a shape, whatever the layers
# (``models/mamba.py::_kernel_scan`` says why).
_kernel_product = jax.jit(grouped_matmul)


def grouped_kernel_takes(m: int, groups: int, k: int, n: int, dtype) -> bool:
    """Whether the grouped product of ``m`` sorted rows with ``groups``
    matrices of ``(k, n)`` is the Pallas kernel's where the program is
    lowered for a TPU: a function of the static shape alone, and the
    kernel's tiling is the whole of it. No floor of rows a group:
    measured alone beside ``jax.lax.ragged_dot`` at the eight shapes
    the cells meet (PERF.md section 6, PR 44) the kernel is ahead 2.1-7.7
    times where a held expert has 40 sorted rows or more and 1.3 times
    at laguna's tick of 64 lanes (5 sorted rows an expert, both forms
    bound by the weights' read), least where a group has fewest rows.
    Below that it holds too: a visit costs its weight block's read,
    which few rows' arithmetic hides under, so the kernel is a stream
    of the touched experts where ``ragged-dot-*`` adds a fixed cost a
    group (PERF.md section 6, PR 49: the tick in its program)."""
    return grouped_matmul_tiles(m, groups, k, n, dtype)


def _grouped_product(rows, w, sizes):
    """Rows sorted by group against each group's matrix, by the
    platform the program is lowered for: on a TPU the one-pass kernel
    (``ops/grouped_matmul.py``) where :func:`grouped_kernel_takes` the
    shape; anywhere else, and for any other shape,
    ``jax.lax.ragged_dot``. The same sum in float32 accumulators either
    way."""
    if not grouped_kernel_takes(rows.shape[0], *w.shape, rows.dtype):
        return jax.lax.ragged_dot(rows, w, sizes)
    return jax.lax.platform_dependent(
        rows, w, sizes, tpu=_kernel_product, default=jax.lax.ragged_dot)


def mlp_ffn(h: jax.Array, w1, w3, w2, form: str, product) -> jax.Array:
    """One MLP of ``form`` (``models.plan.MlpKind.form``) on its rows:
    ``silu``, the gated ``(silu(h W1) * (h W3)) W2``, or ``relu2``, the
    ungated ``relu(h W1)^2 W2``, which has no ``W3``. ``product(rows,
    w)`` is the matrix product the caller's weights take (plain, or
    grouped by expert)."""
    if form == "silu":
        return product(jax.nn.silu(product(h, w1)) * product(h, w3), w2)
    if form == "relu2":
        return product(jnp.square(jax.nn.relu(product(h, w1))), w2)
    raise ValueError(f"unknown MLP form {form!r}")


def shared_expert_ffn(h: jax.Array, lp: dict, dt,
                      form: str = "silu") -> jax.Array:
    """The always-on expert every holder computes alike (added
    unweighted), of its layer's ``form``."""
    with jax.named_scope("moe.shared"):
        return mlp_ffn(h, lp["ws1"], lp.get("ws3"), lp["ws2"], form,
                       lambda rows, w: rows @ wload(w, dt))


def moe_layer_body(cfg: MoEConfig, x: jax.Array, lp: dict, cos, sin,
                   constrain, constrain_ec, mesh=None, mlp=None,
                   attn=None):
    """One MoE block. ``mlp`` (default: the full-E :func:`moe_mlp`)
    is the routed-FFN seam — ``(h, lp) -> (y, aux, drop)`` — so
    manual-collective callers (the pp x ep pipeline) swap in their
    expert-sharded variant without duplicating the attention half.
    ``attn`` is the attention seam (``(q, k, v) -> out``) mirroring
    :func:`~pbs_tpu.models.transformer.layer_body`: manual-region
    callers pass the ring/ulysses per-device bodies (their public
    wrappers open their own shard_map, which cannot nest)."""
    B, S, _ = x.shape
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dt = cfg.dtype

    h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    q = (h @ lp["wq"].astype(dt)).reshape(B, S, nh, hd)
    k = (h @ lp["wk"].astype(dt)).reshape(B, S, nkv, hd)
    v = (h @ lp["wv"].astype(dt)).reshape(B, S, nkv, hd)
    q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
    # mesh threads the sequence-parallel impls (ring/ulysses) through,
    # exactly like the dense flagship: long-context MoE is dp x ep x sp.
    if attn is None:
        a = causal_attention(q, k, v, cfg, mesh)
    else:
        a = attn(q, k, v)
    x = constrain(x + a.reshape(B, S, nh * hd) @ lp["wo"].astype(dt))

    h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    if mlp is None:
        y, aux, drop = moe_mlp(cfg, h, lp, constrain_ec)
    else:
        y, aux, drop = mlp(h, lp)
    x = constrain(x + y)
    return x, aux, drop


def moe_forward_hidden(cfg: MoEConfig, params: dict, tokens: jax.Array,
                       constrain=lambda x: x, constrain_ec=lambda x: x,
                       mesh=None):
    """tokens (B, S) -> (final normed hidden (B, S, d), aux, drop)."""
    B, S = tokens.shape
    dt = cfg.dtype
    x = constrain(params["embed"].astype(dt)[tokens])
    cos, sin = rope_tables(cfg, S)

    def body(x, lp, cos, sin):
        return moe_layer_body(cfg, x, lp, cos, sin, constrain,
                              constrain_ec, mesh)

    if cfg.remat:
        body = jax.checkpoint(body)

    def scan_fn(carry, lp):
        x, aux, drop = carry
        x, a, d = body(x, lp, cos, sin)
        return (x, aux + a, drop + d), None

    zero = jnp.zeros((), jnp.float32)
    (x, aux, drop), _ = jax.lax.scan(
        scan_fn, (x, zero, zero), params["layers"]
    )
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, aux / cfg.n_layers, drop / cfg.n_layers


def moe_forward(cfg: MoEConfig, params: dict, tokens: jax.Array,
                constrain=lambda x: x, constrain_ec=lambda x: x,
                mesh=None):
    """tokens (B, S) -> (logits (B, S, V) fp32, aux_loss, drop_frac)."""
    x, aux, drop = moe_forward_hidden(cfg, params, tokens, constrain,
                                      constrain_ec, mesh)
    logits = (x @ params["head"].astype(cfg.dtype)).astype(jnp.float32)
    return logits, aux, drop


# -- serving (KV-cached autoregressive decode) ------------------------------


def moe_forward_with_cache(cfg: MoEConfig, params: dict,
                           tokens: jax.Array, cache: dict,
                           constrain=lambda x: x,
                           constrain_ec=lambda x: x):
    """The MoE twin of ``generate.forward_with_cache``: attention runs
    against the KV slabs (same cache layout — MoE changes the FFN, not
    attention), the FFN routes per position (no cross-token state, so
    S=1 decode routes exactly like training did). Returns
    (logits (B, S, V) fp32, updated cache, mean drop_frac) — the drop
    fraction stays observable in serving, where a capacity-starved
    router silently degrades quality."""
    from pbs_tpu.models.generate import _forward_with_cache_impl

    logits, new_cache, drop_sum = _forward_with_cache_impl(
        cfg, params, tokens, cache, constrain,
        mlp_fn=moe_slot_mlp(cfg, constrain_ec))
    return logits, new_cache, drop_sum / cfg.n_layers


def make_moe_generate(cfg: MoEConfig, max_new_tokens: int,
                      temperature: float = 0.0,
                      constrain=lambda x: x,
                      constrain_ec=lambda x: x):
    """MoE twin of ``generate.make_generate`` (same shared decode
    loop); ``generate(params, prompt, key) ->
    ((B, max_new_tokens) tokens, token-weighted mean drop_frac)``."""
    from pbs_tpu.models.generate import make_generate_loop

    def fwd(params, tokens, cache):
        return moe_forward_with_cache(cfg, params, tokens, cache,
                                      constrain, constrain_ec)

    loop = make_generate_loop(cfg, max_new_tokens, temperature, fwd)

    def generate(params: dict, prompt: jax.Array, key: jax.Array):
        toks, drop0, dsum, P = loop(params, prompt, key)
        # TOKEN-weighted drop: the prefill routed P tokens per forward,
        # each decode step 1 — an unweighted per-forward mean would let
        # a capacity-starved long-prompt prefill hide behind clean
        # decode steps (review finding).
        total_tokens = P + max(0, max_new_tokens - 1)
        return toks, (drop0 * P + dsum) / total_tokens

    return generate


def moe_slot_mlp(cfg: MoEConfig, constrain_ec=lambda x: x):
    """The MoE FFN block in the serving ``mlp_fn`` contract —
    ``(lp, h) -> (y, drop_frac)`` — shared by the lockstep cache path
    (``moe_forward_with_cache``) and the continuous-batching engines
    (``ContinuousBatcher(..., mlp_fn=moe_slot_mlp(cfg))``, where the
    drop fraction surfaces as ``stats()['mlp_extra_mean']``). For
    engine/lockstep routing parity use ``MoEConfig(dropless=True)``
    (capacity = group tokens: overflow structurally impossible, the
    canonical mode for serving and speculative verification); under
    capacity routing a nonzero drop telemetry means co-resident lanes
    are competing for expert slots."""
    def mlp(lp, h):
        y, _aux, drop = moe_mlp(cfg, h, lp, constrain_ec)
        return y, drop

    return mlp


def moe_loss(cfg: MoEConfig, params: dict, tokens: jax.Array,
             constrain=lambda x: x, constrain_ec=lambda x: x,
             mesh=None, full_seq: bool = False):
    """``full_seq`` mirrors transformer.next_token_loss: forward over
    all S tokens and drop the last logit, keeping the in-graph
    sequence length divisible by an sp axis (and the routing groups
    identical between the sharded and reference runs).

    ``cfg.loss_chunks > 1`` uses the chunked loss tail shared with the
    dense family (``transformer.chunked_head_xent``): the (B, S, V)
    logits never materialize — at MoE scale the vocab head is the same
    memory hog it is dense."""
    if cfg.loss_chunks > 1:
        x, aux, drop = moe_forward_hidden(
            cfg, params, tokens, constrain, constrain_ec, mesh
        )
        targets, weights = shift_targets_and_weights(tokens)
        lm = chunked_head_xent(cfg, x, params["head"], targets, weights,
                               cfg.loss_chunks)
        return lm + cfg.aux_loss_weight * aux, (lm, aux, drop)
    if full_seq:
        logits, aux, drop = moe_forward(
            cfg, params, tokens, constrain, constrain_ec, mesh
        )
        lm = token_xent(logits[:, :-1], tokens[:, 1:])
    else:
        logits, aux, drop = moe_forward(
            cfg, params, tokens[:, :-1], constrain, constrain_ec, mesh
        )
        lm = token_xent(logits, tokens[:, 1:])
    return lm + cfg.aux_loss_weight * aux, (lm, aux, drop)


def make_moe_train_step(cfg: MoEConfig, learning_rate: float = 3e-4,
                        constrain=lambda x: x, constrain_ec=lambda x: x,
                        mesh=None, full_seq: bool = False):
    """Returns (init_opt_state, train_step); metrics include the router
    drop fraction — the batched in-graph contention hint (vcrd_op
    analog) the feedback policy consumes."""
    import optax

    tx = default_optimizer(learning_rate)

    def init_opt_state(params):
        return tx.init(params)

    def train_step(state, tokens):
        params, opt_state, step = state
        (loss, (lm, aux, drop)), grads = jax.value_and_grad(
            lambda p: moe_loss(cfg, p, tokens, constrain, constrain_ec,
                               mesh, full_seq),
            has_aux=True,
        )(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        ntok = tokens.shape[0] * (tokens.shape[1] - 1)
        metrics = {
            "loss": lm,
            "aux_loss": aux,
            "moe_drop_frac": drop,
            "tokens": jnp.asarray(ntok, jnp.int32),
        }
        return (params, opt_state, step + 1), metrics

    return init_opt_state, train_step
