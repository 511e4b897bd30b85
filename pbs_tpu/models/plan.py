"""Layer plan: one description of a decoder's layer stack.

A dense decoder is ``n_layers`` copies of one layer, and
``TransformerConfig`` says so with a handful of widths. A model whose
layers differ (full beside sliding-window attention with their own head
counts and rotary settings; a leading dense MLP followed by routed
experts) is described here instead: a few layer *kinds*, and per layer
which attention kind and which MLP kind it is. The serving engine reads
the plan (``models/slot_programs.py``: the cache, the forward over slot rows,
the ingestion of a prompt); nothing branches on a model's name.

A planned model's parameters are held a layer at a time, every weight
a buffer of its own (stacked by kind, XLA:TPU copies a layer's slice out
of the stack before each use: 768 MB a routed-expert weight, every
tick)::

    embed, final_norm[, head]           (no head: the embedding is tied)
    blocks/<NN>/attn/{attn_norm, wq, wk, wv, wo[, wg]
                      [, q_norm, k_norm]}                     softmax
    blocks/<NN>/attn/{attn_norm, wq, wk, wv, cq, ck, cv, wa1, wa2,
                      a_log, dt_bias, wb, wg1, wg2, o_norm, wo}  delta rule
    blocks/<NN>/attn/{attn_norm, w_in, conv_w, conv_b, w_x, dt_norm,
                      b_norm, c_norm, w_dt, dt_bias, a_log, d_skip,
                      w_out}                                  state space
    blocks/<NN>/attn/{attn_norm, wq_a, q_norm, wq_b, wkv_a, kv_norm,
                      wkv_b, wo[, wi_q, wi_k, ik_norm, ik_bias,
                      wi_w]}            latent[, selecting: an indexer]
    blocks/<NN>/attn/{attn_norm, w_in, conv_w, conv_b, dt_bias, a_log,
                      d_skip, g_norm, w_out}          matrix state space
    blocks/<NN>/attn/{attn_norm, w_in, conv_w, w_out}   gated convolution
    blocks/<NN>/mlp/{mlp_norm, w1, w3, w2}                    dense
    blocks/<NN>/mlp/{mlp_norm, router[, router_bias], we1, we3, we2
                     [, ws1, ws3, ws2]}
    blocks/mtp/{enorm, hnorm, eh_proj, head_norm, attn/..., mlp/...}
                                  the drafting block (``LayerPlan.draft``)

A block may be a mixer alone or an MLP alone (its entry of
``LayerPlan.layers`` holds ``None`` for the half it lacks): it then has
the one subtree, with the one norm. An MLP whose ``form`` is ``relu2``
has no gate matrix: no ``w3``, ``we3`` or ``ws3``.

A configuration without a plan is the uniform one its widths describe
(:func:`uniform_plan`), held in the stacked ``layers/...`` tree and run
by one ``lax.scan`` as always.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class Rope:
    """Rotary embedding of one attention kind. ``rotary_dim`` leading
    dims of each head rotate (half-split convention: dim i with dim
    i + rotary_dim / 2; with ``interleave`` adjacent dims ``(2i, 2i +
    1)`` turn together), the rest pass through; ``None`` is the whole
    head. ``factor`` > 1 is YaRN as Hugging Face's
    ``_compute_yarn_parameters`` writes it."""

    theta: float = 10_000.0
    rotary_dim: int | None = None
    factor: float = 1.0
    original_max: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0
    interleave: bool = False


@dataclasses.dataclass(frozen=True)
class AttnKind:
    """Softmax attention over cached keys and values."""

    name: str
    n_heads: int
    window: int | None = None  # None: every earlier position is seen
    rope: Rope | None = Rope()  # None: no rotary at all (NoPE)
    # The sigmoid output gate, at most one of two widths (each a field
    # callers construct with; ``gate`` reads them as one word).
    head_gate: bool = False    # one scalar a query head
    wide_gate: bool = False    # one scalar a channel of every head
    #: queries and keys are RMS-normed a head before the rotary, one
    #: weight of ``head_dim`` shared by every head (``q_norm``,
    #: ``k_norm``)
    qk_norm: bool = False

    def __post_init__(self):
        if self.head_gate and self.wide_gate:
            raise ValueError(f"attention kind {self.name!r} names two "
                             f"output gates")
        if self.rope is not None and self.rope.interleave:
            raise NotImplementedError(
                f"attention kind {self.name!r}: a softmax layer's rotary "
                f"is half-split; adjacent pairs are turned in a latent "
                f"layer alone (models/mla.py)")

    @property
    def gate(self) -> str | None:
        """``per_head`` | ``elementwise`` | None: what the sigmoid
        output gate, read off the layer's normed input, has one of."""
        return "per_head" if self.head_gate else \
            "elementwise" if self.wide_gate else None


@dataclasses.dataclass(frozen=True)
class KdaKind:
    """Gated delta-rule linear attention (Kimi Delta Attention): no
    keys and values are kept; a request's state is one float32
    ``(head_dim, head_dim)`` matrix a head, decayed per key channel and
    corrected by the delta rule each token, and the last ``conv - 1``
    inputs of the short causal convolution q, k and v pass through
    (``models/kda.py``).
    ``rank`` is the width of the two low-rank pairs (decay, output
    gate)."""

    name: str
    n_heads: int
    head_dim: int
    conv: int = 4
    rank: int = 128


@dataclasses.dataclass(frozen=True)
class MambaKind:
    """A selective state-space layer (Mamba-1, as ``JambaMambaMixer``
    writes it: RMSNorm on the step, ``B`` and ``C``): no keys and
    values are kept; a request's state is one float32 ``(d_state,
    d_inner)`` matrix, every entry decayed by a factor of its own each
    token (``exp(dt[c] A[c, n])``: no matrix product moves it), and the
    last ``conv - 1`` inputs of the short causal convolution
    (``models/mamba.py``). The state and ``a_log`` lie ``d_state``
    first, so that the 5120 channels and not the 16 states fill a
    vector register's 128 lanes and a tile of HBM."""

    name: str
    d_inner: int
    d_state: int
    dt_rank: int
    conv: int = 4


@dataclasses.dataclass(frozen=True)
class Mamba2Kind:
    """A state-space layer whose state is a matrix a head under one
    scalar decay (Mamba-2, arXiv:2405.21060, as ``NemotronHMamba2Mixer``
    writes it): no keys and values are kept; a request's state is one
    float32 ``(head_dim, d_state)`` matrix a head, the whole of it
    decayed by ``exp(dt a)`` each token (one scalar a head: a chunk of
    the prompt therefore goes through matrix products), and the last
    ``conv - 1`` inputs of the short causal convolution over x, B and C
    side by side (``models/mamba2.py``). ``B`` and ``C`` are shared by
    the heads of a group (head j reads group ``j // (n_heads //
    n_groups)``). The state lies ``d_state`` last: 128 states fill a
    vector register's lanes."""

    name: str
    n_heads: int
    head_dim: int
    n_groups: int
    d_state: int
    conv: int = 4

    def __post_init__(self):
        if self.n_heads % self.n_groups:
            raise ValueError(
                f"attention kind {self.name!r}: {self.n_heads} heads do "
                f"not divide into {self.n_groups} groups")

    @property
    def d_inner(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def d_conv(self) -> int:
        """Channels the convolution runs over: x, B and C."""
        return self.d_inner + 2 * self.n_groups * self.d_state


@dataclasses.dataclass(frozen=True)
class ConvKind:
    """A gated short convolution and nothing else (LFM2's mixer, as
    ``Lfm2MoeShortConv`` writes it): no keys and values, no state
    matrix, no decay and no step size. The in-projection gives three
    rows of ``channels``, ``B | C | u``; ``g = B * u`` goes through a
    causal depthwise filter of ``conv`` taps and ``C`` gates what comes
    out (``models/shortconv.py``). A request keeps the last ``conv -
    1`` rows of ``g``, whatever its length."""

    name: str
    channels: int
    conv: int = 3


@dataclasses.dataclass(frozen=True)
class MlaKind:
    """Latent attention (multi-head latent attention), with or without
    a learned indexer that selects its positions (DeepSeek sparse
    attention). No keys or values a head are kept: a position is one
    RMS-normed latent row of ``kv_rank`` and one rotary key of
    ``rope_dim`` shared by every head; under an indexer one indexer key
    of ``index_dim`` besides. A query (``n_heads`` of ``nope_dim +
    rope_dim``, from a latent of ``q_rank``) attends every earlier
    position, or under an indexer (``index_heads``, ``index_dim`` and
    ``topk`` all given) the ``topk`` earlier positions whose indexer
    score (``index_heads`` heads, a weight a head, relu) is largest,
    all of them while there are no more than ``topk``; a head's key and
    value (``nope_dim``, ``v_dim``) are read off the latent row
    (``models/mla.py``). ``rope`` turns ``rope_dim`` dims of a query,
    of the shared key and of the indexer's query and key (their leading
    ones). ``mscale`` is YaRN's magnitude correction where it sits in
    the softmax's scale and not on the tables (DeepSeek-V3: ``0.1
    mscale_all_dim ln(factor) + 1``, squared in :attr:`scale`)."""

    name: str
    n_heads: int
    q_rank: int
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    index_heads: int = 0
    index_dim: int = 0
    topk: int = 0
    rope: Rope | None = None
    mscale: float = 1.0

    def __post_init__(self):
        if self.rope is None or self.rope.rotary_dim != self.rope_dim:
            raise ValueError(
                f"attention kind {self.name!r}: its rotary turns "
                f"{self.rope and self.rope.rotary_dim} dims, its rotary "
                f"key has {self.rope_dim}")
        indexer = (self.index_heads, self.index_dim, self.topk)
        if any(indexer) and not all(indexer):
            raise ValueError(
                f"attention kind {self.name!r}: an indexer has heads, a "
                f"width and a topk, all three or none: {indexer}")

    @property
    def selects(self) -> bool:
        """An indexer chooses the positions a query attends."""
        return self.topk > 0

    @property
    def scale(self) -> float:
        """What the softmax's scores are multiplied by."""
        return self.mscale ** 2 / math.sqrt(self.nope_dim + self.rope_dim)

    @property
    def rows(self) -> tuple[str, ...]:
        """The cache entries that hold what a position keeps."""
        return ("ckv", "kr", "ik") if self.selects else ("ckv", "kr")


#: What an MLP computes (``MlpKind.form``).
MLP_FORMS = ("silu", "relu2")


@dataclasses.dataclass(frozen=True)
class MlpKind:
    """``n_experts`` 0 is a dense MLP of width ``d_ff``. Otherwise
    ``d_ff`` is one routed expert's width, the router scores all
    ``n_experts``, and this program holds ``held`` = (first, count) of
    them: what the others would add is some other chip's to compute
    (``parallel/expert.py``). ``form`` is what an MLP of this kind
    (dense, a routed expert, the shared one) computes: ``silu``, the
    gated ``(silu(x W1) * (x W3)) W2``, or ``relu2``, the ungated
    ``relu(x W1)^2 W2``: two matrices, not three."""

    name: str
    d_ff: int
    n_experts: int = 0
    top_k: int = 0
    held: tuple[int, int] = (0, 0)
    shared_d_ff: int = 0       # one always-on expert beside the routed
    routed_scale: float = 1.0
    #: ``softmax`` over the router's logits, or ``sigmoid`` of each with
    #: a learned bias (``router_bias``) that takes part in choosing the
    #: experts and not in weighting them
    scoring: str = "softmax"
    form: str = "silu"
    #: added to the sum the chosen experts' scores are renormalised by
    #: (``lfm2_moe`` divides by ``sum + 1e-6``)
    renorm_eps: float = 0.0
    #: the group limit of a sigmoid router (``noaux_tc``): the experts
    #: are ``n_group`` runs of consecutive ones, a run's score is the
    #: sum of its two largest ``score + bias``, and a token chooses
    #: among the ``topk_group`` best runs alone. 1 and 1: no limit
    n_group: int = 1
    topk_group: int = 1

    def __post_init__(self):
        if self.form not in MLP_FORMS:
            raise ValueError(f"MLP kind {self.name!r}: unknown form "
                             f"{self.form!r}; known: {MLP_FORMS}")
        if self.n_group > 1 and (
                self.scoring != "sigmoid" or self.n_experts % self.n_group
                or not 0 < self.topk_group <= self.n_group
                or self.n_experts // self.n_group < 2):
            raise ValueError(
                f"MLP kind {self.name!r}: a group limit ({self.n_group} "
                f"groups, {self.topk_group} kept) is a sigmoid router's, "
                f"over whole groups of two experts or more")


@dataclasses.dataclass(frozen=True)
class LayerPlan:
    attn: tuple[AttnKind | KdaKind | MambaKind | Mamba2Kind | ConvKind
                | MlaKind, ...]
    mlp: tuple[MlpKind, ...]
    #: per layer (attn i, mlp i); None for the half a block does not
    #: have (a mixer alone, or an MLP alone: one norm, one residual add)
    layers: tuple[tuple[int | None, int | None], ...]
    #: the drafting block (a multi-token-prediction module of depth
    #: one, DeepSeek-V3's): (attn i, mlp i) of one more block of this
    #: plan's kinds, which reads the stack's normed output at a position
    #: beside the embedding of the token after it, through two norms
    #: and one ``(2 d, d)`` projection, and predicts the token after
    #: that through a norm of its own and the stack's head. A plan that
    #: has one drafts for itself (``models/serving.py``); None: no such
    #: block
    draft: tuple[int, int] | None = None

    def __post_init__(self):
        if any(a is None and m is None for a, m in self.layers):
            raise ValueError("a block of a layer plan has a mixer, an "
                             "MLP or both")

    @property
    def draft_kinds(self):
        """The drafting block's mixer kind and MLP kind; None without
        one."""
        return None if self.draft is None else (
            self.attn[self.draft[0]], self.mlp[self.draft[1]])

    @property
    def mixers(self) -> tuple:
        """The kind of every mixer a tick runs: the layers', then the
        drafting block's."""
        return tuple(self.attn[a] for a, _ in self.layers
                     if a is not None) + (
            () if self.draft is None else (self.attn[self.draft[0]],))

    @property
    def routed(self) -> bool:
        return any(m is not None and self.mlp[m].n_experts
                   for _, m in self.layers + (
                       () if self.draft is None else (self.draft,)))

    @property
    def recurrent(self) -> bool:
        """Some layer (delta-rule, state-space or gated convolution)
        keeps a state or a tail that every token is folded into, not
        keys and values a cursor can mask."""
        return any(a is not None and isinstance(
            self.attn[a], (KdaKind, MambaKind, Mamba2Kind, ConvKind))
            for a, _ in self.layers)

    @property
    def select_topk(self) -> int | None:
        """The most positions a selecting layer's query attends; None
        where every layer attends all it keeps."""
        return max((a.topk for a in self.mixers
                    if isinstance(a, MlaKind) and a.selects), default=None)

    @property
    def latent(self) -> bool:
        """Some layer keeps latent rows a position."""
        return any(isinstance(a, MlaKind) for a in self.mixers)

    @property
    def takes_window(self) -> bool:
        """A decode tick can verify a window of more than one position
        a lane: every mixer is latent attention without an indexer,
        whose rows a cursor masks and a later tick writes over (a ring
        takes one position a tick, a recurrent state folds every token
        in, an indexer would have to choose for every query of the
        window; a softmax layer over keys and values could, and is not
        written)."""
        return all(isinstance(a, MlaKind) and not a.selects
                   for a in self.mixers)

    def kinds(self, layer: int) -> tuple[AttnKind | None, MlpKind | None]:
        """The layer's mixer kind and MLP kind, None for a half the
        block does not have."""
        a, m = self.layers[layer]
        return (None if a is None else self.attn[a],
                None if m is None else self.mlp[m])


def block_name(layer: int) -> str:
    return f"{layer:02d}"


#: The drafting block's name: where its leaves lie under ``blocks/``
#: and its rows in the cache's entries.
DRAFT_BLOCK = "mtp"


def uniform_plan(cfg) -> LayerPlan:
    """What a configuration without a plan describes."""
    return LayerPlan(
        attn=(AttnKind("full", cfg.n_heads, None,
                       Rope(theta=cfg.rope_theta)),),
        mlp=(MlpKind("dense", cfg.d_ff),),
        layers=((0, 0),) * cfg.n_layers)


def plan_of(cfg) -> LayerPlan:
    return cfg.layer_plan if cfg.layer_plan is not None \
        else uniform_plan(cfg)


# -- rotary tables ------------------------------------------------------------


def inv_freq(rope: Rope, head_dim: int) -> np.ndarray:
    """Inverse frequencies of the rotating pairs, float64 on the host
    (a table's constants, not a traced value)."""
    dim = rope.rotary_dim or head_dim
    pos_freqs = rope.theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if rope.factor <= 1.0:
        return 1.0 / pos_freqs

    def correction_dim(n_rot: float) -> float:
        return (dim * math.log(rope.original_max / (n_rot * 2 * math.pi))
                / (2 * math.log(rope.theta)))

    low = max(math.floor(correction_dim(rope.beta_fast)), 0)
    high = min(math.ceil(correction_dim(rope.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    extrapolation = 1.0 - ramp  # share of the unscaled frequency
    return ((1.0 / (rope.factor * pos_freqs)) * (1.0 - extrapolation)
            + (1.0 / pos_freqs) * extrapolation)


def rope_table(rope: Rope, head_dim: int,
               seq: int) -> tuple[jax.Array, jax.Array]:
    """cos, sin of shape (seq, rotary_dim / 2), float32, already times
    the kind's ``attention_factor``."""
    freqs = jnp.asarray(inv_freq(rope, head_dim), jnp.float32)
    ang = jnp.outer(jnp.arange(seq, dtype=jnp.float32), freqs)
    scale = jnp.float32(rope.attention_factor)
    return jnp.cos(ang) * scale, jnp.sin(ang) * scale


# -- parameters ---------------------------------------------------------------


def plan_shapes(cfg) -> dict:
    """Shapes of a planned model's parameter tree (see the module
    docstring), as nested dicts of tuples."""
    plan = plan_of(cfg)
    d, hd, nkv = cfg.d_model, cfg.head_dim, cfg.n_kv_heads
    out: dict = {"embed": (cfg.vocab, d), "final_norm": (d,), "blocks": {}}
    if not cfg.tie_embeddings:
        out["head"] = (d, cfg.vocab)
    for layer in range(len(plan.layers)):
        a, m = plan.kinds(layer)
        block = out["blocks"][block_name(layer)] = {}
        if a is not None:
            block["attn"] = _attn_shapes(a, d, hd, nkv)
        if m is not None:
            block["mlp"] = _mlp_shapes(m, d)
    if plan.draft is not None:
        a, m = plan.draft_kinds
        out["blocks"][DRAFT_BLOCK] = {
            "enorm": (d,), "hnorm": (d,), "eh_proj": (2 * d, d),
            "attn": _attn_shapes(a, d, hd, nkv), "mlp": _mlp_shapes(m, d),
            "head_norm": (d,)}
    return out


def _attn_shapes(a, d: int, hd: int, nkv: int) -> dict:
    """A mixer's leaves, by its kind."""
    if isinstance(a, KdaKind):
        w, r = a.n_heads * a.head_dim, a.rank
        return {"attn_norm": (d,), "wq": (d, w), "wk": (d, w),
                "wv": (d, w), "cq": (a.conv, w), "ck": (a.conv, w),
                "cv": (a.conv, w), "wa1": (d, r), "wa2": (r, w),
                "a_log": (a.n_heads,), "dt_bias": (w,),
                "wb": (d, a.n_heads), "wg1": (d, r), "wg2": (r, w),
                "o_norm": (a.head_dim,), "wo": (w, d)}
    if isinstance(a, MambaKind):
        c, n, r = a.d_inner, a.d_state, a.dt_rank
        return {"attn_norm": (d,), "w_in": (d, 2 * c),
                "conv_w": (a.conv, c), "conv_b": (c,),
                "w_x": (c, r + 2 * n), "dt_norm": (r,),
                "b_norm": (n,), "c_norm": (n,), "w_dt": (r, c),
                "dt_bias": (c,), "a_log": (n, c), "d_skip": (c,),
                "w_out": (c, d)}
    if isinstance(a, Mamba2Kind):
        c, H = a.d_inner, a.n_heads
        # w_in's columns: z (c) | x (c) | B | C (groups x d_state
        # each) | dt (H); the convolution runs over x, B and C
        return {"attn_norm": (d,), "w_in": (d, c + a.d_conv + H),
                "conv_w": (a.conv, a.d_conv), "conv_b": (a.d_conv,),
                "dt_bias": (H,), "a_log": (H,), "d_skip": (H,),
                "g_norm": (c,), "w_out": (c, d)}
    if isinstance(a, ConvKind):
        # w_in's columns: B | C | u; no bias anywhere
        c = a.channels
        return {"attn_norm": (d,), "w_in": (d, 3 * c),
                "conv_w": (a.conv, c), "w_out": (c, d)}
    if isinstance(a, MlaKind):
        H, qk = a.n_heads, a.nope_dim + a.rope_dim
        out = {"attn_norm": (d,), "wq_a": (d, a.q_rank),
               "q_norm": (a.q_rank,), "wq_b": (a.q_rank, H * qk),
               "wkv_a": (d, a.kv_rank + a.rope_dim),
               "kv_norm": (a.kv_rank,),
               "wkv_b": (a.kv_rank, H * (a.nope_dim + a.v_dim)),
               "wo": (H * a.v_dim, d)}
        if a.selects:
            out.update({"wi_q": (a.q_rank, a.index_heads * a.index_dim),
                        "wi_k": (d, a.index_dim),
                        "ik_norm": (a.index_dim,),
                        "ik_bias": (a.index_dim,),
                        "wi_w": (d, a.index_heads)})
        return out
    attn = {"attn_norm": (d,), "wq": (d, a.n_heads * hd),
            "wk": (d, nkv * hd), "wv": (d, nkv * hd),
            "wo": (a.n_heads * hd, d)}
    if a.gate:
        attn["wg"] = (d, a.n_heads * (
            hd if a.gate == "elementwise" else 1))
    if a.qk_norm:
        attn["q_norm"], attn["k_norm"] = (hd,), (hd,)
    return attn


def _mlp_shapes(m: MlpKind, d: int) -> dict:
    """An MLP's leaves: a gate matrix (``w3``, ``we3``, ``ws3``) only
    where its form has a gate."""

    def matrices(stem: str, width: int, lead: tuple = ()) -> dict:
        out = {stem + "1": lead + (d, width), stem + "2": lead + (width, d)}
        if m.form == "silu":
            out[stem + "3"] = lead + (d, width)
        return out

    if not m.n_experts:
        return {"mlp_norm": (d,), **matrices("w", m.d_ff)}
    mlp = {"mlp_norm": (d,), "router": (d, m.n_experts),
           **matrices("we", m.d_ff, (m.held[1],))}
    if m.scoring == "sigmoid":
        mlp["router_bias"] = (m.n_experts,)
    if m.shared_d_ff:
        mlp.update(matrices("ws", m.shared_d_ff))
    return mlp


def init_plan_params(cfg, key: jax.Array) -> dict:
    """fp32 parameters of a planned model: normal / sqrt(fan_in), norms
    at one, the embedding scaled by sqrt(d) as ``init_params`` has it.
    A delta-rule layer's ``a_log`` is the log of uniform(1, 16) and its
    ``dt_bias`` the inverse softplus of a step in [0.001, 0.1] (the
    published layer's own start), the router's bias a small normal. A
    state-space layer starts as Mamba does: ``a_log`` the log of 1 ..
    ``d_state`` a channel, the same ``dt_bias``, ``d_skip`` one, the
    convolution's filter uniform in +-1/sqrt(taps) and its bias in
    +-1/2; one whose state is a matrix a head as Mamba-2 does:
    ``a_log`` a head the log of uniform(1, 16), the rest alike; a gated
    convolution's filter alike (it has no bias). A selecting layer's
    ``ik_bias`` (its indexer key's LayerNorm) a tenth of a normal."""
    shapes = plan_shapes(cfg)
    flat, treedef = jax.tree.flatten(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    paths = [p for p, _ in jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))[0]]
    keys = jax.random.split(key, len(flat))
    leaves = []
    for path, shape, k in zip(paths, flat, keys):
        name = str(path[-1].key)
        if name.endswith("norm"):
            leaves.append(jnp.ones(shape, jnp.float32))
            continue
        if name == "a_log" and len(shape) == 2:
            leaves.append(jnp.log(jnp.broadcast_to(jnp.arange(
                1, shape[0] + 1, dtype=jnp.float32)[:, None], shape)))
            continue
        if name == "a_log":
            leaves.append(jnp.log(jax.random.uniform(
                k, shape, jnp.float32, 1.0, 16.0)))
            continue
        if name == "d_skip":
            leaves.append(jnp.ones(shape, jnp.float32))
            continue
        if name in ("conv_w", "conv_b"):
            # a Conv1d's start at its fan-in, the taps: +-1/2 at four
            bound = 1.0 / np.sqrt(shape[0]) if name == "conv_w" else 0.5
            leaves.append(jax.random.uniform(k, shape, jnp.float32,
                                             -bound, bound))
            continue
        if name == "dt_bias":
            dt = jnp.exp(jax.random.uniform(
                k, shape, jnp.float32, np.log(1e-3), np.log(1e-1)))
            leaves.append(dt + jnp.log(-jnp.expm1(-dt)))
            continue
        if name == "router_bias":
            leaves.append(0.005 * jax.random.normal(k, shape, jnp.float32))
            continue
        if name == "ik_bias":
            leaves.append(0.1 * jax.random.normal(k, shape, jnp.float32))
            continue
        w = jax.random.normal(k, shape, jnp.float32) / np.sqrt(shape[-2])
        leaves.append(w * np.sqrt(cfg.d_model) if name == "embed" else w)
    return jax.tree.unflatten(treedef, leaves)
