"""Regex-rule parameter partitioning for the serving tier.

The training stack annotates shardings per-leaf in code
(``parallel/sharding.param_specs``); the serving tier instead carries
ONE declarative rule table — ordered ``(path regex, positional spec)``
pairs in the fmengine ``match_partition_rules`` style (SNIPPETS.md §1)
— because a serving deployment swaps checkpoints whose trees it does
not own. Matching walks the param tree with ``/``-joined paths,
scalars are never partitioned, the FIRST matching rule wins, and an
unmatched leaf is a hard error: silently replicating an unmatched
8 GB embedding is exactly the failure mode a rule table exists to
prevent.

Specs are written with POSITIONAL mesh-axis indices (SNIPPETS.md §3):
``-1`` is "the innermost mesh axis" — by repo convention the tensor
axis — so the table never names an axis and library code stays
mesh-agnostic. Only :func:`make_serve_mesh` (this module) and
``pbs_tpu/parallel`` may spell axis NAMES; the ``serve-raw-mesh-axis``
rule of ``pbst check`` (docs/ANALYSIS.md) holds every other module to
that. Resolution against a concrete mesh reuses
``parallel/sharding.quant_aware_shardings``, so int8 ``{"q","s"}``
checkpoint leaves place exactly like their fp twins.

This table is the one place that says where a serving weight lives,
and :func:`place` the one call that puts it there: the engine
(``models/serving.py``) takes its parameters as handed and places
only its own cache.
"""

from __future__ import annotations

import functools
import re
from typing import Any, Callable, Iterable

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from pbs_tpu.parallel.mesh import make_mesh
from pbs_tpu.parallel.sharding import quant_aware_shardings

#: Positional spec entry vocabulary: ``None`` (replicated dim), an
#: ``int`` mesh-axis index, or a tuple of indices (multi-axis dim).
SpecEntry = Any

#: The rule table of every tree the serving path takes. Paths are
#: "/"-joined from the ``init_params`` tree; order matters (first match
#: wins). The layout is the Megatron one ``parallel/sharding.param_specs``
#: derives for training (tests/test_serve.py holds the two to each
#: other leaf by leaf) — vocab-sharded embed/head, column-parallel
#: wq/wk/wv/w1/w3, row-parallel wo/w2, replicated norms — restated
#: positionally: ``-1`` = the innermost (tensor) mesh axis.
PARTITION_RULES: tuple[tuple[str, tuple], ...] = (
    (r"^embed$", (-1, None)),
    (r"(^|/)(attn_norm|mlp_norm|final_norm|o_norm|dt_norm|b_norm|c_norm"
     r"|q_norm|k_norm|kv_norm|ik_norm|g_norm|enorm|hnorm|head_norm)$", ()),
    (r"/w[qkv]$", (None, None, -1)),
    (r"/wo$", (None, -1, None)),
    (r"/w[13]$", (None, None, -1)),
    (r"/w2$", (None, -1, None)),
    (r"^head$", (None, -1)),
    # The stacked MoE tree (models/moe.init_moe_params): experts
    # ``(layer, expert, d, F)`` cut on their d_ff over the same tensor
    # axis, we1/we3 by column and we2 by row (XLA puts the psum at the
    # we2 product); every expert on every chip. Ahead of the planned
    # tree's ``we`` rule below, which is written for ``(expert, d, F)``.
    (r"^layers/we[13]$", (None, None, None, -1)),
    (r"^layers/we2$", (None, None, -1, None)),
    # A planned stack (models/plan.py) holds a layer at a time, under
    # ``blocks/<NN>/attn/`` and ``blocks/<NN>/mlp/``: there a spec
    # above, written for a stack of layers, loses its leading (layer)
    # entry (match_partition_rules). The planned tree's own leaves, at
    # their own rank: the output gate (one column a head, or one a
    # channel) and the router with its selection bias replicated (every
    # holder routes over all experts), the routed experts along the
    # axis they are divided on (the expert axis), the shared expert
    # like one layer's dense MLP.
    (r"/(wg|router|router_bias)$", ()),
    (r"/we[123]$", (-1, None, None)),
    (r"/ws[13]$", (None, -1)),
    (r"/ws2$", (-1, None)),
    # A delta-rule layer (models/plan.KdaKind): what feeds a head's
    # state lies along the head axis the state would be divided on (wq,
    # wk, wv by the rule above, like any column-parallel projection;
    # the convolution's filters and beta, one column a head, here); the
    # decay's and the gate's low-rank pairs, ``a_log`` and ``dt_bias``
    # are a few hundred columns and replicated.
    (r"/(c[qkv]|wb)$", (None, -1)),
    (r"/(wa[12]|wg[12]|a_log|dt_bias)$", ()),
    # A state-space layer (models/plan.MambaKind): what it holds lies
    # along ``d_inner``, the axis its state would be divided on: the
    # in-projection, the filter and the step's projection by column,
    # the two products that contract over it (``w_x``, ``w_out``) by
    # row, the bias and the skip by their one axis. Its ``a_log``
    # (d_state, d_inner) and ``dt_bias`` go by the delta-rule layer's
    # rule above (a rule reads a path, not a rank, and there they are
    # one entry a head or a channel): replicated, 340 KB a layer; the
    # step's, B's and C's small norms by the norms' rule.
    # A matrix-state layer (models/plan.Mamba2Kind) goes by the same
    # paths: ``w_in`` (z | x | B | C | dt side by side), the filter and
    # its bias over x, B and C, ``d_skip`` one a head, ``w_out`` by row;
    # its gated norm's weight ``g_norm`` by the norms' rule. Read on a
    # tensor axis these cut ``w_in``'s columns across its parts, not by
    # heads: a planned stack serves on one device (``place_cache``),
    # and the state's division by heads is not written (ROADMAP R23).
    # A gated convolution (models/plan.ConvKind) has these three paths
    # and no other: ``w_in`` (B | C | u side by side) and the filter by
    # column, ``w_out`` by row, along the channels its tail would be
    # divided on (read on a tensor axis ``w_in``'s cut falls across its
    # three parts, as above: one device).
    (r"/(w_in|conv_w|w_dt)$", (None, -1)),
    (r"/(w_x|w_out)$", (-1, None)),
    (r"/(conv_b|d_skip)$", (-1,)),
    # A latent layer (models/plan.MlaKind): the cache holds nothing a
    # head, so what is divided is the heads' own work: the up-
    # projections out of the two latents (``wq_b``, ``wkv_b``), the
    # indexer's query projection and its head weights by column (a head
    # a column block), ``wo`` by the rule above. The down-projections
    # into the latents, the indexer's one key and its LayerNorm's bias
    # feed every head alike and are replicated (its three norms by the
    # norms' rule).
    (r"/(wq_b|wkv_b|wi_q|wi_w)$", (None, -1)),
    (r"/(wq_a|wkv_a|wi_k|ik_bias)$", ()),
    # A plan's drafting block (``LayerPlan.draft``) lies under
    # ``blocks/mtp/``: its mixer and its MLP by their kinds' paths
    # above; its three norms (``enorm``, ``hnorm``, ``head_norm``) by
    # the norms' rule; the ``(2 d, d)`` projection of the embedding
    # beside the stack's output by column, like any projection into the
    # stream's width.
    (r"/eh_proj$", (None, -1)),
)

#: The canonical param paths the table must cover (the dense
#: ``layers/...`` tree, the stacked MoE one and a planned stack's
#: per-kind one) — the static ``serve-unmatched-rule`` check audits
#: PARTITION_RULES against this literal (dead/shadowed/uncovered
#: detection without importing jax), and tests/test_serve.py pins it
#: against the real ``init_params`` trees so it cannot drift from the
#: models.
TEMPLATE_PATHS: tuple[str, ...] = (
    "embed",
    "layers/attn_norm",
    "layers/wq",
    "layers/wk",
    "layers/wv",
    "layers/wo",
    "layers/mlp_norm",
    "layers/w1",
    "layers/w3",
    "layers/w2",
    "final_norm",
    "head",
    # the stacked MoE tree's own leaves (models/moe.init_moe_params)
    "layers/router",
    "layers/we1",
    "layers/we3",
    "layers/we2",
    # one block of a planned stack's tree (models/plan.plan_shapes);
    # N stands for the layer's number
    "blocks/N/attn/attn_norm",
    "blocks/N/attn/wq",
    "blocks/N/attn/wk",
    "blocks/N/attn/wv",
    "blocks/N/attn/wo",
    "blocks/N/attn/wg",
    "blocks/N/mlp/mlp_norm",
    "blocks/N/mlp/w1",
    "blocks/N/mlp/w3",
    "blocks/N/mlp/w2",
    "blocks/N/mlp/router",
    "blocks/N/mlp/router_bias",
    "blocks/N/mlp/we1",
    "blocks/N/mlp/we3",
    "blocks/N/mlp/we2",
    "blocks/N/mlp/ws1",
    "blocks/N/mlp/ws3",
    "blocks/N/mlp/ws2",
    # a delta-rule layer's own leaves (its attn_norm, wq, wk, wv and wo
    # are the paths above)
    "blocks/N/attn/cq",
    "blocks/N/attn/ck",
    "blocks/N/attn/cv",
    "blocks/N/attn/wa1",
    "blocks/N/attn/wa2",
    "blocks/N/attn/a_log",
    "blocks/N/attn/dt_bias",
    "blocks/N/attn/wb",
    "blocks/N/attn/wg1",
    "blocks/N/attn/wg2",
    "blocks/N/attn/o_norm",
    # a state-space layer's own leaves (its attn_norm, a_log and
    # dt_bias are the paths above)
    "blocks/N/attn/w_in",
    "blocks/N/attn/conv_w",
    "blocks/N/attn/conv_b",
    "blocks/N/attn/w_x",
    "blocks/N/attn/dt_norm",
    "blocks/N/attn/b_norm",
    "blocks/N/attn/c_norm",
    "blocks/N/attn/w_dt",
    "blocks/N/attn/d_skip",
    "blocks/N/attn/w_out",
    # a matrix-state layer's own leaf (the rest are the paths above)
    "blocks/N/attn/g_norm",
    # a gated convolution's leaves are three of the state-space
    # layer's paths (w_in, conv_w, w_out); a softmax layer whose heads
    # are normed has two of its own (q_norm is also a latent layer's)
    "blocks/N/attn/k_norm",
    # a latent layer's own leaves (its attn_norm and wo are the paths
    # above)
    "blocks/N/attn/wq_a",
    "blocks/N/attn/q_norm",
    "blocks/N/attn/wq_b",
    "blocks/N/attn/wkv_a",
    "blocks/N/attn/kv_norm",
    "blocks/N/attn/wkv_b",
    "blocks/N/attn/wi_q",
    "blocks/N/attn/wi_k",
    "blocks/N/attn/ik_norm",
    "blocks/N/attn/ik_bias",
    "blocks/N/attn/wi_w",
    # a drafting block's own leaves (its mixer's and its MLP's are the
    # paths above, under ``blocks/mtp/``)
    "blocks/mtp/enorm",
    "blocks/mtp/hnorm",
    "blocks/mtp/eh_proj",
    "blocks/mtp/head_norm",
)


def _is_quant_leaf(node: Any) -> bool:
    """int8 checkpoint leaf: {"q": int8 weights, "s": scales}
    (models/quant._quantize_leaf) — partitioned as ONE logical leaf."""
    return isinstance(node, dict) and set(node) == {"q", "s"}


def iter_leaf_paths(params: dict, prefix: str = "") -> Iterable[tuple[str, Any]]:
    """(path, leaf) pairs in deterministic key order; quant dicts are
    single logical leaves."""
    for key in sorted(params):
        node = params[key]
        path = f"{prefix}/{key}" if prefix else str(key)
        if isinstance(node, dict) and not _is_quant_leaf(node):
            yield from iter_leaf_paths(node, path)
        else:
            yield path, node


def _leaf_shape(leaf: Any) -> tuple:
    if _is_quant_leaf(leaf):
        return tuple(np.shape(leaf["q"]))
    return tuple(np.shape(leaf))


def match_partition_rules(rules: Iterable[tuple[str, tuple]],
                          params: dict) -> dict:
    """Positional-spec tree for ``params``: scalars (ndim 0 or one
    element) are unpartitioned, the first rule whose regex ``search``es
    the "/"-joined path wins, an unmatched non-scalar leaf raises. A
    planned tree keeps one layer a leaf under ``blocks/``: there a
    spec one entry longer than the leaf was written for a stack of
    layers, and its leading entry falls away. Any other spec that
    names dimensions names as many as its leaf has, or raises here
    with the path: a rule written for a tree of another rank would
    cut the wrong axis. ``()`` is replicated at any rank."""
    rules = tuple(rules)

    def walk(tree: dict, prefix: str) -> dict:
        out = {}
        for key in sorted(tree):
            node = tree[key]
            path = f"{prefix}/{key}" if prefix else str(key)
            if isinstance(node, dict) and not _is_quant_leaf(node):
                out[key] = walk(node, path)
                continue
            shape = _leaf_shape(node)
            if len(shape) == 0 or int(np.prod(shape)) == 1:
                out[key] = ()
                continue
            for pattern, spec in rules:
                if re.search(pattern, path) is not None:
                    spec = tuple(spec)
                    if path.startswith("blocks/") \
                            and len(spec) == len(shape) + 1:
                        spec = spec[1:]
                    if spec and len(spec) != len(shape):
                        raise ValueError(
                            f"partition rule {pattern!r} gives param "
                            f"{path!r} (shape {shape}) the "
                            f"{len(spec)}-entry spec {spec}: written "
                            f"for a leaf of another rank")
                    out[key] = spec
                    break
            else:
                raise ValueError(
                    f"no partition rule matches param {path!r} "
                    f"(shape {shape}); every non-scalar leaf must be "
                    f"covered — extend the rule table, do not rely on "
                    f"silent replication")
        return out

    return walk(params, "")


def audit_rules(rules: Iterable[tuple[str, tuple]],
                paths: Iterable[str] = TEMPLATE_PATHS) -> dict:
    """First-match-wins audit of a rule table against a path universe:
    ``dead`` rules match nothing, ``shadowed`` rules match only paths
    an earlier rule already claimed, ``uncovered`` paths match no rule.
    The runtime twin of the static ``serve-unmatched-rule`` check."""
    rules = tuple(rules)
    paths = tuple(paths)
    claimed: dict[str, int] = {}
    raw_hits: list[set[str]] = [set() for _ in rules]
    for path in paths:
        for i, (pattern, _) in enumerate(rules):
            if re.search(pattern, path) is not None:
                raw_hits[i].add(path)
                if path not in claimed:
                    claimed[path] = i
    dead = [rules[i][0] for i in range(len(rules)) if not raw_hits[i]]
    shadowed = [
        rules[i][0] for i in range(len(rules))
        if raw_hits[i] and all(claimed[p] != i for p in raw_hits[i])
    ]
    uncovered = [p for p in paths if p not in claimed]
    return {"dead": dead, "shadowed": shadowed, "uncovered": uncovered}


def resolve_spec(mesh: Mesh, raw: tuple) -> P:
    """Positional spec -> named :class:`PartitionSpec` for ``mesh``.
    Non-negative indices address ``mesh.axis_names`` directly,
    negatives index Python-style (``-1`` = innermost axis)."""
    names = mesh.axis_names

    def one(entry: SpecEntry):
        if entry is None:
            return None
        if isinstance(entry, tuple):
            return tuple(one(e) for e in entry)
        idx = int(entry)
        try:
            return names[idx]
        except IndexError:
            raise ValueError(
                f"positional spec index {idx} out of range for mesh "
                f"axes {names}") from None

    return P(*(one(e) for e in raw))


def rule_shardings(params: dict, mesh: Mesh) -> dict:
    """NamedSharding tree for ``params``: match rules, resolve the
    positional specs against ``mesh``, and hand placement to the
    quant-aware walk ``parallel/sharding`` already owns."""
    raw = match_partition_rules(PARTITION_RULES, params)

    def named(tree):
        if isinstance(tree, dict):
            return {k: named(v) for k, v in tree.items()}
        return resolve_spec(mesh, tree)

    return quant_aware_shardings(named(raw), params, mesh)


def place(params: dict, mesh: Mesh) -> dict:
    """Serving weights on ``mesh``, each leaf where the rule table
    says: the only code that puts a serving weight on a mesh. A leaf
    that already lies there (a tree made under :func:`rule_shardings`)
    comes back as the array it was."""
    return jax.tree.map(jax.device_put, params,
                        rule_shardings(params, mesh))


def make_shard_and_gather_fns(mesh: Mesh) -> tuple[Callable, Callable]:
    """(shard, gather) tree functions on ``mesh``. ``shard`` is
    :func:`place`; ``gather`` jit-reshards everything to
    fully-replicated (host-readable) form — the checkpoint save path,
    and the roundtrip the byte-identity test pins."""
    gather = jax.jit(lambda tree: tree,
                     out_shardings=NamedSharding(mesh, P()))
    return functools.partial(place, mesh=mesh), gather


def make_serve_mesh(tp: int = 1, dp: int = 1,
                    devices=None) -> Mesh:
    """The serving mesh: (dp, tp) with the tensor axis INNERMOST, so
    positional ``-1`` in the rule table lands on it and tp groups sit
    on neighboring devices. The one place in the serve package that
    spells mesh-axis names (the engine's kv-cache placement contract
    requires a 'tp' axis; docs/SERVING.md).

    With ``devices=None`` the FIRST ``dp*tp`` visible devices are
    taken — a 1x1 serving mesh must construct on a host that exposes
    many devices (the test harness forces 8 CPU devices), not demand
    the whole fleet."""
    if devices is None:
        need = int(dp) * int(tp)
        avail = jax.devices()
        if len(avail) < need:
            raise ValueError(
                f"serve mesh dp={dp} x tp={tp} needs {need} devices, "
                f"have {len(avail)}")
        devices = avail[:need]
    return make_mesh({"dp": int(dp), "tp": int(tp)}, devices=devices)
