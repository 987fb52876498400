"""Logical-axis sharding rules for the SWARM-LLM framework.

Every parameter / activation dimension is tagged with a *logical* axis name;
rules map logical names to (tuples of) physical mesh axes.  Specs are built
with divisibility checking: a logical axis only shards over a physical axis
set when the dimension size divides the product of those axes' sizes,
otherwise it falls back down a chain of alternatives (ultimately replicated).

This mirrors the MaxText/Flax `logical_axis_rules` pattern but is pure JAX:
params are plain pytrees and the model definition produces a parallel pytree
of logical-axis tuples (see ``models/*.py: param_axes``).

Public entry points (consumed by training/train.py, launch/dryrun.py and
the mesh-sharded serving engine — see docs/SHARDING.md):

* ``spec_for(shape, logical, mesh, rules) -> PartitionSpec`` — one array.
* ``tree_specs / tree_shardings`` — map ``spec_for`` over parallel
  (shapes, logical-axes) pytrees; ``tree_shardings`` wraps the specs in
  ``NamedSharding`` for jit in/out shardings and ``device_put``.
* ``constrain(x, logical, mesh, rules)`` — ``with_sharding_constraint``
  by logical names; a no-op when ``mesh`` is None, which is how the
  serving/runtime code stays bit-identical off-mesh.
* ``kernel_split(mesh, batch, heads)`` — how a per-(row, head) Pallas
  kernel splits under ``shard_map`` (Mosaic kernels are not partitioned
  automatically).
* ``RULE_SETS``: ``default`` (training FSDP x TP), ``sp`` (sequence-
  parallel prefill), ``serve`` (weights replicated over 'data', pure TP —
  decode never re-gathers FSDP shards).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Mapping, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# ---------------------------------------------------------------------------
# Rule tables
# ---------------------------------------------------------------------------

# Each logical axis maps to a *preference chain*: the first physical-axis
# tuple whose size divides the dimension wins; `None` (replicate) always
# terminates the chain implicitly.
#
# Physical axes: "pod" (cross-pod DCN/ICI), "data" (FSDP/batch), "model" (TP).

MeshAxes = tuple[str, ...]
Chain = tuple[MeshAxes, ...]


def _chain(*alts: Sequence[str] | str | None) -> Chain:
    out = []
    for a in alts:
        if a is None:
            continue
        if isinstance(a, str):
            out.append((a,))
        else:
            out.append(tuple(a))
    return tuple(out)


# Parameter logical axes.
PARAM_RULES: dict[str, Chain] = {
    "layers": _chain(),                          # scan-stacked layer dim: never sharded
    "vocab": _chain("model"),                    # embedding / lm-head vocab dim (TP)
    "embed": _chain(("pod", "data"), "data"),    # d_model dim of params (FSDP)
    "heads": _chain("model"),                    # attention q heads (TP)
    "kv_heads": _chain("model"),                 # attention kv heads (TP when divisible)
    "head_dim": _chain(),                        # per-head dim
    "ffn": _chain("model"),                      # MLP hidden (TP)
    "experts": _chain("model"),                  # MoE experts (EP)
    "expert_ffn": _chain(),                      # per-expert hidden
    "ssm_inner": _chain("model"),                # mamba d_inner / rg-lru width
    "ssm_state": _chain(),                       # SSD state dim
    "conv_width": _chain(),
    "norm": _chain(),
    "bias_ffn": _chain("model"),
    "bias_heads": _chain("model"),
}

# Activation logical axes.
ACT_RULES: dict[str, Chain] = {
    "act_batch": _chain(("pod", "data"), "data"),
    "act_seq": _chain(),                         # sequence (SP variant remaps this)
    "act_embed": _chain(),
    "act_heads": _chain("model"),
    "act_kv_heads": _chain("model"),
    "act_head_dim": _chain(),
    "act_vocab": _chain("model"),                # logits vocab dim
    "act_ffn": _chain("model"),
    "act_experts": _chain("model"),
    "act_expert_cap": _chain(),
    # serving-MoE dispatch tensors (docs/SHARDING.md "capacity buffer" rows):
    # the per-position prefill buffer's group dim is the sequence — pinned
    # unsharded (like act_seq) and the expert dim kept OFF 'model', so the
    # 3-index dispatch scatter stays a per-group scatter instead of SPMD's
    # dense select-update rewrite (see moe.moe_block's sharding note).
    "act_moe_group": _chain(),
    # decode's gathered top-k expert weights: batch('data') x replicated k/Fe
    # — each data shard gathers only its own tokens' k weight rows from the
    # 'model'-sharded resident experts.
    "act_topk": _chain(),
    "act_expert_ffn": _chain(),
    "act_ssm_inner": _chain("model"),
    "act_state": _chain(),
    "act_kv_seq": _chain("model"),               # KV-cache seq: fallback TP
    # dim when kv_heads doesn't divide the model axis (Pope et al.-style
    # sequence-sharded cache; softmax partials all-reduce over 'model')
    # paged cache pool (docs/SHARDING.md "paged pool & block tables"): the
    # block/state-row dim of the per-layer pools shards over 'data' like a
    # batch dim — the allocator hands out contiguous slot-major runs, so a
    # slot's blocks land on few 'data' shards; tables/row-ids ride with
    # act_batch and the block_len dim inside a block stays unsharded.
    "act_pool": _chain(("pod", "data"), "data"),
    # quantized-pool scale sidecar (cache_quant engines): the per-row f32
    # scale leaves (n_blocks, L, K) shard exactly like their pool — block
    # dim over 'data' — so a block and its scales always land on the same
    # shard and the fused-dequant read never crosses devices for a scale.
    "act_pool_scale": _chain(("pod", "data"), "data"),
}

# Dims with lower numbers claim mesh axes first (a KV cache lists seq before
# heads in layout order, but heads should win the 'model' axis when it can).
AXIS_PRIORITY = {
    "act_kv_heads": 0, "act_heads": 0, "heads": 0, "kv_heads": 0,
    "ffn": 0, "experts": 0, "vocab": 0, "act_vocab": 0, "act_ffn": 0,
    "act_experts": 0, "ssm_inner": 0, "act_ssm_inner": 0,
    "act_batch": 0, "act_pool": 0, "act_pool_scale": 0, "embed": 1,
    "act_kv_seq": 2,
}


@dataclasses.dataclass(frozen=True, eq=False)
class ShardingRules:
    """A rule set = param rules + activation rules (both overridable).

    Hashable by rule content so a rule set can ride through ``jax.jit`` as a
    static argument (the serving engine closes its jitted prefill/decode
    over (mesh, rules) this way).
    """

    param_rules: Mapping[str, Chain] = dataclasses.field(
        default_factory=lambda: dict(PARAM_RULES))
    act_rules: Mapping[str, Chain] = dataclasses.field(
        default_factory=lambda: dict(ACT_RULES))

    def _frozen(self) -> tuple:
        return (tuple(sorted(self.param_rules.items())),
                tuple(sorted(self.act_rules.items())))

    def __hash__(self) -> int:
        return hash(self._frozen())

    def __eq__(self, other: object) -> bool:
        return (isinstance(other, ShardingRules)
                and self._frozen() == other._frozen())

    def with_overrides(self, *, params: Mapping[str, Chain] | None = None,
                       acts: Mapping[str, Chain] | None = None) -> "ShardingRules":
        p = dict(self.param_rules)
        a = dict(self.act_rules)
        if params:
            p.update(params)
        if acts:
            a.update(acts)
        return ShardingRules(param_rules=p, act_rules=a)


DEFAULT_RULES = ShardingRules()

# Sequence-parallel variant: long prefill shards seq over the model axis for
# everything outside attention (norms / MLP); attention re-gathers.
SP_RULES = DEFAULT_RULES.with_overrides(acts={"act_seq": _chain("model")})

# Decode-serving variant (weights stay put, activations move — Pope et al.).
# Under DEFAULT_RULES a decode step re-all-gathers the FSDP ('data'-dim)
# weight shards every token (measured: 24 GB/device/step -> 0.49 s
# collective term on command-r decode_32k).  Serving has no optimizer state,
# so bf16 weights are replicated over 'data' (pure TP over 'model'): the
# only per-step collectives are activation-sized all-reduces.  Batch stays
# data-sharded; the KV cache is (batch x seq|heads) 2-D sharded.
SERVE_RULES = DEFAULT_RULES.with_overrides(
    params={"embed": _chain(), "vocab": _chain("model")},
)

RULE_SETS = {"default": DEFAULT_RULES, "sp": SP_RULES, "serve": SERVE_RULES}


# ---------------------------------------------------------------------------
# Spec construction
# ---------------------------------------------------------------------------

def _axis_size(mesh: Mesh, axes: MeshAxes) -> int:
    size = 1
    for a in axes:
        size *= mesh.shape[a]
    return size


def spec_for(shape: Sequence[int], logical: Sequence[str | None],
             mesh: Mesh, rules: Mapping[str, Chain]) -> P:
    """Build a PartitionSpec for `shape` given per-dim logical names.

    Divisibility-aware: each logical axis walks its preference chain and
    takes the first physical-axis tuple (a) whose axes are all present in
    the mesh, (b) not already used by an earlier dim, and (c) whose total
    size divides the dim.
    """
    assert len(shape) == len(logical), (shape, logical)
    used: set[str] = set()
    parts: list[Any] = [None] * len(shape)
    order = sorted(range(len(shape)),
                   key=lambda d: AXIS_PRIORITY.get(logical[d], 1))
    for d in order:
        dim, name = shape[d], logical[d]
        if name is None:
            continue
        chain = rules.get(name, ())
        for axes in chain:
            if any(a not in mesh.shape for a in axes):
                continue
            if any(a in used for a in axes):
                continue
            if dim % _axis_size(mesh, axes) != 0:
                continue
            parts[d] = axes if len(axes) > 1 else axes[0]
            used.update(axes)
            break
    # Trim trailing Nones for cleanliness.
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def tree_specs(shapes: Any, axes_tree: Any, mesh: Mesh,
               rules: Mapping[str, Chain]) -> Any:
    """Map `spec_for` over parallel pytrees of shapes and logical-axis tuples.

    `shapes` leaves are either jax.ShapeDtypeStruct / arrays (have .shape) or
    raw tuples. `axes_tree` leaves are tuples of logical names (or None).
    """
    def one(shape_leaf, ax):
        if shape_leaf is None or ax is None:
            return None
        shape = getattr(shape_leaf, "shape", shape_leaf)
        return spec_for(shape, ax, mesh, rules)

    return jax.tree.map(one, shapes, axes_tree,
                        is_leaf=lambda x: x is None or (
                            isinstance(x, tuple) and all(
                                isinstance(e, (str, type(None))) for e in x)))


def tree_shardings(shapes: Any, axes_tree: Any, mesh: Mesh,
                   rules: ShardingRules | None = None) -> Any:
    rules = rules or DEFAULT_RULES
    specs = tree_specs(shapes, axes_tree, mesh, rules.param_rules)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs)


def constrain(x: jax.Array, logical: Sequence[str | None], mesh: Mesh | None,
              rules: ShardingRules | None = None) -> jax.Array:
    """with_sharding_constraint by logical axes; no-op off-mesh."""
    if mesh is None or mesh.empty:
        return x
    rules = rules or DEFAULT_RULES
    spec = spec_for(x.shape, logical, mesh, rules.act_rules)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def _data_axes(mesh: Mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def data_shards(mesh: Mesh) -> int:
    """Number of batch shards = product of pod/data axis sizes."""
    return _axis_size(mesh, _data_axes(mesh))


def kernel_split(mesh: Mesh, batch: int, heads: int) -> tuple[Any, Any]:
    """(batch entry, head entry) of the ``shard_map`` specs for a kernel
    that is independent per batch row and per (KV) head: rows split over
    the mesh's pod/data axes and heads over 'model', each only where it
    divides — otherwise that dim is replicated and every shard computes
    all of it."""
    daxes = _data_axes(mesh)
    b = None
    if daxes and batch % _axis_size(mesh, daxes) == 0:
        b = daxes if len(daxes) > 1 else daxes[0]
    h = None
    if "model" in mesh.shape and heads % mesh.shape["model"] == 0:
        h = "model"
    return b, h
