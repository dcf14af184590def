"""Logical-axis sharding substrate: rules table, mesh context, constraints.

Model code never names mesh axes directly. It speaks in *logical* axes
("batch", "seq_act", "tp", "expert", ...) and this module maps them onto
the active mesh through a ``ShardingRules`` table (the MaxText/Pax
logical-axis-rules design):

    rules = default_rules()
    with sharding_ctx(mesh, rules):
        x = constrain(x, "batch", "seq_act", None)  # sharding hint
        lp = gather_fsdp(lp)                        # un-shard fsdp dims

Outside a ``sharding_ctx`` every helper degrades to identity / None / 1,
so the same model code runs unsharded on a single CPU device (smoke
tests) and sharded under GSPMD (dry-run, training) without branches.

Resolution against the active mesh is defensive by design: axes missing
from the mesh are dropped, an axis is never used twice within one spec
(first dim wins), and — when the tensor shape is known — mappings that
do not evenly divide the dim fall back to replication. This lets one
rules table serve full-size and ``reduced()`` configs alike.
"""
from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Any, Dict, Mapping, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

PyTree = Any
Axis = Union[str, Tuple[str, ...], None]

#: Canonical logical axes understood by the rules table.
LOGICAL_AXES = (
    "batch",       # data-parallel batch dim of activations
    "seq_act",     # context/sequence-parallel dim of activations
    "embed_act",   # model dim of activations (usually replicated)
    "fsdp",        # weight dim gathered per layer (ZeRO-3 style)
    "embed_fsdp",  # fsdp axis for embedding/unembedding tables
    "moe_fsdp",    # fsdp axis for expert weights
    "tp",          # tensor-parallel weight dim
    "expert",      # expert-parallel dim of MoE weights
    "vocab",       # vocab dim of embedding table / logits
)

_FSDP_AXES = ("fsdp", "embed_fsdp", "moe_fsdp")


class ShardingRules:
    """Immutable logical-axis -> mesh-axis table.

    Values are a mesh axis name, a tuple of names (one tensor dim split
    over several mesh axes), or None (replicated). Missing keys resolve
    to None, so partial tables (tests) are fine.
    """

    def __init__(self, table: Mapping[str, Axis]):
        self.table: Dict[str, Axis] = dict(table)

    def get(self, logical: Optional[str]) -> Axis:
        if logical is None:
            return None
        return self.table.get(logical)

    def override(self, **overrides: Axis) -> "ShardingRules":
        t = dict(self.table)
        t.update(overrides)
        return ShardingRules(t)

    def __eq__(self, other: object) -> bool:
        return (isinstance(other, ShardingRules)
                and self.table == other.table)

    def __repr__(self) -> str:
        return f"ShardingRules({self.table!r})"


def default_rules(*, multi_pod: bool = False) -> ShardingRules:
    """Training-layout defaults for the production meshes in launch.mesh.

    batch/fsdp ride the 'data' axis (plus 'pod' for the batch under
    multi-pod: FSDP weight-gather stays intra-pod, the gradient
    all-reduce crosses pods); tp/seq_act/expert share the 'model' axis
    (a tensor is only ever sharded by one of them at a time — the
    sanitizer drops duplicate uses within a single spec).
    """
    dp = ("pod", "data") if multi_pod else ("data",)
    return ShardingRules({
        "batch": dp,
        "seq_act": "model",
        "embed_act": None,
        "fsdp": ("data",),
        "embed_fsdp": ("data",),
        "moe_fsdp": None,
        "tp": "model",
        "expert": "model",
        "vocab": None,
    })


# ---------------------------------------------------------------------------
# Context management
# ---------------------------------------------------------------------------


class _CtxStack(threading.local):
    def __init__(self):
        self.stack = []


_CTX = _CtxStack()


@contextmanager
def sharding_ctx(mesh: Mesh, rules: ShardingRules):
    """Activate (mesh, rules) for constrain/axis_for/gather_fsdp lookups."""
    _CTX.stack.append((mesh, rules))
    try:
        yield mesh, rules
    finally:
        _CTX.stack.pop()


def _current() -> Optional[Tuple[Mesh, ShardingRules]]:
    return _CTX.stack[-1] if _CTX.stack else None


def active_mesh() -> Optional[Mesh]:
    c = _current()
    return c[0] if c else None


def active_rules() -> Optional[ShardingRules]:
    c = _current()
    return c[1] if c else None


# ---------------------------------------------------------------------------
# Axis lookups
# ---------------------------------------------------------------------------


def _mesh_sizes(mesh: Mesh) -> Dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def _names(axis: Axis) -> Tuple[str, ...]:
    if axis is None:
        return ()
    if isinstance(axis, str):
        return (axis,)
    return tuple(axis)


def axis_for(logical: str) -> Axis:
    """Mesh axis the logical axis maps to under the active ctx.

    None when outside a ctx, unmapped, or the mapped axes are absent
    from the active mesh. Preserves str vs tuple form of the rule.
    """
    c = _current()
    if c is None:
        return None
    mesh, rules = c
    ax = rules.get(logical)
    have = _mesh_sizes(mesh)
    kept = tuple(n for n in _names(ax) if n in have)
    if not kept:
        return None
    return ax if isinstance(ax, str) else kept


def axis_size_of(logical: str) -> int:
    """Number of shards the logical axis is split into (1 outside a ctx)."""
    c = _current()
    if c is None:
        return 1
    have = _mesh_sizes(c[0])
    n = 1
    for nm in _names(axis_for(logical)):
        n *= have.get(nm, 1)
    return n


# ---------------------------------------------------------------------------
# Spec resolution / sanitization
# ---------------------------------------------------------------------------


def _sanitize_spec(mesh: Mesh, entries: Sequence[Axis],
                   shape: Optional[Tuple[int, ...]] = None
                   ) -> Tuple[Axis, ...]:
    """Resolve per-dim mesh-axis entries into a valid PartitionSpec body.

    Drops axes absent from the mesh, axes already consumed by an earlier
    dim, and (when `shape` is known) whole mappings that do not evenly
    divide their dim.
    """
    have = _mesh_sizes(mesh)
    used: set = set()
    out = []
    for i, ax in enumerate(entries):
        names = [n for n in _names(ax) if n in have and n not in used]
        if names and shape is not None and i < len(shape):
            size = 1
            for n in names:
                size *= have[n]
            if size > 1 and shape[i] % size != 0:
                names = []
        if not names:
            out.append(None)
        elif len(names) == 1:
            out.append(names[0])
        else:
            out.append(tuple(names))
        used.update(names)
    return tuple(out)


def constrain(x: jax.Array, *logical: Optional[str]) -> jax.Array:
    """with_sharding_constraint by logical axis names.

    Positional args line up with the leading dims of ``x``; None entries
    and unmapped/invalid axes replicate. Identity outside a ctx.
    """
    c = _current()
    if c is None:
        return x
    mesh, rules = c
    entries = [rules.get(l) if isinstance(l, str) else l for l in logical]
    spec = _sanitize_spec(mesh, entries, getattr(x, "shape", None))
    if all(s is None for s in spec):
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*spec)))


def named_shardings(mesh: Mesh, tree: PyTree) -> PyTree:
    """PartitionSpec tree -> NamedSharding tree on `mesh`.

    Axes absent from the mesh are dropped per leaf (one spec tree can
    serve both single- and multi-pod meshes).
    """
    def one(spec: P) -> NamedSharding:
        clean = _sanitize_spec(mesh, tuple(spec))
        return NamedSharding(mesh, P(*clean))

    return jax.tree.map(one, tree, is_leaf=lambda s: isinstance(s, P))


# ---------------------------------------------------------------------------
# Parameter partition rules (name-based)
# ---------------------------------------------------------------------------

# Trailing-"core"-dims logical axes by parameter leaf name. Any extra
# leading dims (scan-over-layers stacking, hybrid superlayer stacking)
# are replicated. Norm scales, biases, conv taps and fp32 SSM leaves
# (A_log, D, dt_bias) are small and stay replicated.
_CORE2: Dict[str, Tuple[Optional[str], ...]] = {
    "wq": ("fsdp", "tp"), "wk": ("fsdp", "tp"), "wv": ("fsdp", "tp"),
    "wo": ("tp", "fsdp"),
    "w_up": ("fsdp", "tp"), "w_gate": ("fsdp", "tp"),
    "w_down": ("tp", "fsdp"),
    "in_proj": ("fsdp", "tp"), "out_proj": ("tp", "fsdp"),
    "x_proj": ("tp", None), "dt_proj": (None, "tp"),
    "embed": ("vocab", "embed_fsdp"),
    "lm_head": ("embed_fsdp", "vocab"),
    "router": (None, None),  # fp32, tiny; replicated for exact routing
    # GNN-side parameters (models/gnn.py): projection cores follow the
    # same fsdp x tp layout as the LM blocks. Temporal-attention output
    # MLP, SAGE/GAT projections, the TGN memory GRU gates and the link
    # head are all (d_in, d_out) mats; per-head GAT attention vectors
    # and time-encoding leaves are tiny and stay replicated (1-D leaves
    # never match a 2-entry rule).
    "w_out1": ("fsdp", "tp"), "w_out2": ("tp", "fsdp"),
    "w_self": ("fsdp", "tp"), "w_nbr": ("fsdp", "tp"),
    "w_dst": ("fsdp", "tp"),
    "a_dst": (None, None), "a_nbr": (None, None),
    "w_z": ("fsdp", "tp"), "w_r": ("fsdp", "tp"),
    "w_n": ("fsdp", "tp"),
    "w1": ("fsdp", "tp"), "w2": ("fsdp", "tp"),
}
# Stacked expert weights (E, d_in, d_out) under a "moe" subtree.
_MOE_CORE3: Dict[str, Tuple[Optional[str], ...]] = {
    "w_up": ("expert", "moe_fsdp", "tp"),
    "w_gate": ("expert", "moe_fsdp", "tp"),
    "w_down": ("expert", "tp", "moe_fsdp"),
}


def _path_names(path: Sequence[Any]) -> Tuple[str, ...]:
    out = []
    for p in path:
        if hasattr(p, "key"):
            out.append(str(p.key))
        elif hasattr(p, "name"):
            out.append(str(p.name))
        elif hasattr(p, "idx"):
            out.append(str(p.idx))
        else:
            out.append(str(p))
    return tuple(out)


def _logical_param_axes(path: Sequence[Any], ndim: int
                        ) -> Tuple[Optional[str], ...]:
    """Per-dim logical axes for a parameter leaf, from its tree path."""
    names = _path_names(path)
    leaf = names[-1] if names else ""
    in_moe_experts = ("moe" in names[:-1] and "shared" not in names
                      and leaf in _MOE_CORE3)
    core = _MOE_CORE3[leaf] if in_moe_experts else _CORE2.get(leaf)
    if core is None or ndim < len(core):
        return (None,) * ndim
    return (None,) * (ndim - len(core)) + tuple(core)


def param_partition_specs(params: PyTree,
                          rules: Optional[ShardingRules] = None) -> PyTree:
    """Parameter (spec) tree -> PartitionSpec tree via name-based rules.

    Works on real arrays or ShapeDtypeStructs. Inside a sharding_ctx the
    specs are additionally sanitized against the active mesh (axes
    dropped where a dim is not divisible), so reduced test configs get
    valid shardings from the same table as the full-size configs.
    """
    c = _current()
    if rules is None:
        if c is None:
            raise ValueError(
                "param_partition_specs needs explicit rules or an active "
                "sharding_ctx")
        rules = c[1]
    mesh = c[0] if c else None

    def one(path, leaf):
        entries = [rules.get(l) for l in
                   _logical_param_axes(path, leaf.ndim)]
        if mesh is not None:
            entries = _sanitize_spec(mesh, entries, leaf.shape)
        return P(*entries)

    return jax.tree_util.tree_map_with_path(one, params)


def gather_fsdp(params: PyTree) -> PyTree:
    """Constrain parameter leaves to their spec with fsdp axes dropped.

    Called on the per-layer slice inside the scan body: under GSPMD this
    makes XLA all-gather the fsdp-sharded weight dims once per layer
    (the ZeRO-3 schedule) while tp/expert/vocab shardings are kept.
    Identity outside a ctx.
    """
    c = _current()
    if c is None:
        return params
    mesh, rules = c
    gr = rules.override(**{a: None for a in _FSDP_AXES})

    def one(path, leaf):
        ndim = getattr(leaf, "ndim", 0)
        if ndim == 0:
            return leaf
        entries = [gr.get(l) for l in _logical_param_axes(path, ndim)]
        spec = _sanitize_spec(mesh, entries, leaf.shape)
        return jax.lax.with_sharding_constraint(
            leaf, NamedSharding(mesh, P(*spec)))

    return jax.tree_util.tree_map_with_path(one, params)
