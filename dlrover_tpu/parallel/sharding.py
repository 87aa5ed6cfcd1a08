"""Logical-axis sharding rules.

The reference achieves TP by *swapping modules* for Megatron-style parallel
layers (atorch opt_lib/tensor_parallel_optimization.py:23, layers.py:239) and
FSDP by wrapping. On TPU neither is needed: model code stays the same and
parallelism is a *pytree of PartitionSpecs* computed from per-parameter
logical axis names (t5x-style rules). Changing strategy = changing rules,
not the model.

Each parameter carries logical axes, e.g. ``("vocab", "embed")`` for the
embedding table; rules map logical axis → mesh axis (or None = replicate).
"""

import contextlib
import threading
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

MeshAxes = Union[None, str, Tuple[str, ...]]

# rules: logical axis name -> mesh axis (or tuple, or None)
Rules = Dict[str, MeshAxes]

# The default "3D + sequence" ruleset:
#  - batch over (dp, fsdp): standard fsdp data sharding
#  - seq over sp: sequence/context parallelism
#  - embed over fsdp: ZeRO-3 parameter sharding along the model dim
#  - heads/mlp/vocab over tp: Megatron-style tensor parallelism
#  - experts over ep; layers (scan axis) over pp when pipelining
DEFAULT_RULES: Rules = {
    "batch": ("dp", "fsdp"),
    "seq": "sp",
    "embed": "fsdp",
    "vocab": "tp",
    "heads": "tp",
    "kv": None,
    "mlp": "tp",
    "expert": "ep",
    "layers": None,
    "norm": None,
}


def rules_for_mesh(mesh: Mesh, rules: Optional[Rules] = None) -> Rules:
    """DEFAULT_RULES specialised to a mesh: the stacked-layer axis shards
    over pp when the mesh pipelines (each stage holds its layer block)."""
    out = dict(DEFAULT_RULES)
    if mesh.shape.get("pp", 1) > 1:
        out["layers"] = "pp"
    out.update(rules or {})
    return out


def logical_to_mesh_axes(
    logical_axes: Optional[Sequence[Optional[str]]],
    rules: Rules,
) -> P:
    """Map a tuple of logical axis names to a PartitionSpec."""
    if logical_axes is None:
        return P()
    spec: List[MeshAxes] = []
    used: set = set()
    for name in logical_axes:
        axis = rules.get(name) if name is not None else None
        # One mesh axis may shard at most one tensor dim.
        if axis is not None:
            axes = axis if isinstance(axis, tuple) else (axis,)
            if any(a in used for a in axes):
                axis = None
            else:
                used.update(axes)
        spec.append(axis)
    while spec and spec[-1] is None:
        spec.pop()
    return P(*spec)


def shardings_for_tree(
    mesh: Mesh,
    logical_tree,
    rules: Optional[Rules] = None,
):
    """Pytree of logical-axes tuples → pytree of NamedSharding."""
    rules = rules_for_mesh(mesh, rules)
    return jax.tree.map(
        lambda axes: NamedSharding(mesh, logical_to_mesh_axes(axes, rules)),
        logical_tree,
        is_leaf=lambda x: x is None or isinstance(x, tuple),
    )


def constrain(x, mesh: Mesh, *logical_axes: Optional[str], rules=None):
    """``with_sharding_constraint`` by logical axis names.

    Works both at top level and inside a partial-manual ``shard_map`` (the
    pipeline's pp region): there the constraint must be built against the
    ambient abstract mesh, with any manual axes stripped from the spec.
    """
    if in_update_sharding_region():
        # inside the weight-update-sharding shard_map every mesh axis is
        # manual (dp-only meshes; see CommConfig) and jax 0.4.x cannot
        # report that via manual_axis_names — constraints are no-ops on
        # local values anyway, so drop them
        return x
    rules = rules_for_mesh(mesh, rules)
    spec = logical_to_mesh_axes(logical_axes, rules)
    manual = manual_axis_names()
    if manual:
        am = jax.sharding.get_abstract_mesh()
        spec = P(*[_drop_axes(entry, set(manual)) for entry in spec])
        return jax.lax.with_sharding_constraint(x, NamedSharding(am, spec))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def manual_axis_names() -> frozenset:
    """Mesh axes currently under manual control (inside a ``shard_map``);
    empty at top level."""
    am = jax.sharding.get_abstract_mesh()
    return frozenset(
        name
        for name, t in zip(am.axis_names, am.axis_types)
        if t == AxisType.Manual
    )


def _drop_axes(entry: MeshAxes, names: set) -> MeshAxes:
    if entry is None:
        return None
    if isinstance(entry, tuple):
        kept = tuple(a for a in entry if a not in names)
        return kept or None
    return None if entry in names else entry


# ---------------------------------------------------------------------------
# Gradient-collective comm config (weight-update sharding + wire dtypes)
# ---------------------------------------------------------------------------

_WIRE_DTYPES = ("float32", "bfloat16", "int8")

# update_sharding mode strings. "zero1" defers the gradient exchange to
# one reduce-scatter per step (full local gradient accumulates on-rank);
# "zero2" reduce-scatters every microbatch so only the 1/dp shard of the
# summed gradient is ever resident across the accumulation loop. The
# legacy boolean True maps to "zero2" — that per-microbatch exchange IS
# the behaviour the boolean has always selected, so existing configs
# stay bitwise identical.
_UPDATE_MODES = ("zero1", "zero2")


@dataclass(frozen=True)
class CommConfig:
    """How gradients cross the mesh and where the optimizer runs.

    ``update_sharding`` turns on the ZeRO-1 weight-update path
    (arxiv 2004.13336): gradients ride a reduce-scatter instead of an
    all-reduce, each dp rank runs the optimizer on its 1/dp shard of a
    flat bucketed view of the parameters, and the updated params come
    back through one all-gather. Optimizer state (Adam moments) lives
    permanently dp-sharded, cutting its HBM per replica by ~dp.

    ``bucket_mb`` sizes the fixed buckets the flattened gradients are
    packed into: each bucket is an independent reduce-scatter, so XLA's
    latency-hiding scheduler can start shipping early buckets while the
    tail of backward still computes.

    ``update_sharding`` also accepts a mode string: ``"zero2"`` (what
    ``True`` means — gradients are reduce-scattered per microbatch, so
    only the 1/dp shard is resident across the grad-accum loop) or
    ``"zero1"`` (accumulate the full local gradient, one deferred
    reduce-scatter per step — fewer collectives when accumulating, at
    the cost of full-gradient residency).

    ``wire_dtype`` is the on-the-wire encoding of the dp gradient
    exchange: "float32" (bitwise-exact psum_scatter), "bfloat16" (half
    the bytes), or "int8" (EQuARX-style, arxiv 2506.17615: blockwise
    scales from ops/quant.py, ~4x fewer bytes). ``wire_dtype_dcn``
    overrides it when the dp axis crosses DCN slices — the hop where
    compression pays for itself.
    """

    update_sharding: Union[bool, str] = False
    bucket_mb: float = 4.0
    wire_dtype: str = "float32"
    wire_dtype_dcn: Optional[str] = None

    def __post_init__(self):
        if (
            not isinstance(self.update_sharding, bool)
            and self.update_sharding not in _UPDATE_MODES
        ):
            raise ValueError(
                f"update_sharding must be a bool or one of {_UPDATE_MODES},"
                f" got {self.update_sharding!r}"
            )
        if self.wire_dtype not in _WIRE_DTYPES:
            raise ValueError(
                f"wire_dtype must be one of {_WIRE_DTYPES}, "
                f"got {self.wire_dtype!r}"
            )
        if (
            self.wire_dtype_dcn is not None
            and self.wire_dtype_dcn not in _WIRE_DTYPES
        ):
            raise ValueError(
                f"wire_dtype_dcn must be one of {_WIRE_DTYPES} or None, "
                f"got {self.wire_dtype_dcn!r}"
            )
        if self.bucket_mb <= 0:
            raise ValueError(f"bucket_mb must be > 0, got {self.bucket_mb}")

    @property
    def bucket_bytes(self) -> int:
        return int(self.bucket_mb * 2**20)

    @property
    def update_mode(self) -> str:
        """Resolved mode string: "" (off), "zero1", or "zero2"."""
        if self.update_sharding is False:
            return ""
        if self.update_sharding is True:
            return "zero2"
        return self.update_sharding

    def wire_for(self, mesh: Mesh, axis: str = "dp") -> str:
        """Wire dtype for the gradient exchange over ``axis``."""
        if self.wire_dtype_dcn is not None:
            from dlrover_tpu.parallel.mesh import axis_crosses_dcn

            if axis_crosses_dcn(mesh, axis):
                return self.wire_dtype_dcn
        return self.wire_dtype


# ---------------------------------------------------------------------------
# Update-sharding trace-time region
# ---------------------------------------------------------------------------

# Trace-time marker for "model code is being traced inside the
# update-sharding shard_map": the train step raises this flag around the
# shard_map body trace, `constrain` turns into a no-op and the
# tied-embedding head read routes through the cotangent-splitting alias
# below.
_REGION = threading.local()


def in_update_sharding_region() -> bool:
    return getattr(_REGION, "depth", 0) > 0


def unroll_layer_scans() -> bool:
    """True inside a PARTIAL-manual update-sharding region (hybrid
    dp×fsdp / dp×tp meshes): the jax 0.4.x partitioner check-fails on a
    ``lax.scan`` whose xs carry auto-axis-sharded values (the stacked
    layer params), so the model trunk must unroll its layer loop."""
    return in_update_sharding_region() and getattr(
        _REGION, "unroll_scans", False
    )


@contextlib.contextmanager
def update_sharding_region(tie_zero=None, unroll_scans=False):
    prev_zero = getattr(_REGION, "tie_zero", None)
    prev_unroll = getattr(_REGION, "unroll_scans", False)
    _REGION.depth = getattr(_REGION, "depth", 0) + 1
    _REGION.tie_zero = tie_zero
    _REGION.unroll_scans = unroll_scans
    try:
        yield
    finally:
        _REGION.depth -= 1
        _REGION.tie_zero = prev_zero
        _REGION.unroll_scans = prev_unroll


def tied_head_table(table: jax.Array) -> jax.Array:
    """The tied lm-head's read of the embedding table.

    Outside an update-sharding region: the table itself. Inside one: a
    ``stop_gradient(table) + z`` alias, where ``z`` is the zeros array
    the region registered — so the head matmul's cotangent lands on
    ``z`` instead of fanning into the lookup's scatter cotangent. The
    two contributions then ride SEPARATE reduce-scatters, reproducing
    GSPMD's unsharded lowering (two all-reduces, added after), which is
    what makes the f32-wire path bitwise-identical to it.
    """
    z = getattr(_REGION, "tie_zero", None)
    if not in_update_sharding_region() or z is None:
        return table
    return jax.lax.stop_gradient(table) + z.astype(table.dtype)


# ---------------------------------------------------------------------------
# Flat bucketed gradient/param packing
# ---------------------------------------------------------------------------


_LANES = 128  # minor tile width of a TPU array of two or more dimensions


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


@dataclass(frozen=True)
class PackPlan:
    """Static layout of a parameter tree flattened into comm buckets.

    The flat stream is the tree's canonical leaf order (jax sorted-key
    flatten), zero-padded to ``n_buckets * bucket_elems``; each bucket
    row is one collective. ``bucket_elems`` is a multiple of
    ``dp * quant BLOCK`` so every dp shard of every bucket quantizes on
    block boundaries. For tied embeddings the table must sit at offset
    0 (bucket-aligned): the split-off head cotangent is packed into its
    own ``n_tie_buckets`` rows and added shard-wise after the exchange.

    ``mesh_axes`` records which mesh axes the plan was built under:
    ``("dp",)`` for the pure-dp layout, or e.g. ``("dp", "fsdp")`` when
    the update shards over the dp axis of a hybrid mesh. The flat
    stream coordinates are only canonical within one mesh_axes family —
    consumers that repack across geometries (elastic/resharding.py)
    key off this field to refuse streams they cannot line up.
    """

    shapes: Tuple[Tuple[int, ...], ...]
    sizes: Tuple[int, ...]
    offsets: Tuple[int, ...]
    total: int
    bucket_elems: int
    n_buckets: int
    dp: int
    tie_size: int          # 0 when embeddings are untied
    n_tie_buckets: int
    mesh_axes: Tuple[str, ...] = ("dp",)

    @property
    def padded(self) -> int:
        return self.n_buckets * self.bucket_elems

    @property
    def shard_elems(self) -> int:
        """Per-rank elements of the flat view (optimizer-state rows)."""
        return self.padded // self.dp


def build_pack_plan(
    params_abs,
    dp: int,
    bucket_bytes: int = 4 * 2**20,
    tie_embeddings: bool = False,
    mesh_axes: Tuple[str, ...] = ("dp",),
) -> PackPlan:
    """Lay a parameter tree out into fixed-size comm buckets."""
    from dlrover_tpu.ops.quant import BLOCK

    leaves = jax.tree.leaves(params_abs)
    bad = [l for l in leaves if jnp.dtype(l.dtype) != jnp.float32]
    if bad:
        raise ValueError(
            "update sharding packs a uniform f32 master-param stream; "
            f"found non-f32 leaves: {[str(l.dtype) for l in bad]}"
        )
    sizes, offsets, shapes, off = [], [], [], 0
    for l in leaves:
        shapes.append(tuple(l.shape))
        sizes.append(int(l.size))
        offsets.append(off)
        off += int(l.size)
    align = dp * BLOCK
    bucket_elems = _round_up(max(bucket_bytes // 4, align), align)
    n_buckets = max(1, -(-off // bucket_elems))
    tie_size = 0
    if tie_embeddings:
        with_path = jax.tree_util.tree_leaves_with_path(params_abs)
        tie_idx = next(
            (
                i
                for i, (kp, _) in enumerate(with_path)
                if "embed" in jax.tree_util.keystr(kp)
                and "tokens" in jax.tree_util.keystr(kp)
            ),
            None,
        )
        if tie_idx is None or offsets[tie_idx] != 0:
            raise ValueError(
                "tied update sharding needs embed/tokens at flat offset "
                f"0 of the canonical leaf order, found index {tie_idx}"
            )
        tie_size = sizes[tie_idx]
    n_tie = -(-tie_size // bucket_elems) if tie_size else 0
    return PackPlan(
        shapes=tuple(shapes),
        sizes=tuple(sizes),
        offsets=tuple(offsets),
        total=off,
        bucket_elems=bucket_elems,
        n_buckets=n_buckets,
        dp=dp,
        tie_size=tie_size,
        n_tie_buckets=n_tie,
        mesh_axes=tuple(mesh_axes),
    )


def _bucket_row(flats, plan: PackPlan, i: int):
    """Row ``i`` of the stream, ``[bucket_elems]``, from the 1-D views
    ``flats`` of the leading leaves: only the leaf slices that overlap
    it, zero-padded past the last leaf."""
    e = plan.bucket_elems
    lo, hi = i * e, (i + 1) * e
    pieces = [
        flat[max(lo, off) - off : min(hi, off + size) - off]
        for off, size, flat in zip(plan.offsets, plan.sizes, flats)
        if off < hi and off + size > lo
    ]
    pad = e - sum(p.shape[0] for p in pieces)
    if pad:
        pieces.append(jnp.zeros((pad,), jnp.float32))
    return pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces)


def _flat_views(tree):
    return [
        leaf.reshape(-1).astype(jnp.float32) for leaf in jax.tree.leaves(tree)
    ]


def pack_buckets(tree, plan: PackPlan, n_buckets: Optional[int] = None):
    """Pytree → list of ``n_buckets`` independent ``[bucket_elems]`` rows
    of the zero-padded flat stream (the tree's leaves in order).

    Each row is built from ONLY the leaf slices overlapping its flat
    range — so a bucket's reduce-scatter depends on just the gradients
    inside it, not on every leaf, which is what lets XLA's
    latency-hiding scheduler issue early buckets while the backward
    tail computes. A tree of fewer leaves than the plan (the tied
    head's cotangent alone, ``n_buckets=plan.n_tie_buckets``) packs
    against the plan's leading offsets.
    """
    nb = plan.n_buckets if n_buckets is None else n_buckets
    with jax.named_scope("zero.pack"):
        flats = _flat_views(tree)
        return [_bucket_row(flats, plan, i) for i in range(nb)]


def pack_flat(tree, plan: PackPlan, n_buckets: Optional[int] = None):
    """Pytree → ``[n_buckets, bucket_elems]`` f32 stream (zero-padded).

    For set-up and tests. The step never holds the whole stream in this
    shape: on the TPU a 2-D array is tiled (8, 128) and a 1-D one by
    1024, so going between ``[n_buckets, bucket_elems]`` and the 1-D
    stream the leaves are cut from is a copy the compiler makes one
    4 MiB row per loop trip, at a twentieth of the memory's rate. The
    step packs each rank's shard from the leaves (``pack_shard``) and
    gathers the stream in its own order (``gather_stream``).
    """
    return jnp.stack(pack_buckets(tree, plan, n_buckets))


def pack_shard(tree, plan: PackPlan, idx):
    """Rank ``idx``'s ``[n_buckets, bucket_elems/dp]`` of the stream:
    ``pack_flat(tree, plan)[:, idx*q:(idx+1)*q]`` without the stream.

    Called inside the dp-manual region with ``idx = axis_index("dp")``.
    A row that lies inside one leaf is one slice of that leaf's 1-D
    view; the few that straddle leaves (about one a leaf) or hold the
    zero tail are built whole and sliced.
    """
    e, q = plan.bucket_elems, plan.bucket_elems // plan.dp
    # unsigned: no wrap-around test of a negative start, three scalar
    # operations a row, in the compiled step
    idx = jnp.asarray(idx, jnp.uint32)
    with jax.named_scope("zero.pack"):
        flats = _flat_views(tree)
        rows = []
        for i in range(plan.n_buckets):
            lo = i * e
            flat, base = next(
                (
                    (flat, lo - off)
                    for off, size, flat in zip(plan.offsets, plan.sizes, flats)
                    if off <= lo and lo + e <= off + size
                ),
                (None, 0),
            )
            if flat is None:
                flat = _bucket_row(flats, plan, i)
            rows.append(
                jax.lax.dynamic_slice(flat, (base + idx * q,), (q,))
            )
        return jnp.stack(rows)


def gather_stream(shard, axis: str = "dp"):
    """All-gather the ranks' ``[n_buckets, bucket_elems/dp]`` shards
    into the whole stream, as ``[n_buckets, bucket_elems/128, 128]``.

    That shape, tiled (8, 128) like every array of two or more
    dimensions, lies in memory in the stream's own order, so
    ``unpack_flat``'s 1-D view of it is free. Gathered as
    ``[n_buckets, bucket_elems]`` the same bytes interleave eight rows
    a tile, and the 1-D view is a row-by-row copy of the whole stream.
    Only the rank's own shard is relaid here. (``bucket_elems/dp`` is a
    multiple of the quantisation block, itself a multiple of 128.)
    """
    n, q = shard.shape
    return jax.lax.all_gather(
        shard.reshape(n, q // _LANES, _LANES), axis, axis=1, tiled=True
    )


def unpack_flat(flat, like, plan: PackPlan):
    """Inverse of ``pack_flat``: the stream, in any shape whose
    row-major order is the stream's, → pytree shaped like ``like``.
    The step hands it ``gather_stream``'s result, never a 2-D array."""
    stream = flat.reshape(-1)
    leaves = jax.tree.leaves(like)
    out = [
        stream[o : o + s].reshape(shp).astype(l.dtype)
        for o, s, shp, l in zip(
            plan.offsets, plan.sizes, plan.shapes, leaves
        )
    ]
    return jax.tree.unflatten(jax.tree.structure(like), out)


# ---------------------------------------------------------------------------
# Bucketed gradient exchange (runs inside the full-manual shard_map)
# ---------------------------------------------------------------------------


def _exchange_bucket(row: jax.Array, axis: str, wire: str, dp: int):
    """One bucket: local partial ``[E]`` → this rank's ``[E/dp]`` of the sum."""
    if wire == "float32":
        # bitwise-identical to all-reduce + slice on this backend
        return jax.lax.psum_scatter(
            row, axis, scatter_dimension=0, tiled=True
        )
    rows = row.reshape(dp, -1)  # rows[r] = my partial of rank r's shard
    if wire == "bfloat16":
        got = jax.lax.all_to_all(
            rows.astype(jnp.bfloat16), axis, split_axis=0, concat_axis=0
        )
        return jnp.sum(got.astype(jnp.float32), axis=0)
    from dlrover_tpu.ops.quant import wire_decode_sum, wire_encode_rows

    q, scale = wire_encode_rows(rows)
    q = jax.lax.all_to_all(q, axis, split_axis=0, concat_axis=0)
    scale = jax.lax.all_to_all(scale, axis, split_axis=0, concat_axis=0)
    return wire_decode_sum(q, scale)


def exchange_buckets(
    g,
    plan: PackPlan,
    wire: str,
    axis: str = "dp",
    tie_extra: Optional[jax.Array] = None,
    issue_order: str = "reverse",
):
    """Reduce-scatter the packed gradient stream bucket-by-bucket.

    ``g``: local partial gradients — a ``[n_buckets, bucket_elems]``
    array (``pack_flat``) or a list of per-bucket rows
    (``pack_buckets``, the overlap-friendly form). Returns this rank's
    ``[n_buckets, bucket_elems/dp]`` of the summed stream. Each bucket
    is its own collective so the scheduler can overlap early buckets
    with the tail of backward. ``issue_order="reverse"`` emits the
    collectives from the LAST bucket down: backward produces gradients
    roughly output-to-input, and the canonical flat order starts with
    the embedding table — whose gradient lands last — so reverse
    issue order matches gradient availability. Values are
    order-independent (each bucket is an independent collective), so
    the f32 wire stays bitwise whatever the order. ``tie_extra`` (the
    split-off tied-head cotangent, ``[tie_size]``) rides its own
    buckets and is added shard-wise onto the leading rows — its zero
    padding makes the adds past the table's end exact no-ops.
    """
    rows = (
        list(g)
        if isinstance(g, (list, tuple))
        else [g[i] for i in range(plan.n_buckets)]
    )
    order = (
        range(plan.n_buckets - 1, -1, -1)
        if issue_order == "reverse"
        else range(plan.n_buckets)
    )
    tied = tie_extra is not None and bool(plan.tie_size)
    with jax.named_scope("zero.exchange"):
        shards: List = [None] * plan.n_buckets
        for i in order:
            shards[i] = _exchange_bucket(rows[i], axis, wire, plan.dp)
        if tied:
            extra = pack_buckets(
                [tie_extra], plan, n_buckets=plan.n_tie_buckets
            )
            for i in range(plan.n_tie_buckets):
                shards[i] = shards[i] + _exchange_bucket(
                    extra[i], axis, wire, plan.dp
                )
        return jnp.stack(shards)


_WIRE_BYTES = {"float32": 4, "bfloat16": 2, "int8": 1}


def exchange_payload_bytes(plan: PackPlan, wire: str, tied: bool) -> int:
    """Bytes of the gradient stream one rank hands to one
    ``exchange_buckets`` call: every bucket at the wire's width, the
    tied head's buckets included (int8: its f32 block scales too)."""
    n = plan.n_buckets + (plan.n_tie_buckets if tied else 0)
    nbytes = n * plan.bucket_elems * _WIRE_BYTES[wire]
    if wire == "int8":
        from dlrover_tpu.ops.quant import BLOCK

        nbytes += n * plan.bucket_elems // BLOCK * 4
    return nbytes
