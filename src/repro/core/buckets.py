"""Bucketed persistent-buffer packing for the gossip engine.

GossipGraD's exchange is O(1) bytes per step, but *how* those bytes are laid
out decides the constant: the per-leaf path issues one collective-permute per
parameter leaf (this repo's scan-stacked blocks keep that to ~15 for the LLM
configs; unstacked trees pay one per layer per tensor), while the old
``fused=True`` path re-concatenated every leaf into a fresh fp32 scratch
buffer on every mix step — a full pack/unpack round-trip through HBM plus
casts that dwarf the collective itself. Buckets decouple launch count from
the tree shape entirely (``target_bucket_bytes`` is the knob) and, unlike
both old paths, move native-dtype bytes with zero per-step packing.

This module packs the parameter tree ONCE at init into a small number of
size-balanced, LANE-aligned, dtype-homogeneous buckets, each stored as the
``(rows, 128)`` view the update and mix kernels tile over:

* **dtype-homogeneous** — a bucket only holds leaves of one dtype, so the
  wire format is the native parameter dtype (bf16 buckets move half the
  bytes the old fp32 scratch did) and no per-step casts exist;
* **LANE-aligned** — every leaf starts on a 128-element boundary and every
  bucket length is a multiple of 128, so a bucket is whole ``(rows, 128)``
  rows with no ragged tail. Storing that view (``(dp, rows, 128)`` with the
  replica axis) makes it the kernels' operand as it stands: a ``(dp, n)``
  store would be tiled by XLA:TPU with the size-1 replica axis as sublanes
  (half of every tile padding), and each step would relayout every
  parameter and moment bucket into the kernels' view and back;
* **size-balanced** — greedy bin-packing (largest leaf first onto the
  emptiest bucket) keeps buckets within ~1 max-leaf of each other, so the
  per-bucket collectives pipeline evenly against compute.

``PackedParams`` is the view layer: a registered pytree whose children are
the bucket buffers. Elementwise code (optimizers, replica means, sharding
constraints) maps straight over the buckets; shape-aware code (the model
forward, checkpointing) reads through ``.unpack()``, which is pure
slice+reshape (each leaf's whole rows, then its length) — XLA fuses it into
consumers, and its autodiff transpose delivers *gradients already packed*,
in the storage shape, so the pack cost is paid exactly once at init
instead of every step.

**Shard-local (hierarchical) layouts**: when the distribution shards leaves
*inside* a replica (fsdp's FSDP+TP over the ``data``/``model`` axes, or
``replica``-mode tensor parallelism), ``build_layout`` packs the LOCAL
SHARD of every leaf instead of the whole leaf. The layout is keyed by
``(leaf, shard_index)``: each of the ``num_shards`` mesh positions inside a
replica owns one LANE-aligned piece of every leaf — its block under the
leaf's PartitionSpec, sub-chunked over the axes the leaf does not use so
that the pieces form an exact PARTITION of the leaf (every element lives in
exactly one shard's bucket bytes; nothing is duplicated, so the unpack
transpose still delivers exact packed gradients). A bucket is then
``num_shards`` equal ``bucket_stride``-sized chunks laid end to end and its
rows shard over the in-replica mesh axes (``packed_param_specs``), so
every device's local bucket block is exactly its own shard bytes — gossip
ppermutes buckets over the replica axis only, and the mix/fused kernels
see the same LANE-aligned ``(rows, 128)`` tiles as the flat case.
With no in-replica sharding (``num_shards == 1``) everything below reduces
bit-for-bit to the flat PR-1 layout.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

PyTree = Any

LANE = 128                       # TPU lane width: alignment quantum
DEFAULT_BUCKET_BYTES = 32 << 20  # ~32 MiB buckets: enough collectives to
                                 # overlap, few enough launches to amortize

__all__ = [
    "LANE",
    "DEFAULT_BUCKET_BYTES",
    "LeafSlot",
    "BucketLayout",
    "PackedParams",
    "build_layout",
    "packed_param_specs",
    "check_layout_mesh",
]


def _align_up(n: int, q: int) -> int:
    return -(-n // q) * q


@dataclasses.dataclass(frozen=True)
class LeafSlot:
    """Where one piece of one leaf lives inside the bucket set (per-replica
    elements). Flat layouts have exactly one whole-leaf slot per leaf;
    shard-local layouts have one slot per ``(leaf, shard_index)``."""

    index: int                 # position in the flattened leaf order
    bucket: int                # bucket id
    offset: int                # LANE-aligned start element WITHIN THE SHARD
    size: int                  # element count of this piece (unpadded)
    shape: Tuple[int, ...]     # block shape (== leaf shape when unsharded)
    dtype: str
    # --- shard-local fields (defaults describe a whole-leaf slot) ---------
    shard: int = 0             # linearized in-replica shard position
    factors: Tuple[int, ...] = ()   # blocks per dim; () means all-ones —
                                    # leaf shape = shape * factors
    block: Tuple[int, ...] = ()     # this piece's block coords (() = zeros)
    chunk_start: int = 0       # flat start of this piece within its block
                               # (replication chunking over unused axes)

    def leaf_shape(self) -> Tuple[int, ...]:
        if not self.factors:
            return self.shape
        return tuple(b * f for b, f in zip(self.shape, self.factors))

    def covers_leaf(self) -> bool:
        """True when this slot is a single whole-leaf piece (flat layout)."""
        return (all(f == 1 for f in self.factors) and self.chunk_start == 0
                and self.size == int(np.prod(self.shape or (1,))))


@dataclasses.dataclass(frozen=True)
class BucketLayout:
    """Static packing plan: hashable, so it can ride as pytree aux data."""

    treedef: Any                        # treedef of the original param tree
    slots: Tuple[LeafSlot, ...]         # sorted by (leaf index, shard)
    bucket_sizes: Tuple[int, ...]       # padded elements per bucket (TOTAL:
                                        # num_shards * stride for each)
    bucket_dtypes: Tuple[str, ...]
    lane: int = LANE
    # --- shard-local (hierarchical) layout fields -------------------------
    num_shards: int = 1                 # in-replica mesh positions
    shard_axes: Tuple[str, ...] = ()    # in-replica mesh axes, row-major
    shard_axis_sizes: Tuple[int, ...] = ()
    bucket_strides: Tuple[int, ...] = ()  # per-shard elements per bucket;
                                          # () means == bucket_sizes (flat)

    @property
    def num_buckets(self) -> int:
        return len(self.bucket_sizes)

    @property
    def num_leaves(self) -> int:
        return self.treedef.num_leaves

    @property
    def strides(self) -> Tuple[int, ...]:
        """Per-shard bucket lengths (== bucket_sizes for flat layouts)."""
        return self.bucket_strides or self.bucket_sizes

    @property
    def hierarchical(self) -> bool:
        return self.num_shards > 1

    def bucket_shape(self, b: int) -> Tuple[int, int]:
        """Per-replica storage shape of bucket ``b``: the ``(rows, lane)``
        view the update and mix kernels tile over."""
        return (self.bucket_sizes[b] // self.lane, self.lane)

    def global_offset(self, slot: LeafSlot) -> int:
        """Element offset of ``slot`` within its bucket's full flat dim."""
        return slot.shard * self.strides[slot.bucket] + slot.offset

    def exact_bytes(self) -> int:
        return sum(s.size * np.dtype(s.dtype).itemsize for s in self.slots)

    def padded_bytes(self) -> int:
        return sum(n * np.dtype(d).itemsize
                   for n, d in zip(self.bucket_sizes, self.bucket_dtypes))

    def summary(self) -> dict:
        exact, padded = self.exact_bytes(), self.padded_bytes()
        return {
            "num_leaves": self.num_leaves,
            "num_buckets": self.num_buckets,
            "num_shards": self.num_shards,
            "exact_bytes": exact,
            "padded_bytes": padded,
            "pad_overhead": padded / exact - 1.0 if exact else 0.0,
            "bucket_dtypes": list(self.bucket_dtypes),
        }

    def _slots_by_leaf(self):
        groups: list = [[] for _ in range(self.num_leaves)]
        for s in self.slots:
            groups[s.index].append(s)
        return groups

    # ------------------------------------------------------------- pack
    def pack(self, tree: PyTree) -> Tuple[jnp.ndarray, ...]:
        """Pack ``tree`` (leaves = per-replica shapes, optionally with shared
        leading axes, e.g. the replica axis) into the bucket buffers. One
        concatenate per bucket — an init-time cost, never per-step."""
        leaves = self.treedef.flatten_up_to(tree)
        if len(leaves) != self.num_leaves:
            raise ValueError(
                f"tree has {len(leaves)} leaves, layout expects "
                f"{self.num_leaves}")
        by_leaf = self._slots_by_leaf()
        lead = None
        for leaf, group in zip(leaves, by_leaf):
            want = group[0].leaf_shape()
            shp = tuple(np.shape(leaf))
            cut = len(shp) - len(want)
            if cut < 0 or shp[cut:] != want:
                raise ValueError(
                    f"leaf {group[0].index} shape {shp} does not end with "
                    f"layout shape {want}")
            if lead is None:
                lead = shp[:cut]
            elif shp[:cut] != lead:
                raise ValueError(
                    f"inconsistent leading axes: {shp[:cut]} vs {lead}")
        lead = lead or ()
        nl = len(lead)

        def piece(slot: LeafSlot) -> jnp.ndarray:
            leaf = jnp.asarray(leaves[slot.index])
            if slot.covers_leaf():  # flat layouts: pure reshape, no slicing
                return jnp.reshape(leaf, lead + (slot.size,))
            if slot.factors:  # slice this shard's block out of the leaf
                idx = tuple(slice(None) for _ in range(nl)) + tuple(
                    slice(c * b, (c + 1) * b)
                    for c, b in zip(slot.block, slot.shape))
                leaf = leaf[idx]
            flat = jnp.reshape(leaf, lead + (-1,))
            return flat[..., slot.chunk_start:slot.chunk_start + slot.size]

        per_bucket: list = [[] for _ in self.bucket_sizes]
        cursors = [0] * self.num_buckets
        # place segments in global-offset order (bin-packing visits leaves by
        # size, so leaf order and offset order differ)
        for slot in sorted(self.slots,
                           key=lambda s: (s.bucket, self.global_offset(s))):
            segs, cur = per_bucket[slot.bucket], cursors[slot.bucket]
            start = self.global_offset(slot)
            dt = np.dtype(slot.dtype)
            if start > cur:  # alignment / shard-boundary gap
                segs.append(jnp.zeros(lead + (start - cur,), dt))
            segs.append(piece(slot))
            cursors[slot.bucket] = start + slot.size
        buckets = []
        for b, (segs, total, dt) in enumerate(
                zip(per_bucket, self.bucket_sizes, self.bucket_dtypes)):
            if cursors[b] < total:  # tail padding up to the LANE multiple
                segs.append(jnp.zeros(lead + (total - cursors[b],), np.dtype(dt)))
            flat = segs[0] if len(segs) == 1 else jnp.concatenate(segs, axis=-1)
            buckets.append(flat.reshape(lead + self.bucket_shape(b)))
        return tuple(buckets)

    # ----------------------------------------------------------- unpack
    def unpack(self, buckets: Sequence[jnp.ndarray]) -> PyTree:
        """Leaf-tree view of the buckets: pure slice+reshape for flat
        layouts (XLA fuses these into consumers; the autodiff transpose
        re-packs gradients for free). Shard-local layouts additionally
        re-assemble each leaf from its per-shard pieces — slice + concat +
        reshape, still pure data movement with an exact transpose (every
        element lives in exactly one piece)."""
        if len(buckets) != self.num_buckets:
            raise ValueError(
                f"{len(buckets)} buckets given, layout has {self.num_buckets}")
        # keep host-side numpy buckets on host (checkpoint save path):
        # numpy slicing is zero-copy and np.concatenate never touches jax
        host = all(isinstance(b, np.ndarray) for b in buckets)
        cat = np.concatenate if host else jnp.concatenate
        lane = self.lane

        def seg_of(slot: LeafSlot):
            b = buckets[slot.bucket]
            start = self.global_offset(slot)
            # the slot's whole rows, flattened, cut to its length: basic
            # indexing, so a static lax.slice under trace (XLA fuses it into
            # each consumer, and no whole-bucket relayout exists) and a
            # zero-copy view on host numpy buckets
            rows = b[..., start // lane:-(-(start + slot.size) // lane), :]
            return rows.reshape(rows.shape[:-2] + (-1,))[..., :slot.size]

        leaves = []
        for group in self._slots_by_leaf():
            lead = tuple(buckets[group[0].bucket].shape[:-2])
            if len(group) == 1 and group[0].covers_leaf():
                slot = group[0]
                leaves.append(seg_of(slot).reshape(lead + slot.shape))
                continue
            first = group[0]
            factors = first.factors or (1,) * len(first.shape)
            # chunks -> blocks: concat each block's pieces in flat order
            blocks: dict = {}
            for slot in sorted(group, key=lambda s: (s.block, s.chunk_start)):
                blocks.setdefault(slot.block or (0,) * len(factors),
                                  []).append(seg_of(slot))
            for coords, segs in blocks.items():
                flat = segs[0] if len(segs) == 1 else cat(segs, axis=-1)
                blocks[coords] = flat.reshape(lead + first.shape)

            # blocks -> leaf: nested concat along each sharded dim
            def assemble(prefix: Tuple[int, ...], dim: int):
                if dim == len(factors):
                    return blocks[prefix]
                parts = [assemble(prefix + (j,), dim + 1)
                         for j in range(factors[dim])]
                return (parts[0] if len(parts) == 1
                        else cat(parts, axis=len(lead) + dim))

            leaves.append(assemble((), 0) if factors else blocks[()])
        return self.treedef.unflatten(leaves)


def _leaf_pieces(shape: Tuple[int, ...], spec, shard_axes: Tuple[str, ...],
                 shard_axis_sizes: Tuple[int, ...]) -> list:
    """Partition one leaf across the ``num_shards`` in-replica positions.

    ``spec`` is the leaf's in-replica PartitionSpec (no leading replica
    entry; None = fully replicated). Dims the spec shards become the block
    decomposition; the axes the leaf does NOT use chunk each block's flat
    element range into near-equal parts, so the pieces tile the leaf exactly
    once. Returns, per linearized shard index, either None (empty piece) or
    ``(block_shape, factors, block_coords, chunk_start, piece_size)``.
    """
    sizes = dict(zip(shard_axes, shard_axis_sizes))
    dims = list(spec) if spec is not None else []
    dims = dims + [None] * (len(shape) - len(dims))
    factors, dim_axes = [], []
    used: list = []
    for size, entry in zip(shape, dims):
        axes = (tuple(entry) if isinstance(entry, tuple)
                else (entry,) if entry else ())
        for a in axes:
            if a not in sizes:
                raise ValueError(
                    f"leaf spec uses mesh axis {a!r} which is not an "
                    f"in-replica shard axis {shard_axes}")
        f = int(np.prod([sizes[a] for a in axes])) if axes else 1
        if size % f:
            raise ValueError(
                f"dim of size {size} not divisible by its {f}-way sharding")
        factors.append(f)
        dim_axes.append(axes)
        used.extend(axes)
    unused = tuple(a for a in shard_axes if a not in used)
    n_chunks = int(np.prod([sizes[a] for a in unused])) if unused else 1
    block_shape = tuple(s // f for s, f in zip(shape, factors))
    block_elems = int(np.prod(block_shape)) if block_shape else 1

    pieces = []
    num_shards = int(np.prod(shard_axis_sizes)) if shard_axis_sizes else 1
    for s in range(num_shards):
        # decode the shard's coordinate per shard axis (row-major)
        coords, rem = {}, s
        for a, n in zip(reversed(shard_axes), reversed(shard_axis_sizes)):
            coords[a] = rem % n
            rem //= n
        block = tuple(
            int(np.ravel_multi_index(tuple(coords[a] for a in axes),
                                     tuple(sizes[a] for a in axes)))
        if axes else 0 for axes in dim_axes)
        r = (int(np.ravel_multi_index(tuple(coords[a] for a in unused),
                                      tuple(sizes[a] for a in unused)))
             if unused else 0)
        base, extra = divmod(block_elems, n_chunks)
        start = r * base + min(r, extra)
        size = base + (1 if r < extra else 0)
        pieces.append(None if size == 0
                      else (block_shape, tuple(factors), block, start, size))
    return pieces


def build_layout(tree: PyTree, *, skip_leading: int = 0,
                 target_bucket_bytes: int = DEFAULT_BUCKET_BYTES,
                 lane: int = LANE,
                 shard_axes: Sequence[str] = (),
                 shard_axis_sizes: Sequence[int] = (),
                 shard_specs: PyTree | None = None) -> BucketLayout:
    """Greedy size-balanced bin-packing of ``tree``'s leaves into
    dtype-homogeneous LANE-aligned buckets.

    ``tree`` leaves may be arrays or ShapeDtypeStructs. ``skip_leading`` drops
    that many leading axes from every leaf shape (the replica axis) so the
    layout describes ONE replica; pack/unpack then broadcast over whatever
    leading axes the actual leaves carry.

    ``shard_axes`` / ``shard_axis_sizes`` (hierarchical fsdp/TP layouts)
    name the in-replica mesh axes and their sizes; ``shard_specs`` is a tree
    matching ``tree`` of in-replica PartitionSpecs (dims AFTER the skipped
    leading axes; None = replicated). Each leaf is then partitioned across
    the ``prod(shard_axis_sizes)`` positions (module docstring) and every
    position's pieces are bin-packed into its own LANE-aligned stretch of
    each bucket — same bucket assignment for all shards, per-shard offsets.
    With no shard axes this reduces exactly to the flat PR-1 layout.
    """
    shard_axes = tuple(shard_axes)
    shard_axis_sizes = tuple(int(n) for n in shard_axis_sizes)
    if len(shard_axes) != len(shard_axis_sizes):
        raise ValueError("shard_axes and shard_axis_sizes must match")
    num_shards = int(np.prod(shard_axis_sizes)) if shard_axis_sizes else 1
    if num_shards > 1 and shard_specs is None:
        raise ValueError("hierarchical layouts need shard_specs (the "
                         "in-replica PartitionSpec per leaf)")

    leaves, treedef = jax.tree.flatten(tree)
    spec_leaves = (treedef.flatten_up_to(shard_specs)
                   if (shard_specs is not None and num_shards > 1)
                   else [None] * len(leaves))
    entries = []  # (index, shape, dtype, size, pieces)
    for i, leaf in enumerate(leaves):
        shape = tuple(int(s) for s in np.shape(leaf)[skip_leading:])
        raw_dtype = leaf.dtype if hasattr(leaf, "dtype") else np.asarray(leaf).dtype
        dtype = str(jax.dtypes.canonicalize_dtype(raw_dtype))
        size = int(np.prod(shape)) if shape else 1
        if num_shards > 1:
            pieces = _leaf_pieces(shape, spec_leaves[i], shard_axes,
                                  shard_axis_sizes)
        else:
            pieces = [(shape, (), (), 0, size)]
        entries.append((i, shape, dtype, size, pieces))

    by_dtype: dict = {}
    for e in entries:
        by_dtype.setdefault(e[2], []).append(e)

    slots: list = []
    bucket_sizes: list = []
    bucket_dtypes: list = []
    bucket_strides: list = []
    for dtype in sorted(by_dtype):
        group = by_dtype[dtype]
        item = np.dtype(dtype).itemsize
        # per-position footprint drives the bucket count: a bucket should be
        # ~target bytes on each device, not summed over shards
        weight = {e[0]: max(p[4] if p else 0 for p in e[4]) for e in group}
        total = sum(_align_up(weight[e[0]], lane) for e in group)
        n_buckets = max(1, math.ceil(total * item / target_bucket_bytes))
        n_buckets = min(n_buckets, len(group))
        base = len(bucket_sizes)
        fills = [[0] * num_shards for _ in range(n_buckets)]
        # largest-first onto the emptiest bucket: balanced to ~1 leaf
        order = sorted(group, key=lambda e: (-weight[e[0]], e[0]))
        for idx, shape, dt, size, pieces in order:
            b = int(np.argmin([max(f) for f in fills]))
            for s, piece in enumerate(pieces):
                if piece is None:
                    continue
                blk_shape, factors, block, chunk_start, psize = piece
                offset = fills[b][s]
                slots.append(LeafSlot(
                    index=idx, bucket=base + b, offset=offset, size=psize,
                    shape=blk_shape, dtype=dt, shard=s, factors=factors,
                    block=block, chunk_start=chunk_start))
                fills[b][s] = _align_up(offset + psize, lane)
        for f in fills:
            stride = max(max(f), lane)
            bucket_strides.append(stride)
            bucket_sizes.append(stride * num_shards)
        bucket_dtypes.extend([dtype] * n_buckets)

    slots.sort(key=lambda s: (s.index, s.shard))
    return BucketLayout(treedef=treedef, slots=tuple(slots),
                        bucket_sizes=tuple(bucket_sizes),
                        bucket_dtypes=tuple(bucket_dtypes), lane=lane,
                        num_shards=num_shards, shard_axes=shard_axes,
                        shard_axis_sizes=shard_axis_sizes,
                        bucket_strides=tuple(bucket_strides))


@jax.tree_util.register_pytree_with_keys_class
class PackedParams:
    """Pytree view over the bucket buffers.

    ``jax.tree.map`` / optimizers / vmap see the buckets as the leaves (so
    elementwise updates and the replica-axis vmap work unchanged);
    ``.unpack()`` gives the named leaf tree for shape-aware consumers."""

    __slots__ = ("buckets", "layout")

    def __init__(self, buckets: Sequence[Any], layout: BucketLayout):
        object.__setattr__(self, "buckets", tuple(buckets))
        object.__setattr__(self, "layout", layout)

    def __setattr__(self, name, value):  # immutability keeps aux-data honest
        raise AttributeError("PackedParams is immutable")

    def tree_flatten_with_keys(self):
        keyed = tuple((jax.tree_util.SequenceKey(i), b)
                      for i, b in enumerate(self.buckets))
        return keyed, self.layout

    @classmethod
    def tree_unflatten(cls, layout, buckets):
        return cls(tuple(buckets), layout)

    @classmethod
    def pack(cls, tree: PyTree, layout: BucketLayout | None = None,
             *, skip_leading: int = 0) -> "PackedParams":
        if layout is None:
            layout = build_layout(tree, skip_leading=skip_leading)
        elif skip_leading:
            raise ValueError(
                "skip_leading only applies when building a new layout; the "
                "given layout already fixes the per-replica shapes")
        return cls(layout.pack(tree), layout)

    def unpack(self) -> PyTree:
        return self.layout.unpack(self.buckets)

    def __repr__(self):
        return (f"PackedParams(buckets={self.layout.num_buckets}, "
                f"leaves={self.layout.num_leaves}, "
                f"dtypes={sorted(set(self.layout.bucket_dtypes))})")


def packed_param_specs(layout: BucketLayout,
                       dp_axes: Sequence[str]) -> PackedParams:
    """PartitionSpec tree for packed params: every bucket is ``(dp, rows,
    LANE)`` with the replica axis on the leading dim. Flat layouts leave the
    rows unsharded; shard-local layouts shard them over the layout's
    in-replica axes — the bucket is ``num_shards`` stride-sized chunks laid
    end to end in exactly the mesh's row-major position order, and every
    stride is a LANE multiple, so each device's local block is whole rows
    of its own shard bytes (zero-copy legality of the hierarchical
    engine)."""
    dp_axes = tuple(dp_axes)
    overlap = set(dp_axes) & set(layout.shard_axes)
    if overlap:
        raise ValueError(
            f"replica axes {sorted(overlap)} also appear as in-replica shard "
            "axes of this layout; rebuild the layout for this distribution")
    front = (dp_axes if len(dp_axes) > 1 else dp_axes[0]) if dp_axes else None
    if layout.num_shards > 1:
        sh = layout.shard_axes
        inner = sh if len(sh) > 1 else sh[0]
    else:
        inner = None
    return PackedParams([P(front, inner, None)] * layout.num_buckets, layout)


def check_layout_mesh(layout: BucketLayout, mesh) -> None:
    """Validate a (possibly shard-local) layout against ``mesh``: every
    shard axis must exist with the size the layout was built for. The old
    'only sharded on the replica axis' guard is subsumed: a flat layout
    (num_shards == 1) asserts nothing about the in-replica axes — callers
    that shard inside a replica must build the layout with shard info
    (train.step does) or packing silently misassigns bytes."""
    for a, n in zip(layout.shard_axes, layout.shard_axis_sizes):
        if a not in mesh.shape:
            raise ValueError(f"layout shard axis {a!r} not in mesh axes "
                             f"{tuple(mesh.axis_names)}")
        if int(mesh.shape[a]) != n:
            raise ValueError(
                f"layout built for {a}={n} but mesh has {a}="
                f"{int(mesh.shape[a])}; rebuild the layout for this mesh")
