"""Checkpointing: pytree <-> npz with a json manifest.

Leaves are keyed by their tree path; the manifest records the path list,
dtypes, and user metadata so restore can validate structure. Works on any
state pytree (train state with replica axis included). Arrays are pulled to
host with ``jax.device_get`` (for sharded arrays this gathers addressable
shards — single-process semantics, which is what this container runs).

Bucketed gossip state (core.buckets.PackedParams) is read THROUGH the view
layer: save unpacks every PackedParams node to its named leaf tree before
writing, and restore re-packs after reading. The on-disk format is therefore
identical between the packed and per-leaf engines — a packed run can restore
a leaf checkpoint and vice versa. This extends to SHARD-LOCAL (hierarchical
fsdp/TP) layouts: unpack reassembles each leaf from its per-shard pieces on
the host (zero-copy numpy views + np.concatenate) and restore re-packs into
whatever layout the template carries, so fsdp-packed, pure_dp-packed, and
per-leaf checkpoints all cross-restore freely — including the inbox ring's
PackedParams slots (tests/test_hier_packed.py).

Asynchronous gossip state: the staleness-k inbox ring (``state["inbox"]`` =
``{"slots": (k param-shaped trees, oldest first), "valid": (dp, k) mask,
"t": dispatch counter}`` — PackedParams slots included) is just another
state subtree, so it persists and re-packs through the same machinery;
together with the step counter in the manifest (from which the gossip phase
resumes: ``phase = step % schedule.period``) an async run restores to the
exact point in the exchange pipeline it left off — resumption is
bit-deterministic (tests/test_async_gossip.py).

Cross-staleness restore: a checkpoint written at one ring depth restores
into a template of another. A shallower checkpoint (e.g. k=1 -> k=4 run) is
**mask-padded**: its in-flight payloads stay oldest-first and the new back
slots start invalid (a skip is always safe — the protocol's own drop
semantics). A deeper checkpoint (k=4 -> k=1 run) is truncated to the oldest
slots: the newer in-flight payloads are "lost on the wire", which gossip
tolerates by design (§4.2). Legacy PR-2 checkpoints (a bare staleness-1
inbox tree, no ring keys) restore as a one-slot ring with a valid mask.

Compressed-wire rings (core.async_gossip.init_wire_inbox_ring): int8 codes
save natively, fp8/bf16 stage as f32 (lossless — every e4m3/bf16 value is
exactly f32-representable) with the true dtype recorded in the manifest.
Their payloads keep the buckets' ``(dp, rows, 128)`` storage shape; a
checkpoint that holds them flat, ``(dp, rows * 128)``, restores by a
reshape.
Cross-WIRE-FORMAT restore (fp32-wire ring <-> compressed ring, either
direction) cannot adapt slot-by-slot — the payload structures differ — so
the params/opt subtrees restore strictly and the ring resets to the
template's bootstrap with t = the manifest step (the first k mixes after
the crossover are skips; dispatch-keyed noise and the bucket-subset
rotation stay aligned with the resumed gossip phase).
"""
from __future__ import annotations

import json
import os
import re
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np

from repro.core.buckets import LANE, PackedParams

PyTree = Any

__all__ = ["save_state", "restore_state", "checkpoint_exists", "read_manifest"]

_RING_KEYS = frozenset(("slots", "valid", "t"))
_SLOT_KEY_RE = re.compile(r"\['inbox'\]\['slots'\]\[(\d+)\]")


def _is_ring(node) -> bool:
    """True for an inbox-ring node (core.async_gossip.init_inbox_ring)."""
    return (isinstance(node, dict) and set(node) == _RING_KEYS
            and isinstance(node["slots"], (tuple, list)))


def _is_packed(x) -> bool:
    return isinstance(x, PackedParams)


def _unpack_view(tree: PyTree) -> PyTree:
    """Replace every PackedParams node by its unpacked leaf tree."""
    return jax.tree.map(lambda x: x.unpack() if _is_packed(x) else x,
                        tree, is_leaf=_is_packed)


def _pack_like(template: PyTree, tree: PyTree) -> PyTree:
    """Re-pack ``tree`` (unpacked form) along ``template``'s PackedParams
    nodes, reusing the template's layouts."""
    if _is_packed(template):
        return PackedParams(template.layout.pack(tree), template.layout)
    if isinstance(template, dict):
        return {k: _pack_like(template[k], tree[k]) for k in template}
    if isinstance(template, (list, tuple)):
        vals = (_pack_like(t, v) for t, v in zip(template, tree))
        return (type(template)(*vals) if hasattr(template, "_fields")
                else type(template)(vals))
    if any(_is_packed(l) for l in jax.tree.leaves(template, is_leaf=_is_packed)):
        raise TypeError(
            f"cannot re-pack through container {type(template).__name__}: "
            "PackedParams nodes must sit under dict/list/tuple state trees")
    return tree


def _flatten(tree: PyTree):
    leaves, treedef = jax.tree_util.tree_flatten_with_path(tree)
    keyed = {}
    for path, leaf in leaves:
        key = jax.tree_util.keystr(path)
        keyed[key] = leaf
    return keyed, treedef


def checkpoint_exists(path: str) -> bool:
    """True when ``path`` holds a complete checkpoint (manifest + arrays)."""
    return (os.path.isfile(os.path.join(path, "manifest.json"))
            and os.path.isfile(os.path.join(path, "arrays.npz")))


def read_manifest(path: str) -> Dict:
    """Manifest only (step / metadata / keys) — no array loading. Lets a
    launcher decide resume step and validate protocol metadata cheaply."""
    with open(os.path.join(path, "manifest.json")) as f:
        return json.load(f)


def save_state(path: str, state: PyTree, metadata: Optional[Dict] = None,
               step: Optional[int] = None) -> None:
    os.makedirs(path, exist_ok=True)
    # pull buckets to host BEFORE unpacking: host-side numpy unpack is
    # zero-copy views, so no second device-side copy of the state exists
    keyed, _ = _flatten(_unpack_view(jax.device_get(state)))
    arrays = {k: np.asarray(v) for k, v in keyed.items()}
    # npz cannot store ml_dtypes (bf16/f8): stage them as f32 and record the
    # original dtype in the manifest for restore
    dtypes = {k: str(v.dtype) for k, v in arrays.items()}
    staged = {k: (v.astype(np.float32) if v.dtype.kind not in "fiub" or
                  str(v.dtype) == "bfloat16" else v)
              for k, v in arrays.items()}
    names = sorted(staged)
    np.savez(os.path.join(path, "arrays.npz"),
             **{f"a{i}": staged[k] for i, k in enumerate(names)})
    manifest = {
        "version": 1,
        "step": step,
        "keys": names,
        "dtypes": dtypes,
        "shapes": {k: list(arrays[k].shape) for k in names},
        "metadata": metadata or {},
    }
    with open(os.path.join(path, "manifest.json"), "w") as f:
        json.dump(manifest, f, indent=1)


def _ckpt_ring_depth(names) -> Optional[Tuple[int, bool]]:
    """(slot count, legacy?) of the checkpoint's inbox, or None when the
    checkpoint has no inbox subtree. ``legacy`` marks the PR-2 format: a
    bare inbox tree with no ring keys (treated as a one-slot valid ring)."""
    slot_idx = set()
    has_inbox = False
    for key in names:
        if key.startswith("['inbox']"):
            has_inbox = True
            m = _SLOT_KEY_RE.match(key)
            if m:
                slot_idx.add(int(m.group(1)))
    if not has_inbox:
        return None
    if not slot_idx:
        return 1, True
    return max(slot_idx) + 1, False


def _adapt_ring(ring: Dict, k_t: int) -> Dict:
    """Resize a restored (unpacked, host-side) inbox ring to depth ``k_t``:
    mask-pad a shallower ring (new back slots carry copies of the newest
    payload but start invalid — consumed as skips), truncate a deeper one to
    its oldest slots (the newer in-flight payloads are dropped, which the
    protocol tolerates by design)."""
    slots, valid = list(ring["slots"]), np.asarray(ring["valid"])
    k_c = len(slots)
    if k_c < k_t:
        pad = k_t - k_c
        slots = slots + [jax.tree.map(np.copy, slots[-1]) for _ in range(pad)]
        valid = np.concatenate(
            [valid, np.zeros((valid.shape[0], pad), valid.dtype)], axis=1)
    elif k_c > k_t:
        slots = slots[:k_t]
        valid = np.ascontiguousarray(valid[:, :k_t])
    return {"slots": tuple(slots), "valid": valid, "t": ring["t"]}


def _as_stored(arr: np.ndarray, shape: Tuple[int, ...], key: str
               ) -> np.ndarray:
    """``arr`` in the template's ``shape``. A bucket-shaped leaf (a wire
    ring's payload codes) is stored ``(..., rows, LANE)``; checkpoints
    written before that storage hold it flat, ``(..., rows * LANE)``, and
    load by a reshape."""
    if tuple(arr.shape) == shape:
        return arr
    if (len(shape) >= 2 and shape[-1] == LANE
            and tuple(arr.shape) == shape[:-2] + (shape[-2] * LANE,)):
        return arr.reshape(shape)
    raise ValueError(f"shape mismatch at {key}: {arr.shape} vs {shape}")


def restore_state(path: str, template: PyTree) -> Tuple[PyTree, Dict]:
    """Restore into the structure of ``template`` (shapes/dtypes validated).
    PackedParams nodes in the template are restored through their unpacked
    leaf view and re-packed; an inbox ring whose depth differs from the
    template's is mask-padded / truncated (module docstring). Returns
    (state, manifest)."""
    with open(os.path.join(path, "manifest.json")) as f:
        manifest = json.load(f)
    data = np.load(os.path.join(path, "arrays.npz"))
    names = manifest["keys"]
    arrays = {k: data[f"a{i}"] for i, k in enumerate(names)}

    packed_template = template
    ring_adapt = None  # (target depth, ckpt depth, legacy?, dp)
    if (isinstance(template, dict) and "inbox" in template
            and _is_ring(template["inbox"])):
        depth = _ckpt_ring_depth(names)
        if depth is not None:
            k_c, legacy = depth
            ring_t = template["inbox"]
            k_t = len(ring_t["slots"])
            dp = int(np.shape(ring_t["valid"])[0])
            if legacy:
                # PR-2 on-disk format: the inbox is a bare param-shaped tree
                template = dict(template, inbox=ring_t["slots"][0])
                ring_adapt = (k_t, 1, True, dp)
            elif k_c != k_t:
                template = dict(template, inbox={
                    "slots": tuple(ring_t["slots"][min(i, k_t - 1)]
                                   for i in range(k_c)),
                    "valid": np.zeros((dp, k_c), np.float32),
                    "t": ring_t["t"],
                })
                ring_adapt = (k_t, k_c, False, dp)
    # abstract unpack: only shapes/dtypes are needed for validation — never
    # materialize a full unpacked copy of the packed state on device
    template = jax.eval_shape(_unpack_view, template)
    keyed, _ = _flatten(template)
    ring_reset = False
    if set(keyed) != set(arrays):
        # cross-WIRE-FORMAT inbox: a ring of compressed payloads (codes +
        # scales) and a ring of raw params flatten to different key sets, so
        # no slot-level adaptation is possible. When the mismatch is confined
        # to the inbox subtree, restore everything else strictly and RESET
        # the ring to the template's bootstrap (all slots as initialized,
        # valid zeroed, t = the manifest step so the dispatch-keyed noise
        # and subset rotation resume in lockstep with the gossip phase) —
        # the first k mixes after the crossover are skips, which the
        # protocol's own drop semantics already tolerate.
        t_rest = {k for k in keyed if not k.startswith("['inbox']")}
        c_rest = {k for k in arrays if not k.startswith("['inbox']")}
        if (t_rest == c_rest and isinstance(packed_template, dict)
                and "inbox" in packed_template
                and _is_ring(packed_template["inbox"])):
            ring_reset = True
            ring_adapt = None
            template = {k: v for k, v in template.items() if k != "inbox"}
            keyed = {k: v for k, v in keyed.items()
                     if not k.startswith("['inbox']")}
            arrays = {k: v for k, v in arrays.items()
                      if not k.startswith("['inbox']")}
        else:
            missing = sorted(set(keyed) - set(arrays))[:5]
            extra = sorted(set(arrays) - set(keyed))[:5]
            raise ValueError(f"checkpoint/template mismatch; "
                             f"missing={missing} extra={extra}")
    leaves_with_path, treedef = jax.tree_util.tree_flatten_with_path(template)
    out = []
    for pth, leaf in leaves_with_path:
        key = jax.tree_util.keystr(pth)
        arr = _as_stored(arrays[key], tuple(np.shape(leaf)), key)
        out.append(arr.astype(leaf.dtype) if hasattr(leaf, "dtype") else arr)
    restored = jax.tree_util.tree_unflatten(treedef, out)
    if ring_adapt is not None:
        k_t, _, legacy, dp = ring_adapt
        ring = restored["inbox"]
        if legacy:
            # the PR-2 inbox always mixed, so it restores as a VALID slot;
            # its dispatch counter resumes from the manifest step (one mix
            # per step, so t == step on the staleness-1 runtime)
            ring = {"slots": (ring,),
                    "valid": np.ones((dp, 1), np.float32),
                    "t": np.asarray(int(manifest.get("step") or 0),
                                    np.int32)}
        restored = dict(restored, inbox=_adapt_ring(ring, k_t))
    if ring_reset:
        rest_tpl = {k: v for k, v in packed_template.items() if k != "inbox"}
        out = _pack_like(rest_tpl, restored)
        tpl_ring = packed_template["inbox"]
        out["inbox"] = {
            "slots": tpl_ring["slots"],
            "valid": np.zeros(np.shape(tpl_ring["valid"]), np.float32),
            "t": np.asarray(int(manifest.get("step") or 0), np.int32),
        }
        return out, manifest
    return _pack_like(packed_template, restored), manifest
