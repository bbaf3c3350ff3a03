"""Quantized checkpoint format: COMQ codes packed to their bit width.

A quantized model checkpoint stores, per QTensor: packed codes (2-bit:
four per byte, 3/4-bit: two per byte, 5..8-bit: one per byte), f32 scales
and int32 zero-points — 4.25 bits/param at b=4 vs 16 for bf16, 2.25 at
b=2 (see DESIGN.md §6 for the bytes-per-param table). The pack width
comes from the QTensor's recorded `bits` (per-leaf mixed-precision
policies make this vary leaf-to-leaf); code values are never inspected.
`pack_tree`/`unpack_tree` convert between the runtime QTensor pytree and
the storage form; CheckpointManager handles the IO. `policy_extra` builds
the checkpoint `extra` metadata that records which policy produced the
codes, so a served checkpoint is self-describing.
"""
from __future__ import annotations

import os
import pickle
import warnings
import zlib
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.pipeline import is_qtensor, qtensor_bits
from repro.core.quantizer import pack_codes, unpack_codes


def pack_tree(tree):
    def walk(node):
        if is_qtensor(node):
            bits = qtensor_bits(node)
            codes = node["codes"]
            packed, cpb = pack_codes(codes, bits)
            out = dict(node)
            if cpb > 1:
                out["codes"] = packed
                out["packed_cpb"] = cpb
                out["unpacked_last"] = codes.shape[-1]
                if cpb == 2:
                    # back-compat alias for pre-policy readers
                    out["packed4"] = True
            return out
        if isinstance(node, dict):
            return {k: walk(v) for k, v in node.items()}
        if isinstance(node, (list, tuple)):
            return type(node)(walk(v) for v in node)
        return node
    return walk(tree)


def unpack_tree(tree):
    def walk(node):
        if is_qtensor(node):
            out = dict(node)
            cpb = out.pop("packed_cpb", None)
            if cpb is None and out.get("packed4"):
                cpb = 2            # pre-policy checkpoint
            out.pop("packed4", None)
            if cpb:
                out["codes"] = unpack_codes(node["codes"], int(cpb))
                out.pop("unpacked_last", None)
            if "bits" not in out:
                # pre-policy checkpoint: backfill the width its storage
                # implies (nibble-packed => 4) so a re-pack or the packed
                # serving path keeps the original density instead of
                # defaulting to one code per byte
                out["bits"] = 4 if cpb == 2 else 8
            return out
        if isinstance(node, dict):
            return {k: walk(v) for k, v in node.items()}
        if isinstance(node, (list, tuple)):
            return type(node)(walk(v) for v in node)
        return node
    return walk(tree)


def policy_extra(policy=None, arch: Optional[str] = None,
                 **kw) -> Dict[str, Any]:
    """Checkpoint `extra` metadata for a quantized save: the arch plus the
    serialized QuantPolicy (core.policy.policy_to_dict) so a restore can
    rebuild the exact per-leaf bit assignment without re-measuring."""
    out: Dict[str, Any] = dict(kw)
    if arch is not None:
        out["arch"] = arch
    if policy is not None:
        from repro.core.policy import as_policy, policy_to_dict
        out["policy"] = policy_to_dict(as_policy(policy))
    return out


def restore_policy(extra: Dict[str, Any]):
    """Inverse of policy_extra: the QuantPolicy a checkpoint was solved
    under, or None for pre-policy checkpoints."""
    if not extra or "policy" not in extra:
        return None
    from repro.core.policy import policy_from_dict
    return policy_from_dict(extra["policy"])


def strip_for_serving(qparams):
    """Drop the stacked dense copies of quantized layers from a
    `quantize_model` output — the on-disk checkpoint form (4.25 bits/param
    instead of carrying both the codes *and* the superseded dense stack).
    Everything serving needs survives: the top-level params and the
    __qlayers__ table, which stores each layer's dense non-quantized
    leaves (norms, biases) alongside its QTensors — for VLM trees the
    per-group "groups" stacks are dropped the same way. `core.
    serving_params` and `core.materialize` both accept the stripped
    form."""
    return {k: v for k, v in qparams.items()
            if k not in ("layers", "groups")}


def tree_bytes(tree) -> int:
    return sum(leaf.size * leaf.dtype.itemsize
               for leaf in jax.tree_util.tree_leaves(tree)
               if hasattr(leaf, "size"))


# -- packed single-file checkpoints (launch/serve --save/--load-quantized) ---

PACKED_FORMAT = "comq-packed-qt"
# 2: planar bit-field layout (quantizer.pack_int4/pack_int2 — field f of
# a byte holds the f-th slice of the last dim). Version-1 and headerless
# files packed adjacent codes into one byte; their packed leaves would
# decode to the wrong codes, so they are refused (re-quantize instead).
PACKED_VERSION = 2


def _has_packed_leaf(tree) -> bool:
    if is_qtensor(tree):
        return bool(tree.get("packed_cpb") or tree.get("packed4"))
    if isinstance(tree, dict):
        return any(_has_packed_leaf(v) for v in tree.values())
    if isinstance(tree, (list, tuple)):
        return any(_has_packed_leaf(v) for v in tree)
    return False


def _refuse_old_layout(path: str, blob, what: str) -> None:
    if _has_packed_leaf(blob.get("tree")):
        raise PackedCkptError(
            f"{path}: {what} holds codes in the interleaved pre-version-"
            f"{PACKED_VERSION} packing, which this reader would decode "
            "wrong — re-quantize to write the planar layout")


class PackedCkptError(RuntimeError):
    """A packed quantized checkpoint failed validation (truncated file,
    checksum mismatch, wrong format/version) — raised with a clear
    message instead of the deep unflatten crash a blind pickle load
    produced."""


def save_packed_ckpt(path: str, tree, fault_cb=None, **meta) -> int:
    """Write a packed quantized tree (host arrays) as a self-describing
    single file: a format/version header plus a crc32 over the pickled
    payload, so a truncated or corrupted file fails loudly at load.

    The write is atomic and durable — tmp + flush + fsync + os.replace —
    so a kill at any instant leaves either the old file or the new one,
    never a torn write. `fault_cb` (fault injection) runs between the
    durable tmp write and the rename: exactly the torn-write window the
    quantization journal's durability ordering must survive. Returns the
    payload crc32 (what the journal records per spilled leaf)."""
    payload = pickle.dumps({"tree": tree, **meta})
    crc = zlib.crc32(payload)
    blob = {"format": PACKED_FORMAT, "version": PACKED_VERSION,
            "crc32": crc, "payload": payload}
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        pickle.dump(blob, f)
        f.flush()
        os.fsync(f.fileno())
    if fault_cb is not None:
        fault_cb()
    os.replace(tmp, path)
    return crc


def load_packed_ckpt(path: str, expect_crc: Optional[int] = None
                     ) -> Dict[str, Any]:
    """Load + validate a packed checkpoint; returns the payload dict
    ({"tree": ..., **meta}). Pre-header files (a bare {"tree", "bits",
    "arch"} pickle) still load, with a warning — re-save to upgrade.
    `expect_crc` (the quantization journal's per-leaf record) must match
    the header crc exactly — a valid-but-different file is as wrong as a
    corrupt one when resuming a run."""
    try:
        with open(path, "rb") as f:
            blob = pickle.load(f)
    except (pickle.UnpicklingError, EOFError, AttributeError) as e:
        raise PackedCkptError(
            f"{path}: not a readable packed checkpoint — the file is "
            f"truncated or corrupt ({type(e).__name__}: {e})") from e
    if not isinstance(blob, dict):
        raise PackedCkptError(f"{path}: unexpected object of type "
                              f"{type(blob).__name__}")
    if "format" not in blob:
        if "tree" not in blob:
            raise PackedCkptError(
                f"{path}: neither a headered packed checkpoint nor a "
                "legacy tree blob (keys: " + ", ".join(sorted(blob)) + ")")
        if expect_crc is not None:
            raise PackedCkptError(
                f"{path}: legacy headerless checkpoint has no checksum "
                f"to match the expected {expect_crc:#010x}")
        _refuse_old_layout(path, blob, "legacy headerless checkpoint")
        warnings.warn(f"{path}: legacy headerless packed checkpoint — "
                      "no checksum to verify; re-save to upgrade",
                      stacklevel=2)
        return blob
    if blob["format"] != PACKED_FORMAT:
        raise PackedCkptError(f"{path}: format {blob['format']!r} is not "
                              f"{PACKED_FORMAT!r}")
    if blob["version"] > PACKED_VERSION:
        raise PackedCkptError(
            f"{path}: version {blob['version']} is newer than this "
            f"reader ({PACKED_VERSION}) — upgrade the code")
    payload = blob["payload"]
    crc = zlib.crc32(payload)
    if crc != blob["crc32"]:
        raise PackedCkptError(
            f"{path}: checksum mismatch (stored {blob['crc32']:#010x}, "
            f"computed {crc:#010x}) — the checkpoint is corrupt")
    if expect_crc is not None and crc != int(expect_crc):
        raise PackedCkptError(
            f"{path}: checksum {crc:#010x} does not match the journaled "
            f"{int(expect_crc):#010x} — the spill was replaced or the "
            "journal belongs to a different run")
    out = pickle.loads(payload)
    if blob["version"] < PACKED_VERSION:
        _refuse_old_layout(path, out, f"version-{blob['version']} file")
    return out
