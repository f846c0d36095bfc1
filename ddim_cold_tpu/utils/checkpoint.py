"""Checkpointing — orbax for native state, plus a torch-pickle bridge.

Reproduces the reference's dual-checkpoint behavior (SURVEY.md C20,
multi_gpu_trainer.py:94-106,152-163):

* ``bestloss`` — bare model weights whenever val improves;
* ``lastepoch`` — full training state (epoch, steps, EMA loss, best metric,
  params, optimizer state) every epoch, the resume target.

Native format is orbax (one directory per checkpoint). The legacy ``*.pkl``
bridge converts between torch state_dicts (``blocks.N.attn.qkv.weight``…) and
the Flax param tree so reference checkpoints load here and vice versa; torch
(cpu) is an optional conversion-time dependency only.
"""

from __future__ import annotations

import os
import re
from typing import Any

import jax
import numpy as np


# ---------------------------------------------------------------------------
# torch state_dict ↔ flax params
# ---------------------------------------------------------------------------

def _strip_ddp_prefix(state_dict: dict) -> dict:
    """lastepoch state_dicts carry DDP's 'module.' prefix (multi_gpu_trainer.py:160)."""
    return {re.sub(r"^module\.", "", k): v for k, v in state_dict.items()}


def flax_from_torch_state_dict(state_dict: dict, patch_size: int) -> dict:
    """Map a reference torch state_dict to the DiffusionViT param tree.

    Layout transforms: Linear ``W (out,in)`` → kernel ``(in,out)``; the patch
    Conv2d ``W (E,C,p,p)`` → Dense kernel ``(p²C, E)`` with (row, col, chan)
    patch-feature order (models/vit.py PatchEmbed docstring); LayerNorm
    weight → scale.
    """
    sd = {k: np.asarray(v.detach().cpu().numpy() if hasattr(v, "detach") else v,
                        dtype=np.float32)
          for k, v in _strip_ddp_prefix(state_dict).items()}
    p = patch_size
    params: dict[str, Any] = {
        "cls_token": sd["cls_token"],
        # pos_embed is absent for use_sincos_pos models (fixed table, not a
        # param) — tolerated in both directions.
        **({"pos_embed": sd["pos_embed"]} if "pos_embed" in sd else {}),
        "time_embed": {"embedding": sd["time_embed.weight"]},
        "norm": {"scale": sd["norm.weight"], "bias": sd["norm.bias"]},
        "head": {"kernel": sd["head.weight"].T, "bias": sd["head.bias"]},
    }
    w = sd["patch_embed.proj.weight"]  # (E, C, p, p)
    e = w.shape[0]
    params["patch_embed"] = {
        "proj": {
            "kernel": w.transpose(2, 3, 1, 0).reshape(p * p * w.shape[1], e),
            "bias": sd["patch_embed.proj.bias"],
        }
    }
    depth = 1 + max(
        int(m.group(1)) for k in sd if (m := re.match(r"blocks\.(\d+)\.", k))
    )
    for i in range(depth):
        b = f"blocks.{i}."
        params[f"blocks_{i}"] = {
            "norm1": {"scale": sd[b + "norm1.weight"], "bias": sd[b + "norm1.bias"]},
            "norm2": {"scale": sd[b + "norm2.weight"], "bias": sd[b + "norm2.bias"]},
            "attn": {
                "qkv": {"kernel": sd[b + "attn.qkv.weight"].T,
                        **({"bias": sd[b + "attn.qkv.bias"]}
                           if b + "attn.qkv.bias" in sd else {})},
                "proj": {"kernel": sd[b + "attn.proj.weight"].T,
                         "bias": sd[b + "attn.proj.bias"]},
            },
            "mlp": {
                "fc1": {"kernel": sd[b + "mlp.fc1.weight"].T, "bias": sd[b + "mlp.fc1.bias"]},
                "fc2": {"kernel": sd[b + "mlp.fc2.weight"].T, "bias": sd[b + "mlp.fc2.bias"]},
            },
        }
    return params


def stack_block_params(params: dict) -> dict:
    """Unrolled ``blocks_0..blocks_{d-1}`` subtrees → one ``blocks`` subtree
    with a leading layer axis (the ``scan_blocks=True`` model's layout)."""
    depth = 0
    while f"blocks_{depth}" in params:
        depth += 1
    if depth == 0:
        return dict(params)
    out = {k: v for k, v in params.items() if not re.match(r"^blocks_\d+$", k)}
    out["blocks"] = jax.tree.map(
        lambda *leaves: np.stack([np.asarray(l) for l in leaves]),
        *(params[f"blocks_{i}"] for i in range(depth)),
    )
    return out


def unstack_block_params(params: dict) -> dict:
    """Inverse of ``stack_block_params``: split the stacked ``blocks`` subtree
    back into per-layer ``blocks_{i}`` trees."""
    if "blocks" not in params:
        return dict(params)
    out = {k: v for k, v in params.items() if k != "blocks"}
    stacked = params["blocks"]
    depth = jax.tree.leaves(stacked)[0].shape[0]
    for i in range(depth):
        out[f"blocks_{i}"] = jax.tree.map(lambda a, _i=i: np.asarray(a[_i]), stacked)
    return out


def torch_state_dict_from_flax(params, patch_size: int) -> dict:
    """Inverse of ``flax_from_torch_state_dict`` (numpy arrays, torch-key
    names). Accepts both block layouts — a stacked ``blocks`` subtree
    (scan_blocks models) is unstacked first."""
    params = unstack_block_params(params)
    if any("moe" in blk for blk in params.values() if isinstance(blk, dict)):
        raise ValueError(
            "MoE params (num_experts > 1) have no reference torch layout — "
            "the torch-pkl bridge covers the reference's dense architecture "
            "only; use the orbax checkpoints for MoE runs")
    g = lambda *ks: np.asarray(_dig(params, ks))
    p = patch_size
    pk = g("patch_embed", "proj", "kernel")  # (p²C, E)
    e = pk.shape[1]
    c = pk.shape[0] // (p * p)
    sd = {
        "cls_token": g("cls_token"),
        **({"pos_embed": g("pos_embed")} if "pos_embed" in params else {}),
        "time_embed.weight": g("time_embed", "embedding"),
        "patch_embed.proj.weight": pk.reshape(p, p, c, e).transpose(3, 2, 0, 1),
        "patch_embed.proj.bias": g("patch_embed", "proj", "bias"),
        "norm.weight": g("norm", "scale"),
        "norm.bias": g("norm", "bias"),
        "head.weight": g("head", "kernel").T,
        "head.bias": g("head", "bias"),
    }
    i = 0
    while f"blocks_{i}" in params:
        b = f"blocks_{i}"
        sd[f"blocks.{i}.norm1.weight"] = g(b, "norm1", "scale")
        sd[f"blocks.{i}.norm1.bias"] = g(b, "norm1", "bias")
        sd[f"blocks.{i}.norm2.weight"] = g(b, "norm2", "scale")
        sd[f"blocks.{i}.norm2.bias"] = g(b, "norm2", "bias")
        sd[f"blocks.{i}.attn.qkv.weight"] = g(b, "attn", "qkv", "kernel").T
        if "bias" in params[b]["attn"]["qkv"]:
            sd[f"blocks.{i}.attn.qkv.bias"] = g(b, "attn", "qkv", "bias")
        sd[f"blocks.{i}.attn.proj.weight"] = g(b, "attn", "proj", "kernel").T
        sd[f"blocks.{i}.attn.proj.bias"] = g(b, "attn", "proj", "bias")
        sd[f"blocks.{i}.mlp.fc1.weight"] = g(b, "mlp", "fc1", "kernel").T
        sd[f"blocks.{i}.mlp.fc1.bias"] = g(b, "mlp", "fc1", "bias")
        sd[f"blocks.{i}.mlp.fc2.weight"] = g(b, "mlp", "fc2", "kernel").T
        sd[f"blocks.{i}.mlp.fc2.bias"] = g(b, "mlp", "fc2", "bias")
        i += 1
    return sd


def _dig(tree, keys):
    for k in keys:
        tree = tree[k]
    return tree


def load_torch_pkl(path: str, patch_size: int) -> dict:
    """Load a reference ``*.pkl`` (bare state_dict or the lastepoch dict) into
    a Flax param tree. Uses torch when importable; otherwise falls back to the
    torch-free zip-format reader (:mod:`.torch_pickle`) — a TPU host needs no
    torch install to ingest reference checkpoints (parity pinned by
    tests/test_torch_pickle.py::test_load_torch_pkl_falls_back_without_torch).
    """
    try:
        # only the IMPORT selects the fallback: an ImportError raised inside
        # torch.load itself (e.g. a module named by the pickle stream missing
        # on this host) is a real error that must surface, not trigger a
        # silent re-parse that fails elsewhere
        import torch
    except ImportError:
        from ddim_cold_tpu.utils import torch_pickle

        obj = torch_pickle.load(path)
    else:
        obj = torch.load(path, map_location="cpu", weights_only=False)
    if isinstance(obj, dict) and "state_dict" in obj:
        obj = obj["state_dict"]
    return flax_from_torch_state_dict(obj, patch_size)


def save_torch_pkl(params, path: str, patch_size: int) -> None:
    """Write params as a torch state_dict pickle a reference user can load.
    Torch-less hosts fall back to the native zip-format writer
    (:func:`.torch_pickle.save`) — real ``torch.load`` reads its output
    (parity pinned by tests/test_torch_pickle.py)."""
    sd_np = {k: np.array(v, order="C")
             for k, v in torch_state_dict_from_flax(params, patch_size).items()}
    try:
        import torch
    except ImportError:
        from ddim_cold_tpu.utils import torch_pickle

        torch_pickle.save(sd_np, path)  # write-then-rename internally
        return
    # same atomicity as the native writer: torch.save writes the destination
    # directly, and a crash mid-write would leave a truncated file that
    # poisons every later warm start
    from ddim_cold_tpu.utils.torch_pickle import atomic_replace

    with atomic_replace(path) as tmp:
        torch.save({k: torch.from_numpy(v) for k, v in sd_np.items()}, tmp)


# ---------------------------------------------------------------------------
# orbax train-state checkpoints
# ---------------------------------------------------------------------------

def _to_host(tree):
    def conv(x):
        # multi-host shards aren't host-materializable; orbax writes global
        # jax.Arrays distributedly, so pass them through untouched.
        if hasattr(x, "is_fully_addressable") and not x.is_fully_addressable:
            return x
        return np.asarray(x)

    return jax.tree.map(conv, tree)


def save_checkpoint(path: str, tree) -> None:
    """Save a pytree checkpoint directory (orbax).

    Single-host saves write beside the destination and swap in with two
    rename metadata ops — ``force=True`` straight onto ``path`` would delete
    the PREVIOUS checkpoint before the (possibly multi-second) write, so a
    crash mid-write would lose the only resume point. Multi-host
    saves go directly through orbax's own collective commit protocol (a
    per-process directory swap on a shared fs would race).

    The ``ckpt.save`` fault site fires at every crash window of the
    single-host sequence (pre-write / post-write / mid-swap / post-swap) —
    the crash-window tests kill the save at each and assert a loadable
    checkpoint always survives (``recover_swap`` + restore).
    """
    import shutil

    import orbax.checkpoint as ocp

    from ddim_cold_tpu.utils import faults

    path = os.path.abspath(path)
    ckptr = ocp.PyTreeCheckpointer()
    if jax.process_count() > 1:
        ckptr.save(path, _to_host(tree), force=True)
        return
    recover_swap(path)
    tmp, old = path + ".writing", path + ".old"
    for d in (tmp, old):  # true leftovers (post-recovery) from a crashed save
        if os.path.isdir(d):
            shutil.rmtree(d)
    faults.fire("ckpt.save", tag="window:pre-write|")
    ckptr.save(tmp, _to_host(tree), force=True)
    faults.fire("ckpt.save", tag="window:post-write|")
    if os.path.isdir(path):
        os.rename(path, old)
    faults.fire("ckpt.save", tag="window:mid-swap|")
    os.rename(tmp, path)
    faults.fire("ckpt.save", tag="window:post-swap|")
    if os.path.isdir(old):
        shutil.rmtree(old)


def recover_swap(path: str) -> None:
    """Heal a crash between the two swap renames in :func:`save_checkpoint`:
    a lone ``<path>.old`` with no ``<path>`` IS the last good checkpoint —
    move it back rather than ever treating it as deletable garbage.

    Only the DIRECTORY OWNER (the trainer, on resume/warm-start and before
    each save) may call this — a read-only consumer healing concurrently
    with a writer's in-progress swap would race its second rename.
    Multi-host: process 0 renames, everyone barriers."""
    path = os.path.abspath(path)
    old = path + ".old"
    if jax.process_count() > 1:
        if jax.process_index() == 0 and not os.path.isdir(path) and os.path.isdir(old):
            os.rename(old, path)
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices("ddim_cold_ckpt_recover")
        return
    if not os.path.isdir(path) and os.path.isdir(old):
        os.rename(old, path)


def restore_checkpoint(path: str, target=None):
    """Restore a pytree checkpoint; ``target`` fixes structure/dtypes.

    numpy targets restore as host arrays regardless of the topology that
    saved them (a checkpoint written by an N-process run names devices a
    different world doesn't have — the restore args below override those
    saved shardings); jax.Array targets restore sharded per their sharding.
    """
    import orbax.checkpoint as ocp

    ckptr = ocp.PyTreeCheckpointer()
    if target is None:
        return ckptr.restore(os.path.abspath(path))
    item = _to_host(target)

    def restore_arg(x):
        if isinstance(x, jax.Array):  # non-addressable multi-host leaf
            return ocp.ArrayRestoreArgs(sharding=x.sharding,
                                        global_shape=x.shape, dtype=x.dtype)
        if isinstance(x, np.ndarray):
            return ocp.RestoreArgs(restore_type=np.ndarray, dtype=x.dtype)
        return ocp.RestoreArgs()

    return ckptr.restore(
        os.path.abspath(path),
        args=ocp.args.PyTreeRestore(
            item=item, restore_args=jax.tree.map(restore_arg, item)),
    )
