"""Logical-axis sharding rules (DP / FSDP / TP / EP / SP).

Params and activations are annotated with *logical* axis names; rules resolve
them to physical mesh axes per execution mode.  This keeps model code mesh-
agnostic (the paper's "record with the exact hardware" requirement becomes:
recordings embed the resolved mesh; replay validates the fingerprint).

Modes
-----
train:  batch/fsdp -> ('pod','data');  heads/ffn/vocab/experts -> 'model'
        (2D weight sharding: FSDP over the data axes + TP over model — ZeRO-1
        optimizer state is sharded the same way.)
serve:  TP-dominant — weights sharded over 'model' only (no per-step weight
        all-gathers on the latency path); KV cache sequence-sharded over
        'model' (sequence parallelism) so GQA archs with few KV heads still
        scale to TP=16; MoE expert weights additionally sharded over the data
        axes on d_model (2D weight-stationary) so 8x22B fits.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXES = ("pod", "data")  # flattened DP axes (pod may be absent)


def _dp(mesh_axes: Tuple[str, ...]):
    present = tuple(a for a in DATA_AXES if a in mesh_axes)
    return present if len(present) > 1 else (present[0] if present else None)


def rules_for(mode: str, mesh_axes: Tuple[str, ...], fsdp: bool = True) -> dict:
    dp = _dp(mesh_axes)
    tp = "model" if "model" in mesh_axes else None
    common = {
        "batch": dp, "seq": None, "embed": None, "heads": tp, "kv_heads": tp,
        "head_dim": None, "ffn": tp, "vocab": tp, "experts": tp,
        "expert_ffn": tp, "kv_lora": None, "ssm_inner": tp, "ssm_heads": tp,
        "ssm_state": None, "layers": None, "conv": None, "norm": None,
        "stack": None,
    }
    if mode == "train":
        common["fsdp"] = dp if fsdp else None      # 2nd weight dim
        common["seq"] = tp                         # Megatron-style SP: the
        # residual stream between blocks is sequence-sharded; attention/MLP
        # internals are head/ffn-sharded (XLA inserts the AG/RS pairs).
        # Cuts saved-activation memory by TP degree at equal collective cost
        # to pure-TP's per-layer all-reduces.
        common["kv_seq"] = None                    # KV == activations in train
        common["expert_embed"] = dp                # MoE 2D weight sharding
    elif mode == "train_zero":
        # ZeRO-3 pure data parallelism: every mesh axis is batch DP; weights
        # (and optimizer state) are sharded over ALL axes and gathered per
        # layer.  No activation collectives at all — the right schedule when
        # per-layer weight bytes << per-layer activation bytes (narrow
        # models / large batches).  Hillclimbed in EXPERIMENTS.md §Perf.
        allaxes = tuple(a for a in ("pod", "data", "model") if a in mesh_axes)
        common.update({
            "batch": allaxes, "seq": None, "heads": None, "kv_heads": None,
            "head_dim": None, "ffn": None, "expert_ffn": None,
            "ssm_inner": None, "ssm_heads": None,
            "fsdp": allaxes, "expert_embed": allaxes, "kv_seq": None,
        })
    elif mode == "serve":
        common["fsdp"] = None                      # no weight gathers at decode
        common["kv_seq"] = tp                      # SP: cache seq over model
        common["expert_embed"] = dp                # MoE 2D weight-stationary
    else:
        raise ValueError(f"unknown mode {mode}")
    return common


def spec(axes: Tuple[Optional[str], ...], rules: dict,
         shape: Optional[Tuple[int, ...]] = None,
         mesh_shape: Optional[dict] = None) -> P:
    """Resolve logical axes -> PartitionSpec.

    With ``shape``/``mesh_shape``, any dim whose size is not divisible by
    the mapped mesh-axis product falls back to replication (e.g. kv_heads=2
    cannot shard over model=16; starcoder's 36 q-heads likewise)."""
    parts, used = [], set()
    for i, a in enumerate(axes):
        if a is None:
            parts.append(None)
            continue
        phys = rules.get(a)
        # one physical axis may appear only once in a spec
        key = tuple(phys) if isinstance(phys, tuple) else (phys,)
        if phys is None or any(k in used for k in key):
            parts.append(None)
            continue
        if shape is not None and mesh_shape is not None:
            nshard = 1
            for k in key:
                nshard *= mesh_shape.get(k, 1)
            # prefix fallback: drop trailing axes of a tuple mapping until
            # the dim divides (e.g. batch 256 on ("pod","data","model")=512
            # -> ("pod","data")=32)
            while key and shape[i] % nshard:
                nshard //= mesh_shape.get(key[-1], 1)
                key = key[:-1]
            if not key or shape[i] % nshard:
                parts.append(None)
                continue
            phys = key if len(key) > 1 else key[0]
        used.update(key)
        parts.append(phys)
    return P(*parts)


def shardings_for(axes_tree, abstract_tree, mesh: Mesh, rules: dict):
    """Divisibility-checked NamedShardings for an abstract pytree."""
    mesh_shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    is_ax = lambda x: isinstance(x, tuple) and all(
        isinstance(e, (str, type(None))) for e in x)
    flat_ax = jax.tree.flatten(axes_tree, is_leaf=is_ax)[0]
    flat_ab, treedef = jax.tree.flatten(abstract_tree)
    assert len(flat_ax) == len(flat_ab), (len(flat_ax), len(flat_ab))
    out = [NamedSharding(mesh, spec(a, rules, v.shape, mesh_shape))
           for a, v in zip(flat_ax, flat_ab)]
    return jax.tree.unflatten(treedef, out)


def tree_specs(axes_tree, rules: dict):
    return jax.tree.map(
        lambda ax: spec(ax, rules), axes_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x),
    )


def tree_shardings(axes_tree, mesh: Mesh, rules: dict):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree_specs(axes_tree, rules))


def constrain(x, axes: Tuple[Optional[str], ...], rules: dict):
    """with_sharding_constraint by logical axes (no-op outside jit/mesh).

    Under a mesh, a dim the mapped axes do not divide is replicated, as in
    ``shardings_for``: a batch of 1 on a data-parallel mesh stays whole
    instead of being split unevenly, which would let the partitioner split
    the contractions and all-reduce bf16 partial sums."""
    mesh = jax.sharding.get_abstract_mesh()
    mesh_shape = None if mesh.empty else dict(mesh.shape)
    try:
        return jax.lax.with_sharding_constraint(
            x, spec(axes, rules, x.shape, mesh_shape))
    except (ValueError, RuntimeError):
        return x
