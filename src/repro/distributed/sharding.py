"""Logical-axis sharding rules with divisibility-aware fallback.

Every tensor dimension in the framework is named by a *logical axis*
("batch", "ffn", "q_heads", ...). A rules table maps each logical axis to
a *priority list* of mesh-axis tuples; :func:`shard_fit` picks the first
candidate whose mesh axes (a) exist in the mesh, (b) are not already used
by another dimension of the same tensor, and (c) divide the dimension
size evenly. This is what lets all 40 (arch × shape) cells produce legal
NamedShardings from one table — decode batches of 128, 25-head hybrids,
odd vocab sizes and 8-expert MoEs all degrade gracefully instead of
failing the dry-run.

The production meshes (launch/mesh.py) are
    (16, 16)      ('data', 'model')            — one v5e-256 pod
    (2, 16, 16)   ('pod', 'data', 'model')     — two pods
and the rules below express: batch over (pod×data); TP over model for
heads/ffn/vocab; experts over data (EP); FSDP params over data; sequence
over model as the CP fallback when a head count can't split 16 ways.
"""
from __future__ import annotations

import math
from typing import Optional, Sequence

import jax
from jax.sharding import AbstractMesh, Mesh, NamedSharding, PartitionSpec

Candidate = Optional[tuple]
Rules = dict[str, Sequence[Candidate]]


DEFAULT_RULES: Rules = {
    # -- activations ---------------------------------------------------------
    "batch":      [("pod", "data"), ("data",), None],
    "seq":        [None],                       # replicated by default
    "seq_sp":     [("model",), None],           # SP: residual seq over model
    "seq_shard":  [("model",), None],           # CP: sequence over model
    "act_embed":  [None],                       # residual stays replicated
    # -- attention -----------------------------------------------------------
    "q_heads":    [("model",), None],
    "kv_heads":   [("model",), None],
    "head_dim":   [None],
    "cache_seq":  [("model",), None],           # KV cache seq over TP, where
                                                # kv_heads does not take it
    # -- params --------------------------------------------------------------
    "embed":      [("data",), None],            # FSDP dim (gathered per layer)
    "embed_nofsdp": [None],
    "ffn":        [("model",), None],
    "vocab":      [("model",), None],
    "vocab_tbl":  [None],                       # embed-gather-local table
    "embed_tbl":  [("model",), None],
    "experts":    [("data",), None],            # EP
    "expert_ffn": [("model",), None],
    "layers":     [None],                       # scan-stacked layer axis
    # -- ssm ------------------------------------------------------------------
    "ssm_heads":  [("model",), None],
    "ssm_inner":  [("model",), None],
    "ssm_state":  [None],
    "conv_dim":   [("model",), None],
}


def _mesh_axis_sizes(mesh: Mesh) -> dict[str, int]:
    return dict(mesh.shape)


def shard_fit(dim_size: int, candidates: Sequence[Candidate], mesh: Mesh,
              used: set[str]) -> Optional[tuple]:
    """First candidate that exists in the mesh, is unused, and divides."""
    sizes = _mesh_axis_sizes(mesh)
    for cand in candidates:
        if cand is None:
            return None
        if not all(a in sizes for a in cand):
            continue
        if any(a in used for a in cand):
            continue
        prod = math.prod(sizes[a] for a in cand)
        if dim_size % prod == 0:
            return tuple(cand)
    return None


def logical_spec(logical_dims: Sequence[Optional[str]], shape: Sequence[int],
                 mesh: Mesh, rules: Optional[Rules] = None) -> PartitionSpec:
    """PartitionSpec for a tensor whose dims carry logical names."""
    rules = {**DEFAULT_RULES, **(rules or {})}
    if len(logical_dims) != len(shape):
        raise ValueError(f"logical dims {logical_dims} rank != shape {shape}")
    used: set[str] = set()
    out = []
    for name, size in zip(logical_dims, shape):
        if name is None:
            out.append(None)
            continue
        if name not in rules:
            raise KeyError(f"no sharding rule for logical axis {name!r}")
        axes = shard_fit(size, rules[name], mesh, used)
        if axes is None:
            out.append(None)
        else:
            used.update(axes)
            out.append(axes if len(axes) > 1 else axes[0])
    return PartitionSpec(*out)


def logical_sharding(logical_dims: Sequence[Optional[str]],
                     shape: Sequence[int], mesh: Mesh,
                     rules: Optional[Rules] = None) -> NamedSharding:
    return NamedSharding(mesh, logical_spec(logical_dims, shape, mesh, rules))


def tree_shardings(tree_logical, tree_shapes, mesh: Mesh,
                   rules: Optional[Rules] = None):
    """Map matching pytrees of logical-dim tuples and ShapeDtypeStructs to
    a pytree of NamedShardings (the jit in_shardings/out_shardings input)."""
    return jax.tree.map(
        lambda names, sds: logical_sharding(names, sds.shape, mesh, rules),
        tree_logical, tree_shapes,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x),
    )


def constrain(x: jax.Array, logical_dims: Sequence[Optional[str]],
              mesh: Optional[Mesh] = None, rules: Optional[Rules] = None):
    """with_sharding_constraint by logical names (no-op outside a mesh)."""
    mesh = mesh or current_mesh()
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, logical_sharding(logical_dims, x.shape, mesh, rules))


def current_mesh() -> Optional[AbstractMesh]:
    """The mesh set by ``jax.set_mesh`` (None outside one). Abstract, so
    it answers inside ``jit`` as well as outside."""
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m
