"""Logical-axis sharding rules with divisibility fallback.

Parameters and activations are annotated with *logical* axis names
("embed", "heads", "mlp", ...).  A :class:`AxisRules` maps logical names to
mesh axis names.  ``shard_hint`` applies a ``with_sharding_constraint`` but
silently drops any mesh axis that does not divide the corresponding dim —
this single mechanism is what lets all 40 (arch x shape) dry-run cells lower
on the fixed production meshes without per-cell hand tuning (e.g. gemma3's
4 query heads simply fall back to replicated on a 16-way "model" axis while
its mlp dim still tensor-shards).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Mapping, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# ---------------------------------------------------------------------------
# Logical axis vocabulary
# ---------------------------------------------------------------------------
# batch      global batch dim of activations
# seq        sequence dim of activations inside a block (replicated)
# sp_seq     sequence dim of the residual stream *between* blocks
#            (Megatron-style sequence parallelism: sharded over "model")
# kv_seq     sequence dim of KV caches / KV activations (context parallel)
# embed      model width d_model (FSDP axis for weights)
# heads      query heads;  kv_heads  KV heads;  head  head_dim
# mlp        FFN hidden;   vocab     vocabulary
# expert     MoE expert dim (EP);  expert_mlp  FFN hidden inside EP experts
# ssm_heads  Mamba2 heads; ssm_state  SSD state dim; conv  conv channels
# layers     stacked-scan layer dim (never sharded)

DEFAULT_RULES: dict[str, tuple[str, ...]] = {
    "batch": ("pod", "data"),
    "seq": (),
    "sp_seq": ("model",),
    "kv_seq": ("model",),
    "embed": ("data",),  # FSDP within a pod; pod axis only sees grad AR
    "heads": ("model",),
    "kv_heads": ("model",),
    "head": (),
    "mlp": ("model",),
    "vocab": ("model",),
    "expert": ("model",),
    "expert_mlp": (),
    "ssm_heads": ("model",),
    "ssm_state": (),
    "conv": ("model",),
    "layers": (),
    "stats": (),
}


@dataclasses.dataclass(frozen=True)
class AxisRules:
    """Maps logical axis names -> candidate mesh axes (in priority order)."""

    rules: Mapping[str, tuple[str, ...]]

    def mesh_axes(self, logical: str | None) -> tuple[str, ...]:
        if logical is None:
            return ()
        if logical not in self.rules:
            raise KeyError(f"unknown logical axis {logical!r}")
        return tuple(self.rules[logical])

    def replace(self, **updates: tuple[str, ...]) -> "AxisRules":
        merged = dict(self.rules)
        merged.update(updates)
        return AxisRules(merged)


def default_rules(**overrides: tuple[str, ...]) -> AxisRules:
    return AxisRules(dict(DEFAULT_RULES)).replace(**overrides)


# ---------------------------------------------------------------------------
# Spec resolution with divisibility fallback
# ---------------------------------------------------------------------------

def _axis_size(mesh: Mesh, name: str) -> int:
    return int(mesh.shape[name]) if name in mesh.shape else 1


def resolve_spec(
    mesh: Mesh,
    rules: AxisRules,
    logical_axes: Sequence[str | None],
    shape: Sequence[int],
) -> P:
    """Logical axes -> PartitionSpec, dropping non-dividing mesh axes.

    Each mesh axis may be used at most once across the whole spec (a
    PartitionSpec invariant); earlier dims win.
    """
    if len(logical_axes) != len(shape):
        raise ValueError(
            f"logical axes {logical_axes} do not match shape {shape}")
    used: set[str] = set()
    parts: list[Any] = []
    for logical, dim in zip(logical_axes, shape):
        candidates = [a for a in rules.mesh_axes(logical)
                      if a not in used and a in mesh.shape]
        chosen: list[str] = []
        size = 1
        for axis in candidates:
            nxt = size * _axis_size(mesh, axis)
            if nxt == 0 or dim % nxt != 0:
                continue
            chosen.append(axis)
            size = nxt
        used.update(chosen)
        if not chosen:
            parts.append(None)
        elif len(chosen) == 1 and len(candidates) == 1:
            # a product rule keeps tuple form even when one factor fits
            # (identical semantics; stable across PartitionSpec equality
            # behaviour of different jax versions)
            parts.append(chosen[0])
        else:
            parts.append(tuple(chosen))
    return P(*parts)


def named_sharding(
    mesh: Mesh,
    rules: AxisRules,
    logical_axes: Sequence[str | None],
    shape: Sequence[int],
) -> NamedSharding:
    return NamedSharding(mesh, resolve_spec(mesh, rules, logical_axes, shape))


class ShardingCtx:
    """Carries (mesh, rules) through model code; used by shard_hint."""

    def __init__(self, mesh: Mesh, rules: AxisRules | None = None):
        self.mesh = mesh
        self.rules = rules or default_rules()

    def spec(self, logical_axes: Sequence[str | None], shape: Sequence[int]) -> P:
        return resolve_spec(self.mesh, self.rules, logical_axes, shape)

    def hint(self, x: jax.Array, *logical_axes: str | None) -> jax.Array:
        """with_sharding_constraint with divisibility fallback."""
        spec = self.spec(logical_axes, x.shape)
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, spec))


# ---------------------------------------------------------------------------
# Param-tree sharding: params carry a parallel tree of logical-axis tuples
# ---------------------------------------------------------------------------

def tree_shardings(mesh: Mesh, rules: AxisRules, params: Any, specs: Any):
    """Build a NamedSharding pytree for ``params`` from logical ``specs``.

    ``specs`` mirrors ``params`` but leaves are tuples of logical names (or
    None for replicated).  Works on ShapeDtypeStructs or concrete arrays.
    """

    def one(p, s):
        if s is None:
            return NamedSharding(mesh, P())
        return named_sharding(mesh, rules, s, p.shape)

    return jax.tree.map(one, params, specs,
                        is_leaf=lambda x: x is None or isinstance(x, tuple))


def tree_pspecs(mesh: Mesh, rules: AxisRules, params: Any, specs: Any):
    def one(p, s):
        if s is None:
            return P()
        return resolve_spec(mesh, rules, s, p.shape)

    return jax.tree.map(one, params, specs,
                        is_leaf=lambda x: x is None or isinstance(x, tuple))


def bytes_per_device(mesh: Mesh, rules: AxisRules, params: Any, specs: Any) -> int:
    """Estimate parameter bytes resident per device under the rules."""
    total = 0
    flat_p, treedef = jax.tree.flatten(params)
    flat_s = treedef.flatten_up_to(specs)
    for p, s in zip(flat_p, flat_s):
        shard = 1
        if s is not None:
            spec = resolve_spec(mesh, rules, s, p.shape)
            for entry in spec:
                if entry is None:
                    continue
                axes = entry if isinstance(entry, tuple) else (entry,)
                for a in axes:
                    shard *= _axis_size(mesh, a)
        total += int(np.prod(p.shape)) * p.dtype.itemsize // shard
    return total
