"""Logical-axis sharding: map logical axis names to mesh axes.

Model code annotates every parameter and key activation with *logical* axis
names ("vocab", "heads", "ffn", "expert", "batch", ...). A rule table maps
logical names to physical mesh axes; `tree_shardings` converts a pytree of
logical-axis tuples into a pytree of NamedShardings for pjit in/out specs.

Changing a sharding strategy (e.g. for a §Perf experiment) means swapping the
rule table, not touching model code.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Baseline rule table: tensor parallelism over "model", batch data-parallel
# over ("pod","data") when a pod axis exists.
DEFAULT_RULES: Dict[str, object] = {
    "batch": ("pod", "data"),  # activations' batch dim
    "actors": ("pod", "data"),  # async runner's actor-replica lane axis
    "seq": None,
    "vocab": "model",
    "heads": "model",
    "kv_heads": "model",
    "kv_seq": "model",  # flash-decoding cache sharding (opt-in via cache axes)
    "head_dim": None,
    "embed": None,
    "ffn": "model",
    "expert": "model",
    "expert_ffn": None,
    "dinner": "model",
    "state": None,
    "layers": None,
    "codebooks": None,
}

# FSDP+TP: additionally shard the d_model ("embed") dim of weights over the
# data axis — required for 405B/1T-class params to fit per-device HBM. For
# activations the "embed" rule is inert because the batch dim claims the
# data axis first (logical_to_spec never reuses a mesh axis within a spec).
FSDP_TP_RULES: Dict[str, object] = dict(DEFAULT_RULES, embed="data")

# + sequence parallelism: residual activations between layers are sharded on
# the sequence dim over "model" (attention/FFN internals gather as needed) —
# divides stored per-layer residuals by the model-axis size.
FSDP_TP_SP_RULES: Dict[str, object] = dict(FSDP_TP_RULES, seq="model")

PROFILES: Dict[str, Dict[str, object]] = {
    "tp": DEFAULT_RULES,
    "fsdp_tp": FSDP_TP_RULES,
    "fsdp_tp_sp": FSDP_TP_SP_RULES,
}


def rules_for(profile: str) -> Dict[str, object]:
    """The rule table registered under ``profile`` (see `PROFILES`)."""
    return PROFILES[profile]


# Ambient rule table used by with_logical_constraint inside model code.
# Set per-lowering (e.g. the dry-run wraps lowering in set_active_rules) so
# activation-sharding experiments don't require touching model code.
_ACTIVE_RULES: list = [DEFAULT_RULES]


class set_active_rules:
    """Context manager installing a rule table (by dict or profile name)
    as the ambient rules `with_logical_constraint` reads by default."""

    def __init__(self, rules):
        self.rules = rules if isinstance(rules, dict) else rules_for(rules)

    def __enter__(self):
        _ACTIVE_RULES.append(self.rules)
        return self.rules

    def __exit__(self, *exc):
        _ACTIVE_RULES.pop()
        return False


def active_rules() -> Dict[str, object]:
    """The innermost rule table installed by `set_active_rules`."""
    return _ACTIVE_RULES[-1]


def _mesh_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(mesh.axis_names)


def logical_to_spec(
    logical_axes: Optional[Sequence[Optional[str]]],
    rules: Dict[str, object],
    mesh: Mesh,
    shape: Optional[Sequence[int]] = None,
) -> P:
    """Convert a tuple of logical axis names to a PartitionSpec valid on mesh.

    If `shape` is given, mesh axes whose size does not divide the
    corresponding dimension are dropped (JAX rejects uneven shardings at jit
    boundaries) — e.g. 8 kv heads on a 16-way "model" axis fall back to
    replicated.
    """
    if logical_axes is None:
        return P()
    mesh_shape = dict(mesh.shape)
    mesh_axes = set(mesh.axis_names)
    used = set()
    entries = []
    for i, name in enumerate(logical_axes):
        if name is None:
            entries.append(None)
            continue
        target = rules.get(name, None)
        if target is None:
            entries.append(None)
            continue
        if isinstance(target, str):
            target = (target,)
        # keep only axes present in this mesh and not already used in this spec
        phys = tuple(a for a in target if a in mesh_axes and a not in used)
        if shape is not None and phys:
            dim = shape[i]
            kept = []
            prod = 1
            for a in phys:
                asize = mesh_shape[a]
                if dim % (prod * asize) == 0:
                    kept.append(a)
                    prod *= asize
            phys = tuple(kept)
        used.update(phys)
        if not phys:
            entries.append(None)
        elif len(phys) == 1:
            entries.append(phys[0])
        else:
            entries.append(phys)
    # trim trailing Nones for cleanliness
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


def tree_shardings(
    axes_tree,
    mesh: Mesh,
    rules: Optional[Dict[str, object]] = None,
    shapes_tree=None,
):
    """Map a pytree of logical-axis tuples to a pytree of NamedShardings.

    Leaves of `axes_tree` are tuples (possibly empty) of logical names or
    None entries. `None` leaves map to fully-replicated shardings. If
    `shapes_tree` (a matching pytree of arrays / ShapeDtypeStructs) is given,
    non-divisible mesh axes are dropped per-leaf.
    """
    rules = DEFAULT_RULES if rules is None else rules
    is_leaf = lambda x: x is None or isinstance(x, tuple)

    if shapes_tree is None:
        return jax.tree_util.tree_map(
            lambda axes: NamedSharding(mesh, logical_to_spec(axes, rules, mesh)),
            axes_tree,
            is_leaf=is_leaf,
        )
    return jax.tree_util.tree_map(
        lambda axes, arr: NamedSharding(
            mesh, logical_to_spec(axes, rules, mesh, shape=arr.shape)
        ),
        axes_tree,
        shapes_tree,
        is_leaf=is_leaf,
    )


def enter_mesh(mesh):
    """Context manager installing `mesh` as the ambient mesh (`jax.set_mesh`)."""
    return jax.set_mesh(mesh)


def with_logical_constraint(x, logical_axes, rules=None):
    """Apply a sharding constraint from logical axes inside jit.

    Uses the ambient mesh (set via enter_mesh); outside any mesh context
    this is a no-op so the same model code runs in unsharded smoke tests.
    Non-divisible axes are dropped (see logical_to_spec).
    """
    env_mesh = jax.sharding.get_abstract_mesh()
    if env_mesh.empty:
        return x
    rules = active_rules() if rules is None else rules
    spec = logical_to_spec(logical_axes, rules, env_mesh, shape=x.shape)
    return jax.lax.with_sharding_constraint(x, spec)
