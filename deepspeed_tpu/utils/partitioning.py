"""Logical→mesh partitioning utilities.

The model zoo annotates parameters with *logical* axis names
('vocab', 'embed', 'heads', 'mlp', 'layers', ...). These rules map them onto
the canonical mesh axes ('pipe','data','expert','sequence','model'), after
which the ZeRO plan layers its data-axis sharding on top. This replaces the
reference's imperative weight slicing (`module_inject/auto_tp.py:_replace:330`
row/column splits): here the slicing is declarative and XLA moves the bytes.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

# Default logical→physical rules (Megatron-style TP):
#   column-parallel matmuls shard output features ('heads'/'mlp'),
#   row-parallel shard input features ('heads_in'/'mlp_in'),
#   embeddings shard the vocab dim.
DEFAULT_RULES: Dict[str, Optional[str]] = {
    "vocab": "model",
    "embed": None,
    "heads": "model",
    "kv_heads": "model",
    "heads_in": "model",
    "mlp": "model",
    "mlp_in": "model",
    "layers": None,
    "expert": "expert",
    None: None,
}


def logical_to_spec(logical_axes: Tuple, rules: Optional[Dict] = None) -> P:
    rules = {**DEFAULT_RULES, **(rules or {})}
    return P(*[rules.get(name, None) for name in logical_axes])


def extract_params_and_specs(variables, rules: Optional[Dict] = None):
    """Unbox flax `nn.Partitioned` metadata → (raw params, PartitionSpec tree)."""
    import flax.linen as nn
    from flax.core import meta

    params = variables["params"] if "params" in variables else variables

    def spec_of(leaf):
        if isinstance(leaf, meta.Partitioned):
            return logical_to_spec(leaf.names, rules)
        return P()

    specs = jax.tree_util.tree_map(
        spec_of, params, is_leaf=lambda x: isinstance(x, meta.Partitioned))
    raw = meta.unbox(params)
    return raw, specs


def current_mesh():
    from deepspeed_tpu.utils import groups
    try:
        return groups.get_topology(create_default=False).mesh
    except RuntimeError:
        return None


def ambient_manual_mesh():
    """(abstract mesh, names of its Manual axes) while tracing inside a
    shard_map region; (None, frozenset()) outside one."""
    am = jax.sharding.get_abstract_mesh()
    if am is None or am.empty:
        return None, frozenset()
    return am, frozenset(name for name, t in zip(am.axis_names, am.axis_types)
                         if str(t) == "Manual")


def shard_along(x, *axes, rules: Optional[Dict] = None):
    """Constrain an activation's sharding (no-op without an installed topology).

    `axes` are per-dimension entries: mesh axis name(s), logical names (mapped
    through rules), or None. E.g. for (B, S, D) token activations:
        shard_along(x, ('repl', 'data', 'expert'), 'sequence', None)
    """
    mesh = current_mesh()
    if mesh is None:
        return x
    # Inside a shard_map manual region (e.g. the pipeline rotation) the
    # constraint must be built against the ambient AbstractMesh, and specs
    # must not mention Manual axes (they're already mapped away).
    am, manual_axes = ambient_manual_mesh()
    if am is not None:
        mesh = am
    rules = {**DEFAULT_RULES, **(rules or {})}

    def resolve(entry):
        if entry is None:
            return None
        if isinstance(entry, (tuple, list)):
            out = tuple(r for r in (resolve(e) for e in entry) if r is not None)
            return out if out else None
        if entry in mesh.axis_names:
            return entry
        return rules.get(entry, None)

    spec = P(*[resolve(a) for a in axes])
    # Drop axes not present (or trivial) in this mesh.
    sizes = dict(mesh.shape)

    def present(entry):
        if entry is None:
            return None
        if isinstance(entry, tuple):
            kept = tuple(e for e in entry
                         if sizes.get(e, 1) >= 1 and e not in manual_axes)
            return kept if kept else None
        if entry in manual_axes:
            return None
        return entry if sizes.get(entry, 1) >= 1 else None

    spec = P(*[present(e) for e in spec])
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


BATCH_AXES = ("repl", "data", "expert")
