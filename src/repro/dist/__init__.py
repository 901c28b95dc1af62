"""``repro.dist`` — the distribution layer.

Model code never mentions physical mesh axes.  Instead every parameter
and activation dimension carries a *logical* axis name (the ``axes``
tuple on ``repro.nn.module.P`` leaves, or the tuples passed to
``constrain``), and this package resolves those names onto whatever
mesh the program is running under:

  logical name                         physical mesh axes
  -----------------------------------  -----------------------------
  "batch" / "nodes" / "edges"          ("pod", "data")  — jointly,
                                       whichever the mesh has
  "mlp" "heads" "kv_heads" "vocab"
  "items" "table" "centroid" "expert"  "model"
  "seq" "embed" "head_dim" "act_*"
  "code_split" "table_dim" ... / None  replicated

Resolution is best-effort (divisibility fallback to replication,
first-dim-wins on mesh-axis conflicts) so the same model runs
unmodified on a single device, an 8-way host mesh, or a 16x16 pod —
see ``repro.dist.rules``.

Public API
  resolve_axes(axes, shape, mesh[, rules]) -> PartitionSpec
  use_mesh_rules(mesh[, rules])   context manager installing the
                                  ambient mesh (read by ``constrain``,
                                  ``data_shard_count`` and
                                  ``repro.core.sharded``)
  constrain(x, axes)              sharding-constraint (no-op off-mesh)
  data_shard_count()              data-parallel degree of the ambient
                                  mesh (1 off-mesh)
  params_shardings(meta, mesh[, rules])  P-leaf tree -> NamedSharding
                                  tree (jit in/out_shardings, elastic
                                  checkpoint restore)

Submodules: ``rules`` (the table + resolver), ``compression``
(data-parallel gradient exchange with bf16/int8 error feedback),
and ``hlo`` (collective-traffic accounting for the dry-run roofline).
"""
from __future__ import annotations

import math

import jax
from jax.sharding import NamedSharding, PartitionSpec

from repro.dist.rules import (DATA_AXES, DEFAULT_RULES, _CTX,  # noqa: F401
                              resolve_axes, use_mesh_rules)

__all__ = ["resolve_axes", "use_mesh_rules", "constrain",
           "data_shard_count", "params_shardings", "DEFAULT_RULES"]


def constrain(x, axes):
    """Constrain ``x`` to the sharding its logical ``axes`` resolve to
    under the ambient mesh; identity when no mesh is installed."""
    mesh = _CTX.mesh
    if mesh is None:
        return x
    # inside a shard_map body the mesh axes are manual and
    # with_sharding_constraint refuses specs that name them (the
    # compressed-gradient dp step traces model losses there); the
    # enclosing shard_map's specs already pin the layout, so the
    # advisory constraint simply stands down
    manual = jax.sharding.get_abstract_mesh().manual_axes
    if manual and any(a in manual for a in mesh.shape):
        return x
    spec = resolve_axes(axes, x.shape, mesh, _CTX.rules)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, spec))


def data_shard_count() -> int:
    """Data-parallel degree of the ambient mesh (1 off-mesh)."""
    mesh = _CTX.mesh
    if mesh is None:
        return 1
    axes = [a for a in DATA_AXES if a in mesh.shape]
    return math.prod(mesh.shape[a] for a in axes) if axes else 1


def params_shardings(params_meta, mesh, rules=None):
    """Map a ``P``-leaf parameter tree to a matching NamedSharding tree
    (same structure as ``nn.values(params_meta)``)."""
    from repro.nn.module import is_param

    def _leaf(p):
        if is_param(p):
            spec = resolve_axes(p.axes, p.shape, mesh, rules)
        else:
            spec = PartitionSpec()
        return NamedSharding(mesh, spec)

    return jax.tree.map(_leaf, params_meta, is_leaf=is_param)
