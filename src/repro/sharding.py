"""Logical-axis -> mesh-axis resolution (GSPMD layer of the framework).

Every parameter init in ``models/`` returns ``(params, specs)`` where specs
leaves are tuples of *logical* names per dim.  This module maps those names
onto the production mesh:

  * TP  : "heads"/"mlp"/"vocab"/"experts"/"rnn" -> "model"
  * FSDP: "embed" -> "data" when ``cfg.fsdp`` (params + opt state sharded)
  * EP  : "experts" -> "model" (expert parallelism; dispatch becomes
          all-to-all in the lowered HLO)
  * SP  : activation sequence dim -> "model" for long-context cells
  * DP  : activation batch dim -> ("pod", "data")

Resolution is *divisibility-checked per tensor*: a logical dim that does
not divide its mesh axis falls back (e.g. GQA kv_heads=8 on model=16
replicates; 40-head archs shard head_dim instead of heads).  This is what
lets one rule table serve all ten assigned architectures.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Sequence, Tuple, Union

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

AxisEntry = Union[None, str, Tuple[str, ...]]


def auto_axes_mesh(mesh: Mesh) -> Mesh:
    """``mesh`` with every axis of type Auto.  The model code places
    parameters and activations through GSPMD constraints
    (``ShardingCtx.constrain``), which only name Auto axes; ``jax.make_mesh``
    makes Explicit axes by default, so a caller's mesh is normalized here."""
    if mesh.are_all_axes_auto:
        return mesh
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


def data_axis_names(mesh: Mesh) -> Tuple[str, ...]:
    """Mesh axes that carry the batch (DP): ("pod","data") or ("data",)."""
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def axis_size(mesh: Mesh, entry: AxisEntry) -> int:
    if entry is None:
        return 1
    if isinstance(entry, str):
        return mesh.shape[entry]
    return int(np.prod([mesh.shape[a] for a in entry]))


def logical_rules(mesh: Mesh, *, fsdp: bool = False,
                  seq_shard: bool = True) -> Dict[str, AxisEntry]:
    """Primary logical-name -> mesh-axis table."""
    model = "model" if "model" in mesh.shape else None
    data = data_axis_names(mesh) or None
    fsdp_ax = "data" if (fsdp and "data" in mesh.shape) else None
    return {
        # ---- parameters -------------------------------------------------
        "embed": fsdp_ax,          # FSDP shards the embed dim of every weight
        "vocab": model,
        "heads": model,
        "kv_heads": model,
        "head_dim": None,
        "mlp": model,
        "experts": model,          # EP
        "expert_mlp": None,
        "rnn": model,
        "rnn_heads": model,
        "conv": None,
        "layers": None,            # scan-stacked leading dim
        # ---- activations -------------------------------------------------
        "act_batch": data,
        "act_seq": model if seq_shard else None,   # SP (residual stream)
        "act_embed": None,
        "act_heads": model,
        "act_kv_seq": model,       # decode KV cache sequence dim
        "act_vocab": model,
        "act_experts": model,
        None: None,
    }


# Second-chance mapping: if a tensor got no "model" shard in the first
# pass (e.g. granite's odd vocab), these dims may take it instead.
# head_dim is deliberately NOT here: sharding K/V projections by head_dim
# while Q shards by heads mismatches the attention contraction and makes
# GSPMD psum the full (B,H,S,T) logits — measured at ~19 TB/device/step
# on llama3-405b train before this rule was fixed (EXPERIMENTS.md §Perf).
_FALLBACK_TO_MODEL = ("expert_mlp", "mlp", "rnn")


def resolve_spec(axes: Sequence[Optional[str]], shape: Sequence[int],
                 rules: Dict[str, AxisEntry], mesh: Mesh) -> P:
    """Map per-dim logical names to a PartitionSpec, enforcing divisibility
    and one-use-per-mesh-axis."""
    if len(axes) != len(shape):
        raise ValueError(f"spec {axes} does not match shape {shape}")
    parts: list[AxisEntry] = [None] * len(shape)
    used: set[str] = set()

    def mesh_axes(entry: AxisEntry) -> Tuple[str, ...]:
        if entry is None:
            return ()
        return (entry,) if isinstance(entry, str) else tuple(entry)

    def try_assign(i: int, entry: AxisEntry) -> bool:
        names = mesh_axes(entry)
        if not names or any(a in used for a in names):
            return False
        size = axis_size(mesh, entry)
        if size <= 1 or shape[i] % size != 0:
            return False
        parts[i] = entry if len(names) > 1 else names[0]
        used.update(names)
        return True

    # Weight-style dims first, activation dims second — e.g. a KV cache
    # (B, T, kv_heads, hd) shards kv_heads over "model" when divisible and
    # only falls back to sequence sharding (psum'd softmax) when not.
    for i, name in enumerate(axes):
        if name is not None and not str(name).startswith("act_"):
            try_assign(i, rules.get(name))
    for i, name in enumerate(axes):
        if parts[i] is None and name is not None and str(name).startswith("act_"):
            try_assign(i, rules.get(name))

    # Fallback pass: claim the model axis through an alternate dim if the
    # primary assignment failed to use it anywhere on this tensor.
    if "model" in mesh.shape and "model" not in used:
        for i, name in enumerate(axes):
            if parts[i] is None and name in _FALLBACK_TO_MODEL:
                if try_assign(i, "model"):
                    break
    return P(*parts)


def _map_specs(params: Any, specs: Any, fn):
    """Recurse matching (params, specs) trees; specs leaves are tuples."""
    if isinstance(params, dict):
        return {k: _map_specs(params[k], specs[k], fn) for k in params}
    if isinstance(params, (list,)):
        return [_map_specs(p, s, fn) for p, s in zip(params, specs)]
    return fn(params, specs)


@dataclasses.dataclass
class ShardingCtx:
    """Carried through model code; resolves + applies constraints.

    ``mesh=None`` (CPU smoke tests) makes every method a no-op.
    """
    mesh: Optional[Mesh]
    rules: Dict[str, AxisEntry]

    @classmethod
    def for_mesh(cls, mesh: Optional[Mesh], *, fsdp: bool = False,
                 seq_shard: bool = True) -> "ShardingCtx":
        if mesh is None:
            return cls(None, {})
        mesh = auto_axes_mesh(mesh)
        return cls(mesh, logical_rules(mesh, fsdp=fsdp, seq_shard=seq_shard))

    def spec(self, axes: Sequence[Optional[str]], shape: Sequence[int]) -> P:
        if self.mesh is None:
            return P()
        return resolve_spec(axes, shape, self.rules, self.mesh)

    def constrain(self, x, *axes: Optional[str]):
        """with_sharding_constraint by logical dim names (no-op off-mesh)."""
        if self.mesh is None or x is None:
            return x
        spec = self.spec(axes, x.shape)
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, spec))

    def named(self, axes: Sequence[Optional[str]], shape: Sequence[int]):
        assert self.mesh is not None
        return NamedSharding(self.mesh, self.spec(axes, shape))

    def param_shardings(self, params: Any, specs: Any):
        """NamedSharding tree for a (params, specs) pair (arrays or
        ShapeDtypeStructs — only .shape is read)."""
        assert self.mesh is not None
        return _map_specs(
            params, specs, lambda p, s: self.named(s, p.shape))

    def batch_sharding(self, ndim: int = 2):
        """Sharding for (batch, seq, ...) token arrays."""
        assert self.mesh is not None
        axes = ["act_batch"] + [None] * (ndim - 1)
        return NamedSharding(
            self.mesh, P(*(self.rules.get(a) for a in axes)))


def null_ctx() -> ShardingCtx:
    return ShardingCtx(None, {})
