"""Logical-axis sharding rules (MaxText-style) — DP/FSDP/TP/EP/SP as config.

Every parameter and activation names its dims with *logical* axes; a rule
table maps logical axes onto mesh axes.  The resolver silently degrades
(replicates) when a dim isn't divisible by the mapped mesh extent — e.g.
kv_heads=8 on a 16-way "model" axis — and records the degradation so the
dry-run can report it.

This is the Fix worldview applied to SPMD: the *placement* of every tensor
is declared up front, and the platform (XLA's partitioner) performs all
resulting I/O (collectives).  Changing a rule = changing the data-movement
schedule, which is exactly what the §Perf hillclimb iterates on.
"""
from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Optional, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# ---------------------------------------------------------------- rule sets
# logical axis -> mesh axis name, tuple of names, or None (replicate)
BASE_RULES: dict[str, object] = {
    # activations
    "batch": ("pod", "data"),
    "seq": None,
    "res_seq": None,            # residual stream between blocks; "model" = SP
    "kv_seq": "model",          # decode: KV cache length is context-parallel
    "embed": None,
    "heads": "model",
    "kv_heads": "model",
    "mlp": "model",
    "vocab": "model",
    "experts": "model",
    "expert_cap": None,
    "ssm_heads": "model",
    "ssm_state": None,
    "conv_dim": "model",
    # params (p_*: how weights are laid out at rest)
    "p_embed": "data",          # FSDP / ZeRO-3 over the intra-pod data axis
    "p_mlp": "model",           # tensor parallel
    "p_heads": "model",
    "p_kv_heads": "model",
    "p_vocab": "model",
    "p_experts": "model",       # expert parallel
    "p_ssm_heads": "model",
    "p_conv_dim": "model",
    "p_lora": None,
    "p_layers": None,           # scan axis
    "p_none": None,
}


def make_rules(**overrides) -> dict:
    rules = dict(BASE_RULES)
    rules.update(overrides)
    return rules


# named variants used by the perf hillclimb
RULE_VARIANTS: dict[str, dict] = {
    "baseline": make_rules(),
    "seqpar": make_rules(res_seq="model"),                    # Megatron-style SP:
    # only the residual stream is seq-sharded; RS/AG at block boundaries
    "fsdp_pod": make_rules(p_embed=("pod", "data")),         # ZeRO across pods too
    "no_fsdp": make_rules(p_embed=None),                      # pure TP weights
    "ep_wide": make_rules(p_experts=("data", "model"), experts=("data", "model")),
    "seqpar_no_fsdp": make_rules(res_seq="model", p_embed=None),
    "seqpar_ep_wide": make_rules(res_seq="model", p_experts=("data", "model")),
}


@dataclass
class Sharder:
    """Resolves logical axis names to NamedShardings; no-op without a mesh."""

    mesh: Optional[Mesh] = None
    rules: dict = field(default_factory=make_rules)
    degradations: list = field(default_factory=list)

    def spec(self, axes: Sequence[Optional[str]], shape: Optional[Sequence[int]] = None) -> P:
        """PartitionSpec for logical ``axes`` (checked against ``shape``)."""
        if self.mesh is None:
            return P()
        mesh_axes = dict(zip(self.mesh.axis_names, self.mesh.shape.values()))
        parts = []
        used: set[str] = set()
        for i, name in enumerate(axes):
            rule = self.rules.get(name) if name is not None else None
            if rule is None:
                parts.append(None)
                continue
            names = (rule,) if isinstance(rule, str) else tuple(rule)
            names = tuple(n for n in names if n in mesh_axes and n not in used)
            if not names:
                parts.append(None)
                continue
            extent = 1
            for n in names:
                extent *= mesh_axes[n]
            if shape is not None and shape[i] % extent != 0:
                # degrade: drop trailing axes until divisible
                while names and shape[i] % extent != 0:
                    extent //= mesh_axes[names[-1]]
                    names = names[:-1]
                self.degradations.append((tuple(axes), i, name))
            if not names:
                parts.append(None)
                continue
            used.update(names)
            parts.append(names[0] if len(names) == 1 else names)
        while parts and parts[-1] is None:
            parts.pop()
        return P(*parts)

    def named(self, axes: Sequence[Optional[str]], shape: Optional[Sequence[int]] = None) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec(axes, shape))

    def __call__(self, x, *axes: Optional[str]):
        """Constrain activation ``x`` to the resolved sharding.  Inside a
        shard_map (e.g. the pod-manual EF-int8 grad sync) the constraint
        rebinds to the ambient abstract mesh with manual axes excluded."""
        if self.mesh is None:
            return x
        ctx = jax.sharding.get_abstract_mesh()
        manual = set(ctx.manual_axes)
        if manual:
            sub = Sharder(ctx, {k: self._strip(v, manual)
                                for k, v in self.rules.items()})
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(ctx, sub.spec(axes, x.shape)))
        return jax.lax.with_sharding_constraint(x, self.named(axes, x.shape))

    @staticmethod
    def _strip(rule, manual: set):
        if rule is None:
            return None
        names = (rule,) if isinstance(rule, str) else tuple(rule)
        kept = tuple(n for n in names if n not in manual)
        return kept if kept else None

    def with_rules(self, **overrides) -> "Sharder":
        return Sharder(self.mesh, make_rules(**{**self.rules, **overrides}))
