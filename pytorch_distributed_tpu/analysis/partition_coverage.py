"""Rule: partition-coverage — every shardable param is claimed by a rule.

The TP/EP/vocab partition tables in ``train/lm.py`` are path-regex lists;
a renamed flax module or a typo'd pattern makes a parameter silently fall
through ``match_partition_rules`` to replicated — correct math, quietly
losing the memory/bandwidth the rule existed to save. This check builds
REAL parameter trees (``jax.eval_shape`` over probe configs — no device
memory, no mesh needed) and cross-checks them against the rule tables:

- a leaf with >= ``min_elems`` elements and >= 2 dims that no rule claims
  and no allowlist entry covers -> finding (fell through to replicated);
- a rule pattern that matches no parameter in ANY probe config -> finding
  (dead rule: it guards nothing, usually a drifted path).

Probe configs cover both attention parameterizations (fused MHA qkv vs
GQA q/kv), MoE expert placement and the vocab-parallel head, so every
rule in the table is exercised by at least one tree.

Unlike the AST rules this needs a live jax/flax; the CLI degrades to a
skip (with a notice) when the import fails.
"""

from __future__ import annotations

import re
from typing import List, Optional, Sequence, Tuple

from pytorch_distributed_tpu.analysis.core import Finding

# Parameters that are REPLICATED BY DESIGN: norm scales/offsets and the
# learned position table are small and read by every shard every step —
# sharding them trades a broadcast for an all_gather and wins nothing.
REPLICATED_BY_DESIGN = (
    r"(^|/)ln[^/]*/",      # layernorms (ln_1, ln_2, ln_f)
    r"(^|/)norm[^/]*/",
    r"(^|/)wpe/",          # learned positions
    r"/bias$",
    r"/scale$",
)


def _probe_trees():
    """[(label, config, params shape tree)] for the coverage probes."""
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_tpu.models.transformer import (
        TransformerLM,
        tiny_config,
    )

    # Shapes are GLOBAL and identical across parallel layouts, so the probe
    # initializes through the same dense twin create_lm_state uses.
    probes = [
        (
            "mha+moe+vocab_parallel",
            tiny_config(
                model_axis="model", tp_size=2, vocab_parallel=True,
                n_experts=2, expert_axis="data", ep_size=2,
            ),
        ),
        (
            "gqa+gated_mlp",
            tiny_config(model_axis="model", tp_size=2, num_kv_heads=2,
                        mlp="swiglu"),
        ),
    ]
    out = []
    for label, cfg in probes:
        import dataclasses

        init_cfg = dataclasses.replace(
            cfg, attention="dense", model_axis=None, tp_size=1,
            expert_axis=None, ep_size=1,
        )
        model = TransformerLM(init_cfg)
        shapes = jax.eval_shape(
            lambda m=model: m.init(
                jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
            )
        )["params"]
        out.append((label, cfg, shapes))
    return out


def check_partition_coverage(
    rules: Optional[Sequence[Tuple[str, object]]] = None,
    min_elems: int = 256,
    allow_replicated: Sequence[str] = REPLICATED_BY_DESIGN,
) -> List[Finding]:
    """Cross-check the LM partition tables against real param trees.

    ``rules``: override the full rule list (tests); default derives the
    per-probe list exactly the way ``lm_state_specs`` does
    (TRANSFORMER_TP_RULES + MoE + vocab rules per config).
    """
    import jax

    from pytorch_distributed_tpu.parallel.tensor import path_str
    from pytorch_distributed_tpu.train import lm as lm_mod

    rule_file = "pytorch_distributed_tpu/train/lm.py"
    findings: List[Finding] = []
    matched_patterns = set()
    all_patterns = []

    for label, cfg, shapes in _probe_trees():
        if rules is None:
            probe_rules = (
                lm_mod.TRANSFORMER_TP_RULES
                + lm_mod._moe_rules(cfg)
                + lm_mod._vocab_rules(cfg)
            )
        else:
            probe_rules = tuple(rules)
        for pattern, _spec in probe_rules:
            if pattern not in all_patterns:
                all_patterns.append(pattern)
        leaves = jax.tree_util.tree_flatten_with_path(shapes)[0]
        for path, leaf in leaves:
            name = path_str(path)
            shape = tuple(getattr(leaf, "shape", ()))
            hit = next(
                (p for p, _s in probe_rules if re.search(p, name)), None
            )
            if hit is not None:
                matched_patterns.add(hit)
                continue
            size = 1
            for d in shape:
                size *= d
            if len(shape) < 2 or size < min_elems:
                continue
            if any(re.search(a, name) for a in allow_replicated):
                continue
            findings.append(Finding(
                "partition-coverage", "error", rule_file, 0,
                f"[{label}] parameter {name!r} {shape} matches no partition "
                f"rule and falls through to replicated — add a rule or an "
                f"explicit REPLICATED_BY_DESIGN entry",
            ))

    for pattern in all_patterns:
        if pattern not in matched_patterns:
            findings.append(Finding(
                "partition-coverage", "error", rule_file, 0,
                f"partition rule {pattern!r} matches no parameter in any "
                f"probe config — dead rule (drifted module path?)",
            ))
    return findings
