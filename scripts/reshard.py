"""Offline checkpoint repartitioning for a target mesh topology.

Rewrites a checkpoint's block table so a later restore on the target
mesh takes the zero-copy exact-block path on every region — the
assembly cost of a cross-topology restore, paid once offline instead of
inside every preemption window or per serving replica. Works on sharded
directories AND legacy single-file checkpoints; target shardings are
resolved from the partition-rule tables per leaf path (reshard/resolver
— no live model, no devices needed), so this runs on any host that can
see the files.

    # relayout a dp4xtp2 trainer checkpoint for a dp2xtp2 slice
    python scripts/reshard.py out/step-00000042.ckpt out/re22.ckpt \
        --mesh 2,1,2 --fsdp --verify

    # flatten for single-axis dp8 (tp rules vacuous at model=1)
    python scripts/reshard.py out/latest.ckpt out/re81.ckpt --mesh 8,1,1

``--check`` first proves the rule tables cover every shardable
parameter (analysis/partition_coverage.py) — the guarantee that
rule-derived targets are complete. Exit 0 on success; ``--json`` prints
machine-readable stats.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("src", help="source checkpoint (sharded dir or legacy "
                   "single file)")
    p.add_argument("dst", help="output checkpoint directory")
    p.add_argument("--mesh", required=True,
                   help="target data,seq,model axis sizes, e.g. 2,1,2")
    p.add_argument("--fsdp", action="store_true",
                   help="apply the ZeRO overlay: shard rule-unclaimed "
                        "big leaves over the data axis")
    p.add_argument("--rules", choices=["lm", "none"], default="lm",
                   help="partition-rule table: 'lm' = the transformer "
                        "TP tables (train/lm.py), 'none' = no rules "
                        "(image/ResNet checkpoints: FSDP overlay or "
                        "plain replication)")
    p.add_argument("--vocab-parallel", action="store_true",
                   help="include the vocab-parallel head/embedding rules")
    p.add_argument("--tp-size", type=int, default=None,
                   help="TP degree for conditional rules (default: the "
                        "target mesh's model axis size)")
    p.add_argument("--ep-size", type=int, default=0,
                   help="MoE expert-parallel degree (0 = no MoE rules)")
    p.add_argument("--force", action="store_true",
                   help="overwrite an existing checkpoint at dst")
    p.add_argument("--verify", action="store_true",
                   help="re-read both checkpoints and bit-compare every "
                        "leaf afterwards")
    p.add_argument("--check", action="store_true",
                   help="run the partition-coverage proof before "
                        "resharding")
    p.add_argument("--json", action="store_true",
                   help="print stats as one JSON object")
    args = p.parse_args()

    import jax

    from pytorch_distributed_tpu.parallel.mesh import MESH_AXES
    from pytorch_distributed_tpu.reshard import (
        assert_rules_cover,
        lm_rules,
        repartition,
    )

    sizes = [int(x) for x in args.mesh.split(",")]
    if len(sizes) != len(MESH_AXES):
        raise SystemExit(
            f"--mesh wants {len(MESH_AXES)} sizes ({','.join(MESH_AXES)}), "
            f"got {args.mesh!r}"
        )
    mesh_shape = dict(zip(MESH_AXES, sizes))

    if args.check:
        assert_rules_cover()
        print("partition-coverage: ok (every shardable param is "
              "rule-claimed)")

    if args.rules == "none":
        rules = ()
    else:
        import types

        tp = args.tp_size if args.tp_size is not None else mesh_shape[
            MESH_AXES[-1]
        ]
        # a duck config carrying exactly the fields the conditional rule
        # builders read — the CLI has no TransformerConfig to hand
        cfg = types.SimpleNamespace(
            model_axis=MESH_AXES[-1] if tp > 1 else None,
            tp_size=tp,
            vocab_parallel=args.vocab_parallel,
            n_experts=1 if args.ep_size > 1 else 0,
            expert_axis=MESH_AXES[0] if args.ep_size > 1 else None,
            ep_size=args.ep_size,
        )
        rules = lm_rules(cfg)

    t0 = time.perf_counter()
    stats = repartition(
        args.src, args.dst, mesh_shape,
        rules=rules, fsdp=args.fsdp, mesh_axes=list(MESH_AXES),
        overwrite=args.force, verify=args.verify,
    )
    wall = time.perf_counter() - t0

    out = {
        "reshard_src": args.src,
        "reshard_dst": args.dst,
        "reshard_mesh": args.mesh,
        "reshard_leaves": stats["leaves"],
        "reshard_blocks": stats["blocks"],
        "reshard_mb": round(stats["bytes"] / 2**20, 1),
        "reshard_s": round(wall, 2),
        "reshard_verified": bool(stats.get("verified", False)),
    }
    if args.json:
        print(json.dumps(out))
    else:
        print(
            f"resharded {out['reshard_leaves']} leaves / "
            f"{out['reshard_blocks']} blocks "
            f"({out['reshard_mb']} MB) for mesh [{args.mesh}] in "
            f"{out['reshard_s']} s"
            + (" — verified bit-equal" if out["reshard_verified"] else "")
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
