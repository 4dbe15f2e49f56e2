"""Pre-warm a serving deployment's compile cache from the command line.

Builds the paged serving engine for an LM config, enumerates every
program it can ever run (``compilecache.serving_registry``: one chunk-
prefill program per (job-count, table-width) bucket + the decode tick),
compiles them all — populating jax's persistent compilation cache at
``--compile-cache-dir`` — and writes a warmup manifest JSONL
(``kind="warmup"`` records: program, seconds, backend-compile seconds,
cache_hit, fingerprint) that ``scripts/telemetry_report.py`` renders.

Run it once per (config, cache dir) before rolling out servers: the
first run compiles fresh and fills the cache; every later server start
(``recipes/serve_lm.py --warmup --compile-cache-dir ...``) — and every
re-run of this script — loads executables from disk instead of
recompiling. ``--expect-hits`` turns that into a gate: exit non-zero
unless at least one program was a cache hit (the ci_check.sh
``--warmup-smoke`` assertion that the cache actually persists).

    python scripts/warmup.py --tiny --compile-cache-dir /tmp/cc
    python scripts/warmup.py --tiny --compile-cache-dir /tmp/cc --expect-hits
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from pytorch_distributed_tpu.utils.env import (  # noqa: E402
    enable_compile_cache,
    set_env,
)


def _parse() -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--compile-cache-dir", default=None,
                   help="persistent compilation cache directory (default "
                        "<repo>/.jax_cache; an exported "
                        "JAX_COMPILATION_CACHE_DIR wins over this flag)")
    p.add_argument("--manifest", default=None,
                   help="warmup manifest JSONL path (default "
                        "<cache-dir>/warmup_manifest.jsonl, appended)")
    p.add_argument("--tiny", action="store_true",
                   help="tiny LM config (CPU smoke; matches serve_lm)")
    p.add_argument("--max-seq-len", type=int, default=None,
                   help="override the config's max_seq_len")
    p.add_argument("--slots", type=int, default=8, help="decode lanes")
    p.add_argument("--block-len", type=int, default=16,
                   help="KV block length")
    p.add_argument("--prefill-chunk", type=int, default=32,
                   help="prefill chunk length")
    p.add_argument("--expect-hits", action="store_true",
                   help="exit non-zero unless >= 1 program was a "
                        "persistent-cache hit (warm-start gate)")
    p.add_argument("--json", action="store_true",
                   help="print the summary as one JSON line")
    return p.parse_args()


def main() -> int:
    args = _parse()
    set_env("202607")
    cache_dir = enable_compile_cache(args.compile_cache_dir)
    from pytorch_distributed_tpu.compilecache import (
        WarmupRunner,
        serving_registry,
    )

    import jax
    import jax.numpy as jnp

    from pytorch_distributed_tpu.models.transformer import (
        TransformerConfig,
        TransformerLM,
        tiny_config,
    )
    from pytorch_distributed_tpu.serving.engine import PagedEngine
    from pytorch_distributed_tpu.utils.profiling import MetricsLogger

    if args.tiny:
        cfg = tiny_config(attention="dense",
                          max_seq_len=args.max_seq_len or 128)
    else:
        cfg = TransformerConfig(
            vocab_size=32_000, num_layers=12, num_heads=12, embed_dim=768,
            max_seq_len=args.max_seq_len or 2048, attention="dense",
            dropout=0.0,
        )
    params = TransformerLM(cfg).init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    engine = PagedEngine(cfg, params, args.slots, block_len=args.block_len,
                         prefill_chunk=args.prefill_chunk)
    registry = serving_registry(engine)
    manifest_path = args.manifest or os.path.join(
        cache_dir, "warmup_manifest.jsonl"
    )
    with MetricsLogger(manifest_path) as manifest:
        runner = WarmupRunner(registry, manifest=manifest)
        # foreground everything: a standalone prewarmer has no traffic to
        # overlap with — priority order still drives the compile sequence
        runner.run(background=False)
    summary = runner.summary()
    summary["manifest"] = manifest_path
    if args.json:
        print(json.dumps(summary))
    else:
        print(
            f"warmed {summary['programs']} programs in "
            f"{summary['total_s']:.2f}s ({summary['cache_hits']} cache "
            f"hits, {summary['fresh']} fresh; backend compile "
            f"{summary['backend_compile_s']:.2f}s; fingerprint "
            f"{summary['fingerprint']})\nmanifest: {manifest_path}"
        )
    if args.expect_hits and summary["cache_hits"] < 1:
        print("--expect-hits: no persistent-cache hit — the cache at "
              f"{cache_dir} did not serve this config's programs",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
