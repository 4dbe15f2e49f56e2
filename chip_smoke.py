"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py              # one TPU chip; exits non-zero without one
    python chip_smoke.py --multichip  # four chips: only the cross-chip paths
    python chip_smoke.py --tiny       # same control flow, toy widths, on the CPU

One process drives both hot paths once, through the entry points a user
calls, at the full width of the models the recipes build (depth as the
recipes have it, weights random from a seed):

- ``resnet``: ``recipes/common.py`` → ``Trainer.fit``, ResNet-50 bf16 at
  224x224, per-device batch 128, two epochs of synthetic data with an
  eval pass each and a step-interval checkpoint save;
- ``sync_check``: the same 20 chained ResNet steps timed once with
  ``jax.block_until_ready`` and once with a scalar value fetch;
- ``lm``: ``recipes/lm_pretrain.py`` → ``LMTrainer.fit``, the recipe's
  default 12L/12H/768 model at seq 2048 with the flash kernel and the
  fused CE, on one device (``--seq-parallel 1``);
- ``server``: ``recipes/serve_lm.py``'s model behind ``FleetRouter`` →
  ``Scheduler`` → ``PagedEngine`` and the HTTP gateway, checked token for
  token against an in-process ``Scheduler``, then the same prompts
  through the paged read's other spelling (the default is the fused
  kernel on a TPU, the dense gather elsewhere): the two must agree on
  every first token and on 90% of tokens decoded from the same context;
- ``pool``: the server's K/V pool alone. What a bf16, an int8 and an fp8
  pool take of the device's memory beside their logical size (a leaf the chip
  pads or keeps in another layout shows here), and the fused gather
  against the dense one on random bf16, int8 and fp8 pools, decode and
  chunk rows: they must agree to one bf16 ulp;
- ``ouro``: a looped decoder at ``perfbench/configs/ouro-2.6b.json``'s
  widths on two layers and four passes through ``PagedEngine``: one chunk
  program and one decode tick, each slot's logits against the plain
  reference (``perfbench/references/ouro.py``) on the same bf16 weights;
  then the tick compiled through the fused kernel at heads of 128, and
  whether the chip's compiler took it (reported, not required);
- ``zaya``: the ``zaya`` block at ``perfbench/configs/zaya1-8b.json``'s
  widths on two layers through ``PagedEngine``: chunked prefill (a prompt
  of two chunks, one that ends inside its chunk) and then 64 decode
  ticks, the tick's K/V read by the rule's spelling (the fused kernel's
  grouped-head fold on a TPU), each slot's logits at every served
  position against the plain reference (``perfbench/references/zaya.py``)
  on the same bf16 weights, and the experts' token counts a tick.

``--multichip`` runs the paths that exist only across chips, each beside
what it is compared with: data-parallel ResNet against one device,
seq x tensor parallel ring-flash LM against one-device flash, and four
fleet replicas against one.

Every line of standard output is one JSON object; the last is only
``{"ok": true, "device": {...}}``. A check that fails fails the run: the
phase's line still prints (with what failed under ``"failed"``) and the
process then exits non-zero. Whatever else the library prints goes to
standard error.
"""

from __future__ import annotations

import argparse
import contextlib
import glob
import json
import math
import os
import shutil
import sys
import tempfile
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
_OUT = sys.stdout  # JSON lines go here; everything else to stderr


def _parse(argv) -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--tiny", action="store_true",
                   help="toy widths on the CPU backend (rehearsal; never "
                        "reports a TPU)")
    p.add_argument("--multichip", action="store_true",
                   help="run the four-chip paths and what each is compared "
                        "with, and no other phase")
    p.add_argument("--seed", type=int, default=0,
                   help="seed for the prompts (weights and data take the "
                        "recipes' own seeds)")
    return p.parse_args(argv)


# imported (by a test), it reads no command line and runs at the defaults
ARGS = _parse(sys.argv[1:] if __name__ == "__main__" else [])
if ARGS.tiny:
    # before jax is imported: --tiny is a CPU rehearsal wherever it runs
    os.environ["JAX_PLATFORMS"] = "cpu"
    if ARGS.multichip:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4"
        ).strip()

import jax  # noqa: E402
import numpy as np  # noqa: E402

sys.path.insert(0, os.path.join(ROOT, "recipes"))
import common  # noqa: E402  (recipes/common.py; puts the repo on sys.path)
import lm_pretrain  # noqa: E402
import serve_lm  # noqa: E402

from pytorch_distributed_tpu.compilecache import (  # noqa: E402
    process_compile_totals,
    serving_registry,
)
from pytorch_distributed_tpu.parallel import (  # noqa: E402
    init_process_group,
    make_mesh,
    shard_batch,
    single_device_mesh,
)
from pytorch_distributed_tpu.utils.env import enable_compile_cache  # noqa: E402

KERNEL = "tpu_custom_call"  # a compiled (not interpreted) Pallas kernel
#: the zaya phase judges a row where the reference's routers led by this
#: much in every layer (a score is a probability)
MARGIN = 0.02


def emit(**record) -> None:
    print(json.dumps(record), file=_OUT, flush=True)


def peak_bytes():
    stats = jax.devices()[0].memory_stats()  # None on the CPU backend
    return stats.get("peak_bytes_in_use") if stats else None


@contextlib.contextmanager
def phase(name: str):
    """Time one phase and print its line. ``rec["failed"]`` collects the
    checks that did not hold; the line prints with them, then the run
    dies — so a failed run still says how far it was from passing."""
    rec = {"phase": name, "failed": []}
    hits0, compile0 = process_compile_totals()
    t0 = time.perf_counter()
    yield rec
    hits1, compile1 = process_compile_totals()
    rec.update(wall_s=round(time.perf_counter() - t0, 2),
               compile_s=round(compile1 - compile0, 2),
               cache_hits=hits1 - hits0, peak_bytes_in_use=peak_bytes())
    emit(**rec)
    if rec["failed"]:
        raise RuntimeError(f"phase {name}: {'; '.join(rec['failed'])}")


def require(rec: dict, ok: bool, what: str) -> None:
    if not ok:
        rec["failed"].append(what)


def fit(trainer, rec: dict) -> tuple:
    """Run a trainer to the end; return the train losses it logged and
    its last validation loss."""
    trainer.fit()
    trainer.watcher.uninstall()  # hand SIGTERM back: later phases have no
    # checkpoint to write on it
    with open(trainer.metrics_log.path) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    losses = [r["loss"] for r in rows if r["kind"] == "train"]
    val_loss = [r["loss"] for r in rows if r["kind"] == "val"][-1]
    require(rec, all(math.isfinite(x) for x in losses + [val_loss]),
            "non-finite loss")
    return losses, val_loss


def fit_and_record(trainer, rec: dict) -> list:
    losses, val_loss = fit(trainer, rec)
    rec.update(steps=int(trainer.state.step), first_loss=losses[0],
               last_loss=losses[-1], val_loss=val_loss)
    return losses


def step_text(trainer) -> str:
    """Compiled text of the trainer's train step (a persistent-cache hit:
    the step has run)."""
    spec = next(s for s in trainer.program_registry()
                if s.name.endswith("train_step"))  # "lm_train_step" for LMs
    return spec.aot().as_text()


# ---- one chip ------------------------------------------------------------


def resnet_phase(workdir: str) -> None:
    save_dir = os.path.join(workdir, "resnet")
    argv = ["--save-dir", save_dir, "--epochs", "2", "--log-every", "8",
            "--save-every-n-steps", "48"]  # 64 steps an epoch, both sizes
    argv += ["--tiny"] if ARGS.tiny else ["--synthetic", "--batch-size", "128"]
    with phase("resnet") as rec:
        trainer = common.build_trainer(
            common.parse_args("chip_smoke", argv), make_mesh(),
            precision="bf16",
        )
        rec.update(model="resnet-tiny" if ARGS.tiny else "resnet50",
                   batch=trainer.config.batch_size,
                   precision=trainer.config.precision)
        losses = fit_and_record(trainer, rec)
        # one batch's loss is noisy: the first three logged against the last
        head, tail = np.mean(losses[:3]), np.mean(losses[-3:])
        require(rec, tail < head,
                f"loss did not fall ({head:.4f} -> {tail:.4f})")
        require(rec, rec["steps"] >= 10, f"only {rec['steps']} steps")
        saved = glob.glob(os.path.join(save_dir, "step-*.ckpt*"))
        rec["checkpoints"] = sorted(os.path.basename(p) for p in saved)
        require(rec, bool(saved), "no step checkpoint was saved")
    with phase("sync_check") as rec:
        # Is block_until_ready honest on this runtime? The same chained
        # steps, drained two ways (utils/profiling._scalar_sync's question).
        step, state, n = trainer.train_step, trainer.state, 20
        batches = trainer.train_loader.iter_batches(0)
        batch = shard_batch(trainer.mesh, next(batches))
        batches.close()
        state, metrics = step(state, batch)
        float(metrics["loss"])
        t0 = time.perf_counter()
        for _ in range(n):
            state, metrics = step(state, batch)
        jax.block_until_ready((state, metrics))
        bur_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(n):
            state, metrics = step(state, batch)
        loss = float(metrics["loss"])
        fetch_s = time.perf_counter() - t0
        rec.update(steps=n, block_until_ready_s=round(bur_s, 4),
                   value_fetch_s=round(fetch_s, 4),
                   ratio=round(bur_s / fetch_s, 4))
        require(rec, math.isfinite(loss), "non-finite loss")


def lm_phase(workdir: str) -> None:
    argv = ["--save-dir", os.path.join(workdir, "lm"), "--seq-parallel", "1",
            "--log-every", "1"]
    argv += ["--tiny"] if ARGS.tiny else ["--synthetic",
                                          "--synthetic-size", "96"]
    with phase("lm") as rec:
        trainer = lm_pretrain.build_trainer(
            common.parse_lm_args("chip_smoke", argv)
        )
        cfg = trainer.model_config
        rec.update(layers=cfg.num_layers, heads=cfg.num_heads,
                   embed=cfg.embed_dim, vocab=cfg.vocab_size,
                   seq=cfg.max_seq_len, attention=cfg.attention,
                   batch=trainer.config.batch_size)
        losses = fit_and_record(trainer, rec)
        require(rec, rec["steps"] >= 5, f"only {rec['steps']} steps")
        # random weights, random tokens: step 1 sits near ln(vocab)
        require(rec, abs(losses[0] - math.log(cfg.vocab_size)) < 1.5,
                f"first loss {losses[0]:.3f} far from ln(vocab)")
        rec["kernel_in_step"] = KERNEL in step_text(trainer)
        if not ARGS.tiny:
            require(rec, rec["kernel_in_step"],
                    f"no {KERNEL} in the compiled LM step: the flash "
                    "kernel did not compile in")


def serve_setup():
    """The recipe's model, its weights and its serving geometry."""
    sargs = serve_lm._parse(["--tiny"] if ARGS.tiny else [])
    cfg, params, _ = serve_lm._model(sargs)
    kw = dict(n_slots=sargs.slots, block_len=sargs.block_len,
              prefill_chunk=sargs.prefill_chunk,
              admit_per_step=sargs.admit_per_step)
    return cfg, params, kw


def make_prompts(cfg, lengths) -> list:
    rng = np.random.default_rng(ARGS.seed)
    return [rng.integers(1, cfg.vocab_size, size=n).astype(np.int32)
            for n in lengths]


def replay(scheduler, prompts, max_new: int) -> list:
    """One request at a time through an in-process Scheduler — the same
    program sequence the gateway's sequential clients produce."""
    streams = []
    for p in prompts:
        rid = scheduler.submit(p, max_new)
        streams.append([int(t) for t in scheduler.drain()[rid]])
    return streams


def engine_texts(engine) -> dict:
    """Compiled text of every program the engine has compiled."""
    live = set(engine.compiled_program_names())
    return {s.name: s.aot().as_text() for s in serving_registry(engine)
            if s.name in live}


@contextlib.contextmanager
def paged_read(spelling: str):
    """Every program traced inside reads the pool through ``spelling``.
    No option names a read: ``ops.attention.default_gather_impl`` chooses
    it, so the other spelling is built by steering that rule, as the
    tests do."""
    from pytorch_distributed_tpu.ops import attention

    rule = attention.default_gather_impl
    attention.default_gather_impl = lambda rows=1, dense_bytes=0: spelling
    try:
        yield
    finally:
        attention.default_gather_impl = rule


def agreement(a: list, b: list) -> float:
    """Share of positions at which two sets of streams agree."""
    same = sum(x == y for s, t in zip(a, b) for x, y in zip(s, t))
    return same / sum(len(s) for s in a)


def same_context_agreement(scheduler, prompts, want: list, got: list) -> float:
    """Share of tokens on which ``scheduler`` agrees with the streams
    ``want`` when both have seen the same context. A greedy stream that
    differs once differs from there on, so a position-by-position count
    of ``got`` against ``want`` charges one flipped token with the rest
    of its stream. Here a stream that leaves ``want`` costs the one token,
    and is run again from ``want``'s context for the rest."""
    flips = 0
    for prompt, w, g in zip(prompts, want, got):
        done = 0  # tokens of w accounted for
        while g != w[done:]:
            done += next(i for i, (x, y) in enumerate(zip(g, w[done:]))
                         if x != y) + 1
            flips += 1
            if done == len(w):
                break
            rid = scheduler.submit(
                np.concatenate([prompt, np.asarray(w[:done], np.int32)]),
                len(w) - done,
            )
            g = [int(t) for t in scheduler.drain()[rid]]
    return 1 - flips / sum(len(w) for w in want)


def server_phase() -> None:
    from pytorch_distributed_tpu.fleet import FleetRouter
    from pytorch_distributed_tpu.gateway import Gateway, client
    from pytorch_distributed_tpu.serving import Scheduler

    max_new = 32
    with phase("server") as rec:
        cfg, params, kw = serve_setup()
        top = cfg.max_seq_len - max_new - kw["prefill_chunk"]
        prompts = make_prompts(cfg, [min(n, top) for n in (9, 40, 75, 150)])
        rec.update(layers=cfg.num_layers, heads=cfg.num_heads,
                   embed=cfg.embed_dim, vocab=cfg.vocab_size,
                   max_seq_len=cfg.max_seq_len, max_new=max_new,
                   prompt_lens=[len(p) for p in prompts], **kw)

        reference = Scheduler(cfg, params, **kw)
        want = replay(reference, prompts, max_new)

        router = FleetRouter(cfg, params, n_replicas=1,
                             retain_results=False, **kw)
        gw = Gateway(router, port=0).start()
        try:
            base = f"http://127.0.0.1:{gw.port}"
            # the first request compiles as it goes: a long client timeout
            got = [client.generate(base, p, max_new, timeout=900.0)
                   for p in prompts]
        finally:
            gw.stop()
            router.drain()
        engine = router.replicas[0].engine
        require(rec, all(g["status"] == 200 for g in got),
                f"HTTP statuses {[g['status'] for g in got]}")
        require(rec, all(g.get("outcome") == "complete" for g in got),
                f"outcomes {[g.get('outcome') for g in got]}")
        require(rec, [g.get("tokens") for g in got] == want,
                "gateway streams differ from the in-process Scheduler")
        require(rec, engine.allocator.in_use == 0,
                f"{engine.allocator.in_use} blocks still held after drain")
        serving_registry(engine).assert_covers(
            engine.compiled_program_names()
        )
        rec.update(tokens=sum(len(g.get("tokens") or ()) for g in got),
                   programs=len(engine.compiled_program_names()))

        # the paged read's other spelling (unnamed, a decode tick reads
        # through the fused kernel on a TPU and the dense gather
        # elsewhere; chunk programs gather dense): same prompts, second
        # Scheduler, every program of which compiles the other spelling
        default = engine.gather_impl
        with paged_read("dense" if default == "pallas" else "pallas"):
            second = Scheduler(cfg, params, **kw)
            other = replay(second, prompts, max_new)
            rate = same_context_agreement(second, prompts, want, other)
            if default != "pallas":
                texts = engine_texts(second.engine)
        if default == "pallas":
            texts = engine_texts(engine)
        first_ok = all(f[0] == w[0] for f, w in zip(other, want))
        rec.update(default_read=default, tile_blocks=engine.tile_blocks,
                   heads_folded=engine.heads_folded,
                   grouped_rows=engine.grouped_rows,
                   pallas_agreement=round(rate, 4),
                   pallas_stream_agreement=round(agreement(want, other), 4),
                   pallas_first_tokens_agree=first_ok,
                   pallas_programs={n: KERNEL in t for n, t in texts.items()})
        require(rec, engine.grouped_rows == 0,
                "a model without experts reports a grouped product")
        require(rec, first_ok, "pallas and dense disagree on a first token")
        require(rec, rate >= 0.9, f"pallas/dense agreement {rate:.3f} < 0.9")
        require(rec, second.engine.allocator.in_use == 0,
                "the second scheduler leaked blocks")
        require(rec, "decode_tick" in texts
                and any(n.startswith("chunk_prefill") for n in texts),
                f"pallas run compiled only {sorted(texts)}")
        if not ARGS.tiny:
            require(rec, default == "pallas"
                    and rec["pallas_programs"]["decode_tick"],
                    f"no {KERNEL} in the served tick "
                    f"({rec['pallas_programs']}, read {default}): the "
                    "paged kernel ran interpreted or not at all")


def pool_phase() -> None:
    """The K/V pool by itself, at the server's geometry and capacity."""
    import gc

    import jax.numpy as jnp

    from pytorch_distributed_tpu.ops.attention import paged_attention
    from pytorch_distributed_tpu.serving.kv_pool import init_paged_cache

    with phase("pool") as rec:
        cfg, params, kw = serve_setup()
        bl, slots = kw["block_len"], kw["n_slots"]
        width = cfg.max_seq_len // bl
        n_blocks = slots * width + 1
        head_dim = cfg.embed_dim // cfg.num_heads
        device = jax.devices()[0]
        rec.update(n_blocks=n_blocks, block_len=bl, heads=cfg.num_heads,
                   head_dim=head_dim, bytes={}, pallas_max_abs_diff={})

        def in_use():
            gc.collect()
            stats = device.memory_stats()  # None on the CPU backend
            return stats["bytes_in_use"] if stats else None

        def fill(name, z):
            """Random contents for one leaf: values near N(0, 1) once
            dequantized."""
            if not name.endswith("_scale"):
                top = 127 if z.dtype == jnp.int8 else 1
                x = rng.normal(size=z.shape) * top / 4
                return jnp.asarray(x.clip(-top, top)).astype(z.dtype)
            if z.dtype == jnp.int8:  # fp8: power-of-two exponents
                return jnp.asarray(rng.integers(1, 3, z.shape), z.dtype)
            return jnp.asarray(rng.uniform(0.02, 0.04, z.shape), z.dtype)

        rng = np.random.default_rng(ARGS.seed)
        shapes = {"decode": (slots, 1), "chunk": (4, kw["prefill_chunk"])}
        for kv_dtype in (None, "int8", "fp8"):
            name = kv_dtype or jnp.dtype(cfg.dtype).name
            before = in_use()
            cache = jax.block_until_ready(
                init_paged_cache(cfg, params, n_blocks, bl, kv_dtype))
            held = in_use()
            logical = sum(x.size * x.dtype.itemsize
                          for x in jax.tree.leaves(cache))
            rec["bytes"][name] = {"logical": logical}
            if held is not None:
                ratio = (held - before) / logical
                rec["bytes"][name].update(device=held - before,
                                          ratio=round(ratio, 4))
                require(rec, ratio <= 1.01, f"a {name} pool of {logical} "
                        f"bytes takes {held - before} of the device")

            # the two gather spellings on one layer of it, random contents
            layer = {k: fill(k, z) for k, z in cache["block0"]["attn"].items()}
            del cache
            scales = ({} if kv_dtype is None else
                      dict(k_scale=layer["key_scale"],
                           v_scale=layer["value_scale"]))
            for what, (b, c) in shapes.items():
                q = jnp.asarray(
                    rng.normal(size=(b, c, cfg.num_heads, head_dim)),
                    cfg.dtype)
                tables = jnp.asarray(
                    rng.permutation(np.arange(1, n_blocks))[:b * width]
                    .reshape(b, width).astype(np.int32))
                start = rng.integers(0, width * bl - c, (b, 1))
                pos = jnp.asarray((start + np.arange(c)).astype(np.int32))
                reads = []
                for impl in ("dense", "pallas"):
                    with paged_read(impl):
                        reads.append(np.asarray(paged_attention(
                            q, layer["key"], layer["value"], tables, pos,
                            **scales), np.float32))
                dense, fused = reads
                diff = float(np.abs(dense - fused).max())
                # bf16 keeps 8 bits: one ulp of the largest output
                ulp = 2.0 ** (math.floor(math.log2(np.abs(dense).max())) - 7)
                key = f"{kv_dtype or 'raw'}_{what}"
                rec["pallas_max_abs_diff"][key] = diff
                require(rec, diff <= ulp, f"{key}: pallas and dense differ "
                        f"by {diff}, over one bf16 ulp ({ulp})")


def ouro_phase() -> None:
    """The looped stack through the paged engine at full width."""
    import jax.numpy as jnp

    from perfbench.harness.manifest import load_json, merged
    from perfbench.references import ouro
    from pytorch_distributed_tpu.models.transformer import (
        TransformerConfig,
        TransformerLM,
    )
    from pytorch_distributed_tpu.serving.engine import ChunkJob, PagedEngine

    with phase("ouro") as rec:
        conf = merged(load_json(os.path.join(
            ROOT, "perfbench", "configs", "ouro-2.6b.json")), ARGS.tiny)
        dtype = getattr(jnp, conf["dtype"])
        program = dict(conf["program"], num_layers=2, max_seq_len=128)
        cfg = TransformerConfig(**program, dropout=0.0, dtype=dtype,
                                attention="dense")
        ouro.configure(program)
        shapes = jax.eval_shape(TransformerLM(cfg).init, jax.random.key(0),
                                jnp.zeros((1, 8), jnp.int32))["params"]
        params = ouro.init_params(ARGS.seed, shapes, dtype)
        chunk, slots = 32, 4
        rec.update(layers=cfg.num_layers, passes=cfg.ut_steps,
                   heads=cfg.num_heads, embed_dim=cfg.embed_dim,
                   mlp_dim=cfg.mlp_width, vocab=cfg.vocab_size)

        def build():
            return PagedEngine(cfg, params, slots, n_blocks=33,
                               block_len=16, prefill_chunk=chunk)

        eng = build()
        prompts = make_prompts(cfg, [chunk, 19])
        jobs = []
        for slot, prompt in enumerate(prompts):
            require(rec, eng.admit(slot, len(prompt), 4), "admission")
            padded = np.zeros((chunk,), np.int32)
            padded[:len(prompt)] = prompt
            jobs.append(ChunkJob(slot, padded, 0, True, len(prompt) - 1))
        eng.run_chunks(jobs)
        after_chunk = np.asarray(eng.logits)
        lengths = np.array([len(p) for p in prompts] + [0] * (slots - 2))
        tokens, _ = eng.decode(lengths.astype(np.int32), lengths > 0,
                               jax.random.key(ARGS.seed))
        after_tick = np.asarray(eng.logits)
        rec["programs"] = eng.compiled_program_names()
        rec["pool_leaf"] = list(jax.tree.leaves(eng.cache)[0].shape)
        rec.update(read=eng.gather_impl, tile_blocks=eng.tile_blocks,
                   heads_folded=eng.heads_folded)

        # the reference's full forward over prompt + the decoded token:
        # row L-1 is what the chunk program left, row L what the tick did
        worst = scale = 0.0
        with jax.default_matmul_precision("highest"):
            for slot, prompt in enumerate(prompts):
                seq = np.concatenate([prompt, tokens[slot:slot + 1]])
                want = np.asarray(ouro.logits(params, jnp.asarray(seq)[None]))
                at = len(prompt)
                worst = max(worst,
                            np.abs(after_chunk[slot] - want[0, at - 1]).max(),
                            np.abs(after_tick[slot] - want[0, at]).max())
                scale = max(scale, np.abs(want[0, at - 1:]).max())
        rec.update(logit_max_abs_diff=float(worst),
                   logit_max_abs=float(scale))
        # bf16 keeps 8 bits of a logit and of every state on the way to
        # it; a cache entry read from the wrong pass moves logits by
        # their own size
        require(rec, worst <= (1e-4 if ARGS.tiny else 0.04) * scale,
                f"logits differ from the reference by {worst} (largest "
                f"logit {scale})")

        # the fused gather at heads of 128: does the chip's compiler
        # take it inside the four-trip loop? Reported either way.
        try:
            with paged_read("pallas"):
                text = build().warm_decode(execute=False).as_text()
            rec["pallas_tick"] = {"compiled": True,
                                  "kernel_in_program": KERNEL in text}
        except Exception as e:  # the compiler's refusal is the finding
            rec["pallas_tick"] = {"compiled": False,
                                  "error": str(e).splitlines()[0][:300]}


def zaya_phase() -> None:
    """Attention in a compressed latent with its tail beside the pool, and
    the dropless expert layer, through the paged engine at full width."""
    import jax.numpy as jnp

    from perfbench.harness.manifest import load_json, merged
    from perfbench.references import zaya
    from pytorch_distributed_tpu.models.transformer import (
        TransformerConfig,
        TransformerLM,
    )
    from pytorch_distributed_tpu.serving.engine import ChunkJob, PagedEngine

    with phase("zaya") as rec:
        conf = merged(load_json(os.path.join(
            ROOT, "perfbench", "configs", "zaya1-8b.json")), ARGS.tiny)
        dtype = getattr(jnp, conf["dtype"])
        chunk, slots, ticks = (8, 4, 6) if ARGS.tiny else (128, 8, 64)
        program = dict(conf["program"], num_layers=2,
                       max_seq_len=2 * chunk + ticks + 8)
        cfg = TransformerConfig(**program, dropout=0.0, dtype=dtype,
                                attention="dense")
        zaya.configure(program)
        shapes = jax.eval_shape(TransformerLM(cfg).init, jax.random.key(0),
                                jnp.zeros((1, 8), jnp.int32))["params"]
        params = zaya.init_params(ARGS.seed, shapes, dtype)
        rec.update(layers=cfg.num_layers, heads=cfg.num_heads,
                   kv_heads=cfg.num_kv_heads, head_dim=cfg.head_width,
                   embed_dim=cfg.embed_dim, experts=cfg.n_experts,
                   expert_dim=cfg.moe_dim, router_dim=cfg.router_dim,
                   vocab=cfg.vocab_size, tail=cfg.cca_tail_width)
        eng = PagedEngine(cfg, params, slots, n_blocks=65, block_len=16,
                          prefill_chunk=chunk)
        rec.update(read=eng.gather_impl, tile_blocks=eng.tile_blocks,
                   heads_folded=eng.heads_folded,
                   grouped_rows=eng.grouped_rows)
        # the experts' products are the repo's kernel on the chip, a row
        # tile of all the tick's rows (top-1: a pair a slot), XLA's elsewhere
        from pytorch_distributed_tpu.ops.grouped_matmul import row_tile

        require(rec, eng.grouped_rows == (
            row_tile(slots) if jax.default_backend() == "tpu" else 0),
            f"grouped_rows {eng.grouped_rows} for {slots} slots")
        prompts = make_prompts(cfg, [chunk + chunk // 2 + 1, chunk - 3])
        for slot, prompt in enumerate(prompts):
            require(rec, eng.admit(slot, len(prompt), ticks), "admission")
        for start in (0, chunk):
            jobs = []
            for slot, prompt in enumerate(prompts):
                seg = prompt[start:start + chunk]
                if not len(seg):
                    continue
                padded = np.zeros((chunk,), np.int32)
                padded[:len(seg)] = seg
                last = start + chunk >= len(prompt)
                jobs.append(ChunkJob(slot, padded, start, last,
                                     len(prompt) - 1 - start if last else 0))
            eng.run_chunks(jobs)
        served = [[np.asarray(eng.logits[s])] for s in range(len(prompts))]
        streams = [list(p) for p in prompts]
        positions = np.array([len(p) for p in prompts]
                             + [0] * (slots - len(prompts)), np.int32)
        active = positions > 0
        hit = []
        for _ in range(ticks):
            tokens, positions = eng.decode(positions, active,
                                           jax.random.key(ARGS.seed))
            logits = np.asarray(eng.logits)
            hit.append(eng.tick_expert_counts.sum(axis=1).tolist())
            for s in range(len(prompts)):
                streams[s].append(int(tokens[s]))
                served[s].append(logits[s])
        rec["programs"] = eng.compiled_program_names()
        rec["routed_a_layer"] = sorted({tuple(h) for h in hit})
        require(rec, all(h == [len(prompts)] * cfg.num_layers for h in hit),
                "a tick routed other than its live lanes")

        # the reference's full forward over prompt + decoded tokens: row
        # L-1 is what the last chunk left, the rows after it the ticks'.
        # A row is DECISIVE where every layer's router led its second
        # choice by MARGIN for the row's own token and the two before it
        # (the two convolutions reach that far back through the tail);
        # bf16 may send another token to another expert than float32,
        # which moves its logits, and its followers', by their own size
        # and is no fault: those rows are counted, not judged (a flip
        # further back reaches a row only through its share of the
        # attention)
        worst = close = scale = 0.0
        rows = decisive = 0
        with jax.default_matmul_precision("highest"):
            for s, prompt in enumerate(prompts):
                seq = jnp.asarray(streams[s][:-1], jnp.int32)[None]
                want = np.asarray(zaya.logits(params, seq))
                margins = np.asarray(zaya.walk(params, seq)[1])[:, 0]
                own = margins.min(0)
                near = np.minimum(own, np.minimum(np.roll(own, 1),
                                                  np.roll(own, 2)))
                sure = near >= (
                    0.0 if ARGS.tiny else MARGIN)  # float32 flips nothing
                at = len(prompt) - 1
                gaps = np.abs(np.stack(served[s][:-1]) - want[0, at:]).max(-1)
                rows += len(gaps)
                decisive += int(sure[at:].sum())
                if sure[at:].any():
                    worst = max(worst, gaps[sure[at:]].max())
                if not sure[at:].all():
                    close = max(close, gaps[~sure[at:]].max())
                scale = max(scale, np.abs(want[0, at:]).max())
        rec.update(logit_max_abs_diff=float(worst),
                   logit_max_abs=float(scale), ticks=ticks, rows=rows,
                   decisive_rows=decisive,
                   close_call_rows_max_abs_diff=float(close))
        # two layers in bf16 keep a logit to a few hundredths of the
        # largest; a tail read from the wrong slot moves every row's
        # logits by their own size
        require(rec, decisive >= (rows if ARGS.tiny else rows // 2),
                f"only {decisive} of {rows} rows are decisive")
        require(rec, worst <= (1e-4 if ARGS.tiny else 0.06) * scale,
                f"logits differ from the reference by {worst} (largest "
                f"logit {scale})")


# ---- four chips ------------------------------------------------------------


def device_ids(tree) -> set:
    return {s.device.id for leaf in jax.tree.leaves(tree)
            for s in leaf.addressable_shards}


def dp_phase(workdir: str) -> None:
    """Data-parallel ResNet over every device against the same global
    batch and seed on one. ``--sync-bn``: with each replica's own batch
    statistics (the recipes' default, as DDP) the two are different
    functions of the batch and agree only loosely."""
    n = jax.device_count()
    per_device, steps = (4, 3) if ARGS.tiny else (64, 3)

    def run(rec, name, mesh, batch):
        argv = ["--save-dir", os.path.join(workdir, name), "--epochs", "1",
                "--log-every", "1", "--batch-size", str(batch), "--sync-bn",
                "--synthetic-size", str(per_device * n * steps)]
        argv += ["--tiny"] if ARGS.tiny else ["--synthetic"]
        trainer = common.build_trainer(
            common.parse_args("chip_smoke", argv), mesh, precision="bf16"
        )
        return trainer, fit(trainer, rec)[0]

    with phase("dp_train") as rec:
        want = run(rec, "dp_one", single_device_mesh(), per_device * n)[1]
        dp, got = run(rec, "dp_all", make_mesh(), per_device)
        ids = device_ids(dp.state.params)
        rec.update(devices=n, global_batch=per_device * n,
                   losses_one_device=want, losses_data_parallel=got,
                   param_device_ids=sorted(ids),
                   all_reduce="all-reduce" in step_text(dp))
        # bf16 activations, batch statistics reduced in another order:
        # the losses agree to bf16's 8 bits, not to fp32's 24
        require(rec, np.allclose(got, want, rtol=2e-2),
                "data-parallel losses differ from one device's")
        require(rec, len(ids) == n, f"params live on devices {sorted(ids)}")
        require(rec, rec["all_reduce"], "no all-reduce in the DP step")


def sp_tp_phase(workdir: str) -> None:
    """Ring-flash LM over seq x model = 2 x 2 against one-device flash at
    the same seed and global batch."""
    seq, batch, steps = (None, 2, 3) if ARGS.tiny else (4096, 4, 3)

    def run(rec, name, extra, devices):
        argv = ["--save-dir", os.path.join(workdir, name), "--log-every", "1",
                "--epochs", "1", "--batch-size", str(batch),
                "--synthetic-size", str(batch * steps)] + extra
        argv += ["--tiny"] if ARGS.tiny else ["--synthetic", "--seq-len",
                                              str(seq)]
        trainer = lm_pretrain.build_trainer(
            common.parse_lm_args("chip_smoke", argv), devices=devices
        )
        return trainer, fit(trainer, rec)[0]

    with phase("sp_tp_lm") as rec:
        want = run(rec, "lm_one", ["--seq-parallel", "1"],
                   jax.devices()[:1])[1]
        par, got = run(rec, "lm_sp_tp",
                       ["--seq-parallel", "2", "--model-parallel", "2"],
                       jax.devices()[:4])
        text = step_text(par)
        rec.update(mesh=dict(par.mesh.shape),
                   attention=par.model_config.attention,
                   seq=par.model_config.max_seq_len, global_batch=batch,
                   losses_one_device=want, losses_sp_tp=got,
                   kernel_in_step=KERNEL in text,
                   collective_permute="collective-permute" in text,
                   param_device_ids=sorted(device_ids(par.state.params)))
        # tests/test_zigzag_lm.py holds fp32 runs to rtol 2e-4; bf16 has
        # 16 fewer mantissa bits, and the ring adds its partials in
        # another order than the one-device kernel
        require(rec, np.allclose(got, want, rtol=2e-2),
                "seq x tensor parallel losses differ from one device's")
        require(rec, rec["collective_permute"],
                "no collective-permute in the ring step")
        if not ARGS.tiny:
            require(rec, rec["kernel_in_step"],
                    f"no {KERNEL} in the ring-flash step")


def fleet_phase() -> None:
    """Four replicas, one per device. Each must stream exactly what one
    replica streams for the same requests — placement changes nothing.
    Against one replica serving all sixteen the streams only have to
    agree from the same context: a request prefilled beside three others
    runs other programs than one prefilled beside seven, and in bf16 a
    row's result depends on the program it rode in (on the CPU in fp32
    the streams are identical; on the v5e 251/256 tokens agree)."""
    from pytorch_distributed_tpu.fleet import FleetRouter

    max_new, n = 16, 4
    with phase("fleet") as rec:
        cfg, params, kw = serve_setup()
        rng = np.random.default_rng(ARGS.seed + 1)
        prompts = make_prompts(cfg, rng.integers(8, 60, size=16))

        def run(which, **router_kw):
            router = FleetRouter(cfg, params, **router_kw, **kw)
            rids = [router.submit(prompts[j], max_new, session=j)
                    for j in which]
            results = router.drain()
            require(rec, all(s.engine.allocator.in_use == 0
                             for s in router.replicas),
                    f"{router_kw}: blocks still held after drain")
            return (router, [[int(t) for t in results[r]] for r in rids],
                    [router.placement[r] for r in rids])

        everything = range(len(prompts))
        _, want, _ = run(everything, n_replicas=1)
        router, got, home = run(everything, n_replicas=n)
        placed = [sorted(device_ids((s.engine.params, s.engine.cache)))
                  for s in router.replicas]
        identical = True
        for i in range(n):
            mine = tuple(j for j in everything if home[j] == i)
            alone = run(mine, n_replicas=1)[1]  # one replica, its requests
            identical &= alone == [got[j] for j in mine]
        rate = same_context_agreement(router, prompts, want, got)
        rec.update(
            replica_device_ids=placed,
            requests_per_replica=[home.count(i) for i in range(n)],
            identical_to_one_replica_on_the_same_requests=identical,
            agreement_with_one_replica_on_all=round(rate, 4),
            stream_agreement_with_one_replica_on_all=round(
                agreement(want, got), 4),
        )
        require(rec, identical, "a replica streams otherwise than one "
                "replica given the same requests")
        require(rec, rate >= 0.9, "agreement with one replica serving "
                f"every request {rate:.3f} < 0.9")
        require(rec, len({tuple(p) for p in placed}) == n
                and all(len(p) == 1 for p in placed),
                f"replicas placed on {placed}")
        rec.update(prompts=len(prompts), max_new=max_new)


# ---- driver ----------------------------------------------------------------


def main() -> None:
    device = jax.devices()[0]
    if not ARGS.tiny and device.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, jax found {jax.devices()}; "
                 "--tiny rehearses on the CPU")
    want = 4 if ARGS.multichip else 1
    if jax.device_count() < want:
        sys.exit(f"chip_smoke: needs {want} device(s), jax found "
                 f"{jax.devices()}")
    cache_dir = enable_compile_cache()
    emit(phase="env", jax=jax.__version__, platform=device.platform,
         device_kind=device.device_kind, device_count=jax.device_count(),
         tiny=ARGS.tiny, multichip=ARGS.multichip,
         compile_cache_dir=cache_dir,
         compile_cache_entries=len(os.listdir(cache_dir)),
         TPU_WORKER_HOSTNAMES=os.environ.get("TPU_WORKER_HOSTNAMES"))
    # one host however many chips: the rendezvous must stay a no-op
    init_process_group()
    if jax.process_count() != 1:
        sys.exit(f"chip_smoke: {jax.process_count()} processes; this is a "
                 "one-host check")
    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        if ARGS.multichip:
            dp_phase(workdir)
            sp_tp_phase(workdir)
            fleet_phase()
        else:
            resnet_phase(workdir)
            lm_phase(workdir)
            server_phase()
            pool_phase()
            ouro_phase()
            zaya_phase()
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    hits, compile_s = process_compile_totals()
    emit(phase="compile_cache", dir=cache_dir, hits=hits,
         entries=len(os.listdir(cache_dir)), compile_s=round(compile_s, 2))
    leftover = [t.name for t in threading.enumerate()
                if t is not threading.main_thread() and not t.daemon]
    if leftover:
        sys.exit(f"chip_smoke: threads still running: {leftover}")
    emit(ok=True, device={"platform": device.platform,
                          "kind": device.device_kind,
                          "count": jax.device_count()})


if __name__ == "__main__":
    with contextlib.redirect_stdout(sys.stderr):
        main()
