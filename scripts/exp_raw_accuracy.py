"""Empirical bound on the --raw fast path's augmentation deviation.

The raw pipeline (data/raw.py) applies RandomResizedCrop to the STORED
center-crop instead of the original image — documented, but round 2
shipped no experiment bounding the accuracy effect (VERDICT r2 weak #7:
"the accuracy claim and the throughput claim ride different code
paths"). This trains the same tiny ResNet for a fixed budget on the SAME
underlying images through both pipelines and reports the val-accuracy
delta, at a scaled-down geometry (96px originals → 48px stored crop →
32px training crop, mirroring 512-ish → 256 → 224).

Synthetic but learnable data: each class is a 2-D sinusoid pattern with
class-dependent frequency/orientation plus noise, so accuracy is far
from chance and sensitive to what the crops see.

Run: JAX_PLATFORMS=cpu python scripts/exp_raw_accuracy.py
Emits one JSON line per (pipeline, seed) and a summary line.
"""

from __future__ import annotations

import io
import json
import os
import sys
import tempfile

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

import jax.numpy as jnp
import numpy as np

N_CLASSES = 8
N_TRAIN, N_VAL = 512, 256
ORIG, STORED, CROP = 96, 48, 32
STEPS, BATCH = 80, 32


def make_image(cls: int, rng: np.random.Generator) -> np.ndarray:
    """Class-dependent sinusoid + noise, uint8 HWC."""
    y, x = np.mgrid[0:ORIG, 0:ORIG] / ORIG
    freq = 2 + cls
    angle = cls * np.pi / N_CLASSES
    pattern = np.sin(2 * np.pi * freq * (x * np.cos(angle) + y * np.sin(angle)))
    img = np.stack([
        pattern,
        np.roll(pattern, cls, axis=0),
        -pattern,
    ], axis=-1)
    img = (img * 0.4 + 0.5) + rng.normal(0, 0.15, img.shape)
    return (np.clip(img, 0, 1) * 255).astype(np.uint8)


def jpeg_bytes(img: np.ndarray) -> bytes:
    from PIL import Image

    buf = io.BytesIO()
    Image.fromarray(img).save(buf, format="JPEG", quality=90)
    return buf.getvalue()


def build_splits(root: str):
    from pytorch_distributed_tpu.data.imagenet import write_imagenet_split
    from pytorch_distributed_tpu.data.raw import write_imagenet_raw_split

    rng = np.random.default_rng(0)
    for split, n in (("train", N_TRAIN), ("val", N_VAL)):
        imgs = []
        for i in range(n):
            cls = i % N_CLASSES
            imgs.append((jpeg_bytes(make_image(cls, rng)), cls))
        write_imagenet_split(os.path.join(root, f"{split}.tprc"), imgs)
        write_imagenet_raw_split(
            os.path.join(root, f"{split}.rawtprc"), imgs, image_size=STORED
        )


def run(root: str, pipeline: str, seed: int) -> float:
    from pytorch_distributed_tpu.data import transforms as T
    from pytorch_distributed_tpu.data.imagenet import ImageNet
    from pytorch_distributed_tpu.data.raw import RawImageNet
    from pytorch_distributed_tpu.data.sampler import DistributedSampler
    from pytorch_distributed_tpu.models.resnet import BasicBlock, ResNet
    from pytorch_distributed_tpu.ops.optim import sgd_with_weight_decay
    from pytorch_distributed_tpu.parallel import (
        replicated_sharding,
        shard_batch,
        single_device_mesh,
    )
    from pytorch_distributed_tpu.train.state import TrainState
    from pytorch_distributed_tpu.train.step import (
        make_eval_step,
        make_train_step,
    )

    if pipeline == "jpeg":
        train_tf = T.Compose([
            T.RandomResizedCrop(CROP), T.RandomHorizontalFlip(),
            T.Normalize(),
        ])
        eval_tf = T.Compose([T.Resize(STORED), T.CenterCrop(CROP),
                             T.Normalize()])
        train_ds = ImageNet("train", data_dir=root, transform=train_tf)
        val_ds = ImageNet("val", data_dir=root, transform=eval_tf)
    else:
        train_ds = RawImageNet("train", data_dir=root, crop_size=CROP,
                               aug="rrc")
        val_ds = RawImageNet("val", data_dir=root, crop_size=CROP,
                             aug="none")

    mesh = single_device_mesh()
    model = ResNet(stage_sizes=(1, 1), block_cls=BasicBlock,
                   num_classes=N_CLASSES, num_filters=8, dtype=jnp.float32)
    tx = sgd_with_weight_decay(0.05, momentum=0.9, weight_decay=1e-4)
    state = TrainState.create(model, tx, jax.random.key(seed),
                              (1, CROP, CROP, 3))
    state = jax.device_put(state, replicated_sharding(mesh))
    train_step = make_train_step(mesh)
    eval_step = make_eval_step(mesh)

    sampler = DistributedSampler(len(train_ds), seed=seed)
    loader = train_ds.loader(BATCH, sampler=sampler, num_workers=0,
                             drop_last=True)
    step = 0
    epoch = 0
    while step < STEPS:
        sampler.set_epoch(epoch)
        for host_batch in loader.iter_batches(0):
            state, _ = train_step(state, shard_batch(mesh, host_batch))
            step += 1
            if step >= STEPS:
                break
        epoch += 1

    from pytorch_distributed_tpu.ops.metrics import ClassificationMetrics

    metrics = jax.device_put(ClassificationMetrics.empty(),
                             replicated_sharding(mesh))
    vloader = val_ds.loader(BATCH, num_workers=0, drop_last=True)
    for host_batch in vloader.iter_batches(0):
        metrics = eval_step(state, shard_batch(mesh, host_batch), metrics)
    return float(jax.device_get(metrics).summary()["acc1"])


def main() -> None:
    with tempfile.TemporaryDirectory() as root:
        build_splits(root)
        accs = {"jpeg": [], "raw": []}
        for seed in (0, 1):
            for pipeline in ("jpeg", "raw"):
                acc = run(root, pipeline, seed)
                accs[pipeline].append(acc)
                print(json.dumps({"pipeline": pipeline, "seed": seed,
                                  "val_acc1": round(acc, 2)}), flush=True)
        mj = float(np.mean(accs["jpeg"]))
        mr = float(np.mean(accs["raw"]))
        print(json.dumps({
            "raw_accuracy_summary": {
                "jpeg_mean_acc1": round(mj, 2),
                "raw_mean_acc1": round(mr, 2),
                "delta_pp": round(mr - mj, 2),
                "steps": STEPS, "geometry": f"{ORIG}->{STORED}->{CROP}",
            }
        }))


if __name__ == "__main__":
    main()
