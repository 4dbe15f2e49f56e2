"""The one traffic generator: a mix is a data file, this turns it into work.

A serving mix names two length distributions and a pairing. The lengths
are a FIXED multiset (the quantiles (i + 0.5)/n of each distribution), so
every seed offers token for token the same work; the seed only shuffles the
order, draws the token ids and the first wave's residual lives. A training
mix names a corpus or a record split made from a fixed seed of its own.
"""

from __future__ import annotations

import json
import math
import os
from statistics import NormalDist

import numpy as np

def load_mix(root: str, name: str) -> dict:
    path = os.path.join(root, "traffic", name + ".json")
    with open(path) as f:
        mix = json.load(f)
    mix["name"] = name
    return mix


def lognormal_quantiles(median: float, sigma: float, lo: int, hi: int,
                        n: int) -> list:
    """The n values at quantiles (i + 0.5)/n of a clipped lognormal,
    rounded to whole tokens, ascending."""
    nd = NormalDist()
    out = []
    for i in range(n):
        z = nd.inv_cdf((i + 0.5) / n)
        v = int(round(median * math.exp(sigma * z)))
        out.append(min(max(v, lo), hi))
    return out


def length_multiset(mix: dict) -> list:
    """[(prompt_len, output_len)] of one cycle, in the file's fixed pairing."""
    n = int(mix["multiset"])
    p, o = mix["prompt"], mix["output"]
    prompts = lognormal_quantiles(p["median"], p["sigma"], p["min"],
                                  p["max"], n)
    outputs = lognormal_quantiles(o["median"], o["sigma"], o["min"],
                                  o["max"], n)
    perm = mix["pairing"]
    if sorted(perm) != list(range(n)):
        raise ValueError(f"{mix['name']}: pairing is not a permutation of "
                         f"range({n})")
    return [(prompts[i], outputs[perm[i]]) for i in range(n)]


class RequestStream:
    """Endless seeded stream of requests over the mix's multiset.

    Each cycle is the whole multiset in a new seeded order, so any window
    of n consecutive requests differs from the multiset by at most one
    cycle's boundary. ``first_wave`` requests have their output cut to a
    seeded uniform fraction: the residual lives of a system long in service.
    """

    def __init__(self, mix: dict, seed: int, vocab_size: int,
                 first_wave: int):
        self.pairs = length_multiset(mix)
        self.rng = np.random.default_rng([int(seed) & 0x7FFFFFFFFFFFFFFF,
                                          0x7A])
        self.vocab = int(vocab_size)
        self.first_wave = int(first_wave)
        self.issued = 0
        self._order: list = []

    def next(self) -> tuple:
        """(prompt tokens [L] int32, max_new) of the next request."""
        if not self._order:
            self._order = list(self.rng.permutation(len(self.pairs)))
        plen, olen = self.pairs[self._order.pop()]
        if self.issued < self.first_wave:
            # a life already partly lived: uniform in (0, 1] of the whole
            olen = max(1, int(math.ceil(olen * (1.0 - self.rng.random()))))
        self.issued += 1
        # token 0 is the program's pad id; never sent
        prompt = self.rng.integers(1, self.vocab, size=plen, dtype=np.int64)
        return prompt.astype(np.int32), int(olen)


def token_corpus(mix: dict, seed: int, vocab_size: int) -> np.ndarray:
    """A flat token array of ``sequences`` x ``seq_len`` ids from the seed."""
    n = int(mix["sequences"]) * int(mix["seq_len"])
    rng = np.random.default_rng([int(seed) & 0x7FFFFFFFFFFFFFFF, 0x7B])
    return rng.integers(1, vocab_size, size=n, dtype=np.int32)


def ensure_record_split(mix: dict, data_dir: str) -> str:
    """The packed raw-record split a training mix reads, written once per
    checkout from the mix's own fixed seed and reused when its size is
    right. Returns the directory that holds ``train.rawtprc``."""
    from pytorch_distributed_tpu.data.packed_record import PackedRecordWriter
    from pytorch_distributed_tpu.data.raw import encode_raw_record

    n, px = int(mix["records"]), int(mix["stored_px"])
    classes = int(mix["classes"])
    os.makedirs(data_dir, exist_ok=True)
    path = os.path.join(data_dir, "train.rawtprc")
    stamp = path + ".json"
    want = {"records": n, "stored_px": px, "classes": classes,
            "seed": int(mix["records_seed"])}
    if os.path.exists(path) and os.path.exists(stamp):
        with open(stamp) as f:
            if json.load(f) == want:
                return data_dir
    rng = np.random.default_rng(want["seed"])
    with PackedRecordWriter(path) as w:
        for _ in range(n):
            # smooth low-frequency image + noise: bytes a JPEG of a photo
            # would decode to are not white noise, and PIL's resize cost
            # does not depend on content either way
            base = rng.integers(0, 256, size=(8, 8, 3), dtype=np.uint8)
            img = np.kron(base, np.ones((px // 8, px // 8, 1), np.uint8))
            img = img + rng.integers(0, 32, size=img.shape, dtype=np.uint8)
            w.write(encode_raw_record(img.astype(np.uint8),
                                      int(rng.integers(0, classes))))
    with open(stamp, "w") as f:
        json.dump(want, f)
    return data_dir
