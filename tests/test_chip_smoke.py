"""``chip_smoke.py`` off the chip: the ``--tiny`` rehearsal runs the real
control flow end to end on the CPU, the full-width run refuses to start
without a TPU, and the compile cache lives where the one rule says."""

import importlib.util
import json
import os
import subprocess
import sys

import numpy as np

from pytorch_distributed_tpu.utils.env import REPO_ROOT, compile_cache_dir

SMOKE = os.path.join(REPO_ROOT, "chip_smoke.py")


def _run(args, cache_dir):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(cache_dir))
    return subprocess.run([sys.executable, SMOKE, *args], env=env,
                          capture_output=True, text=True, timeout=600)


def test_tiny_smoke_runs_every_phase(tmp_path):
    cache = tmp_path / "cache"
    out = _run(["--tiny"], cache)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(line) for line in out.stdout.splitlines()]
    assert lines[-1] == {"ok": True, "device": lines[-1]["device"]}
    assert lines[-1]["device"]["platform"] == "cpu"  # never a TPU's line
    phases = [r["phase"] for r in lines[:-1]]
    assert phases == ["env", "resnet", "sync_check", "lm", "server", "pool",
                      "ouro", "zaya", "compile_cache"]
    assert not any(r.get("failed") for r in lines[:-1])
    # the exported directory is the cache, and the only one written
    assert lines[0]["compile_cache_dir"] == lines[-2]["dir"] == str(cache)
    assert lines[-2]["entries"] == len(os.listdir(cache)) > 0


def test_full_width_refuses_to_run_without_a_tpu(tmp_path):
    out = _run([], tmp_path / "cache")
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "needs a TPU" in out.stderr


def test_compile_cache_rule(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert compile_cache_dir() == "/some/dir"
    assert compile_cache_dir("/the/flag") == "/some/dir"  # the flag loses
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert compile_cache_dir("/the/flag") == "/the/flag"
    assert compile_cache_dir() == os.path.join(REPO_ROOT, ".jax_cache")


def test_same_context_agreement_counts_flips_not_their_cascades():
    """One flipped token costs one token: the stream is run again from the
    reference's context, and a second flip there is found too."""
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)

    prompts = [np.array([1, 2, 3], np.int32), np.array([7, 8], np.int32)]
    want = [list(range(10, 20)), list(range(30, 40))]

    class Replays:
        """Continues ``want`` from any context, but flips stream 1 again
        at position 7 when resumed from position 3."""

        def __init__(self):
            self.streams = {}

        def submit(self, prompt, max_new):
            i = 0 if prompt[0] == 1 else 1
            done = len(prompt) - len(prompts[i])
            assert list(prompt[len(prompts[i]):]) == want[i][:done]
            tokens = want[i][done:done + max_new]
            if (i, done) == (1, 3):
                tokens = tokens[:4] + [-1] * (len(tokens) - 4)
            self.streams[len(self.streams)] = tokens
            return len(self.streams) - 1

        def drain(self):
            return self.streams

    got = [want[0][:5] + [99] * 5, want[1][:2] + [98] * 8]
    assert smoke.agreement(want, got) == 7 / 20  # cascades included
    fake = Replays()
    rate = smoke.same_context_agreement(fake, prompts, want, got)
    assert rate == 1 - 3 / 20 and len(fake.streams) == 3
    last = [want[0][:9] + [5], want[1]]  # a flip on the very last token
    assert smoke.same_context_agreement(Replays(), prompts, want, last) \
        == 1 - 1 / 20
