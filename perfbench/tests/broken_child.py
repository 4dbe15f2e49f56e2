"""Drive the rest of a run with the timed path broken underneath, in a
process of its own: ``python broken_child.py <fault> <workload>``. Skips
nothing of the harness but its look for a chip (``--tiny 1``)."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ["JAX_PLATFORMS"] = "cpu"


def unchanged_step():
    """The train step computes its loss and hands back the state it got."""
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_tpu.train import lm_trainer

    real_make = lm_trainer.make_lm_train_step

    def make(*a, **kw):
        real = real_make(*a, **kw)

        def broken(state, batch):
            _, metrics = real(jax.tree.map(jnp.copy, state), batch)
            return state, metrics

        broken.lower = real.lower
        broken._cache_size = real._cache_size
        return broken

    lm_trainer.make_lm_train_step = make


def half_batch():
    """Half of the rows never reach the loss."""
    import numpy as np

    from pytorch_distributed_tpu.train import lm_trainer

    real = lm_trainer.shard_lm_batch

    def broken(mesh, batch, **kw):
        batch = dict(batch)
        w = np.array(batch["weights"])
        w[: len(w) // 2] = 0.0
        batch["weights"] = w
        return real(mesh, batch, **kw)

    lm_trainer.shard_lm_batch = broken


def altered_token():
    """Every token the router hands out is off by one."""
    from pytorch_distributed_tpu.fleet import router

    real = router.FleetRouter.step

    def broken(self):
        vocab = self._config.vocab_size
        return [(rid, (tok + 1) % vocab) for rid, tok in real(self)]

    router.FleetRouter.step = broken


FAULTS = {"unchanged-step": unchanged_step, "half-batch": half_batch,
          "altered-token": altered_token}

if __name__ == "__main__":
    FAULTS[sys.argv[1]]()
    from perfbench import run

    sys.exit(run.main(["--workload", sys.argv[2], "--seed", "4300000011",
                       "--seconds", "1", "--trace", "0", "--tiny", "1"]))
