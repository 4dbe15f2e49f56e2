"""Kill-matrix child for the KV pressure tier: a tiny real serve cycle.

Launched as a subprocess by tests/test_pressure.py. Run 1 carries a
``PDT_FAULT_PLAN`` that SIGKILLs the process at a swap hazard site
(``kv.swap_out_d2h`` / ``kv.host_write`` / ``kv.swap_in_h2d``) mid-cycle;
run 2 relaunches with no plan and must serve the same workload to
completion with token streams identical to an unpreempted reference —
the "fleet host restarts clean" proof: a swap interrupted by SIGKILL
leaves nothing durable to corrupt (the host store dies with the
process), so a relaunch simply serves.

The child streams flight-recorder events to a durable mirror
(``flightrec.jsonl``) so the parent can see the preempt/swap events that
preceded the kill, and writes ``result.json`` with every request's token
stream on a clean finish.

Round 16 (``--fleet-async``): the same seeded workload through a
2-replica ``FleetRouter`` — its dispatch-then-collect loop with worker
threads — so the kill matrix gains an async-loop cell:
SIGKILL inside a swap window while ticks are in flight and workers hold
queued JSONL must still leave nothing durable to corrupt, and the
relaunch must serve token streams identical to the synchronous
reference.

Not a pytest module (no ``test_`` prefix) — invoke as
``python tests/serve_child.py --save-dir DIR [--fleet-async]``.
"""

import argparse
import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


def workload(cfg):
    """The fixed, seeded workload both runs (and the parent's reference
    scheduler) serve — determinism is what makes the token-identity
    assertion meaningful across processes."""
    rng = np.random.default_rng(7)
    lens = [9, 17, 5, 13, 21, 7, 11, 15]
    return [rng.integers(1, cfg.vocab_size, l).astype(np.int32)
            for l in lens]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--save-dir", required=True)
    ap.add_argument("--max-new", type=int, default=6)
    ap.add_argument("--fleet-async", action="store_true",
                    help="serve through a 2-replica async-host fleet "
                         "(dispatch-then-collect + worker threads) "
                         "instead of the single synchronous scheduler")
    args = ap.parse_args()

    from pytorch_distributed_tpu.models.transformer import (
        TransformerLM,
        tiny_config,
    )
    from pytorch_distributed_tpu.serving import Scheduler
    from pytorch_distributed_tpu.telemetry import FlightRecorder

    cfg = tiny_config(attention="dense", max_seq_len=64)
    params = TransformerLM(cfg).init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    flightrec = FlightRecorder(
        mirror_path=os.path.join(args.save_dir, "flightrec.jsonl")
    )
    if args.fleet_async:
        from pytorch_distributed_tpu.fleet import FleetRouter, SLOConfig

        # same over-commit per replica; the async loop keeps ticks in
        # flight and worker threads hold queued telemetry when the
        # fault plan SIGKILLs inside the swap window
        r = FleetRouter(
            cfg, params, n_replicas=2,
            slo=SLOConfig(spill_queue_depth=2, shed_queue_depth=10**6),
            flightrec=flightrec, n_slots=4, n_blocks=10, block_len=8,
            prefill_chunk=16, offload=True, preempt_on_oom=True,
            swap_policy="swap", protect_ticks=0,
        )
        rids = [r.submit(p, args.max_new) for p in workload(cfg)]
        streams = r.drain()
        m = r.metrics()
        assert m["preempts"] >= 1, "workload never preempted"
    else:
        # over-committed on purpose: the pool holds ~3 chains for 4
        # lanes + queue, so admission pressure preempts (forced swap
        # path — the hazard sites under test are the swap's)
        s = Scheduler(
            cfg, params, n_slots=4, n_blocks=10, block_len=8,
            prefill_chunk=16, offload=True, preempt_on_oom=True,
            swap_policy="swap", protect_ticks=0, flightrec=flightrec,
        )
        rids = [s.submit(p, args.max_new) for p in workload(cfg)]
        streams = s.drain()
        m = s.metrics()
        assert m["preempts"] >= 1, "workload never preempted"
    with open(os.path.join(args.save_dir, "result.json"), "w") as f:
        json.dump({
            "streams": {str(rid): streams[rid] for rid in rids},
            "preempts": m["preempts"],
            "swap_aborts": m["swap_aborts"],
        }, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
