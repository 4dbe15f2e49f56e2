"""The lower-precision control of ``zaya1-8b.reason-long-backlog``, read so
that it fits beside the cell's weights.

``run.py --control fp8`` ends in RESOURCE_EXHAUSTED in this cell: beside
9.4 GB of weights ``checks.served_token_gaps`` holds the reference's and
the control's [2560, 262272] float32 logits and a slice of each, 10 GB.
This script is ``run.py`` with that one function replaced by one that
gives THE SAME NUMBERS from less: the sequence padded to the next multiple
of 1,024 and not to the whole context (a causal model does not see the
padding), the served tokens' and the control's logits picked out of the
buffer and not out of a slice of it, one control's logits alive at a time.
The run, the window and the sound comparison that decides ``correct`` are
untouched; the result line is ``run.py``'s.

It reads a SECOND control beside the first: ``cast`` on the expert layer's
products alone (the router's and the experts': ``references/zaya.py``'s
``scope="experts"``), attention and the head in float32. The widest gap of
the served tokens (``sound``) and of each control (``all``, ``experts``),
and the share of the checked positions whose token is not the reference's
first, go to the errors' stream as one JSON line, ``control_scopes``:

    python3 perfbench/controls/zaya_lean.py --workload \\
        zaya1-8b.reason-long-backlog --seed 7 --seconds 50 --trace 0 \\
        --control fp8
"""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

PAD = 1024
SCOPES = ("all", "experts")  # the harness's control; the expert layer's


def lean_gaps(passes: tuple, weights, prompt, served: list,
              pad_to: int) -> dict:
    """``checks.served_token_gaps``' numbers for one request (``gap``,
    ``tokens``, ``control_gap``) and, under ``scopes``, for the served
    tokens (``sound``) and for each control in ``passes`` after the
    reference's own (``SCOPES``, in order): the widest gap, and
    ``off_first``, the share of the checked positions whose token is not
    the reference's first."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    plain, *lowered = passes
    seq = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
    tokens = np.zeros((min(pad_to, -(-len(seq) // PAD) * PAD),), np.int32)
    tokens[:len(seq)] = seq
    tokens = jnp.asarray(tokens)
    n, at = len(served), len(prompt) - 1
    rows = at + jnp.arange(n)
    with jax.default_matmul_precision("highest"):
        lg = plain(weights, tokens)
        best = jnp.max(lg, -1)[at:at + n]
        top = jnp.argmax(lg, -1)[at:at + n]

        def against(first):
            return {"gap": float(jnp.max(best - lg[rows, first])),
                    "off_first": float(jnp.mean(first != top))}

        scopes = {"sound": against(jnp.asarray(served, jnp.int32))}
        for scope, lower in zip(SCOPES, lowered):
            scopes[scope] = against(
                jnp.argmax(lower(weights, tokens), -1)[at:at + n])
    out = {"gap": scopes["sound"]["gap"], "tokens": n, "scopes": scopes}
    if lowered:
        out["control_gap"] = scopes[SCOPES[0]]["gap"]
    return out


def main(argv=None) -> int:
    from perfbench import run

    argv = sys.argv[1:] if argv is None else argv
    args = run.parse(argv)
    if not args.control:
        raise SystemExit("zaya_lean: give --control (a cast of "
                         "harness/weights.py)")
    if args.tiny:  # before jax is imported, as run.main has it
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    from perfbench.harness import checks

    passes_of = checks.logits_pass
    experts_only = []  # the jitted pass, once the job has built its own
    read = []

    def logits_pass(ref, cast=None):
        if cast is not None:
            experts_only.append(jax.jit(
                lambda p, t: ref.logits(p, t[None], cast, "experts")[0]))
        return passes_of(ref, cast)

    def served_token_gaps(passes, weights, prompt, served, pad_to):
        read.append(lean_gaps(passes + tuple(experts_only), weights, prompt,
                              served, pad_to))
        return read[-1]

    checks.logits_pass = logits_pass
    checks.served_token_gaps = served_token_gaps
    rc = run.main(argv)
    tokens = sum(r["tokens"] for r in read)
    print(json.dumps({"control_scopes": {
        scope: {"served_logit_gap_max": max(r["scopes"][scope]["gap"]
                                            for r in read),
                "off_first_share": sum(
                    r["scopes"][scope]["off_first"] * r["tokens"]
                    for r in read) / tokens}
        for scope in (("sound",) + SCOPES if read else ())},
        "requests": len(read), "tokens": tokens}),
        file=sys.stderr, flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
