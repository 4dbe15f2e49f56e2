"""The comparison that decides ``correct``: what the timed path produced
against the configuration's plain reference, each number beside a limit
of its own (``limits`` in the cell's file; PERF.md gives the readings
each was set from)."""

from __future__ import annotations

import statistics

import jax
import jax.numpy as jnp
import numpy as np


def check(name: str, value: float, limit: float, **notes) -> dict:
    value = float(value)
    return {"name": name, "value": value, "limit": float(limit),
            "ok": bool(np.isfinite(value) and value <= limit), **notes}


def leaf_norms(tree) -> dict:
    """{leaf path: l2 norm} in one device call."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    norms = jax.jit(lambda leaves: [
        jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
        for x in leaves])([x for _, x in flat])
    return {jax.tree_util.keystr(p): float(n)
            for (p, _), n in zip(flat, jax.device_get(norms))}


def worst_leaf_gap(program: dict, reference: dict) -> tuple:
    """(gap, leaf): the widest gap between the program's norm of a leaf
    and the reference's, against the reference's norm of that leaf or of
    the median leaf, whichever is larger (some gradients are all but
    zero)."""
    med = statistics.median(reference.values())
    return max((abs(program[k] - r) / max(r, med), k)
               for k, r in reference.items())


# ---- training ----------------------------------------------------------


def reference_training(ref, optim: dict, weights, aux, batches: list,
                       blocks_of, cast=None) -> dict:
    """The first steps in float32 at 'highest' matrix precision, in blocks
    of rows so that it fits (one block at a time inside one program a
    step): per step the loss, and the per-leaf norms of the first gradient
    and of the parameters' change after all steps. ``blocks_of(batch)``
    stacks a host batch into [blocks, rows, ...] arrays."""
    from perfbench.references import optim as ro

    def loss_fn(p, a, block):
        ls, n, new_aux = ref.loss_sum(p, a, block, cast)
        return ls, (n, new_aux)

    def step_grad(p, a, stacked):
        """Loss and gradient of the whole batch: blocks in sequence."""
        def one(carry, block):
            (ls, (n, new_aux)), g = jax.value_and_grad(
                loss_fn, has_aux=True)(p, a, block)
            tot, cnt, acc, aux_acc = carry
            return (tot + ls, cnt + n, jax.tree.map(jnp.add, acc, g),
                    jax.tree.map(jnp.add, aux_acc, new_aux)), None

        zeros = jax.tree.map(jnp.zeros_like, p)
        aux0 = jax.tree.map(jnp.zeros_like, a)
        (tot, cnt, acc, aux_acc), _ = jax.lax.scan(
            one, (jnp.float32(0), jnp.float32(0), zeros, aux0), stacked)
        blocks = jax.tree.leaves(stacked)[0].shape[0]
        return (tot / cnt, jax.tree.map(lambda x: x / cnt, acc),
                jax.tree.map(lambda x: x / blocks, aux_acc))

    if optim["name"] == "adamw":
        def update(p, g, s, lr):
            return ro.adamw_step(p, g, s, lr, optim["weight_decay"])
        init = ro.adamw_init
    else:
        def update(p, g, s, lr):
            return ro.sgd_step(p, g, s, lr, optim["momentum"],
                               optim["weight_decay"])
        init = ro.sgd_init

    with jax.default_matmul_precision("highest"):
        step_grad, update = jax.jit(step_grad), jax.jit(update)
        params = jax.tree.map(lambda x: x.astype(jnp.float32), weights)
        start = params
        state = init(params)
        losses, grad_norms = [], None
        for step, batch in enumerate(batches):
            loss, grads, aux = step_grad(params, aux, blocks_of(batch))
            losses.append(float(loss))
            if step == 0:
                grad_norms = leaf_norms(grads)
            lr = optim["lr"]
            if optim["name"] == "adamw":
                lr = ro.warmup_cosine_lr(step, optim["lr"],
                                         optim["total_steps"],
                                         optim["warmup_steps"],
                                         optim["final_lr"])
            params, state = update(params, grads, state, jnp.float32(lr))
        delta = leaf_norms(jax.tree.map(jnp.subtract, params, start))
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": delta}


def compare_training(program: dict, reference: dict, limits: dict,
                     prefix: str = "") -> list:
    out = []
    for i, (p, r) in enumerate(zip(program["losses"], reference["losses"])):
        out.append(check(f"{prefix}loss_step{i}_rel_gap",
                         abs(p - r) / abs(r), limits["loss_rel_gap"]))
    for name, key, limit in (
            ("first_grad_norm", "grad_norms", "grad_norm_gap"),
            ("param_change_norm", "delta_norms", "delta_norm_gap")):
        gap, leaf = worst_leaf_gap(program[key], reference[key])
        out.append(check(f"{prefix}{name}_worst_leaf_gap", gap,
                         limits[limit], leaf=leaf))
    return out


# ---- serving -----------------------------------------------------------


def logits_pass(ref, cast=None):
    """The reference's logits of one sequence, compiled once a run."""
    return jax.jit(lambda p, t: ref.logits(p, t[None], cast)[0])


def served_token_gaps(passes: tuple, weights, prompt: np.ndarray,
                      served: list, pad_to: int) -> dict:
    """One reference pass over a prompt with its served tokens (padded at
    the end to ``pad_to``, which a causal model does not see, so that one
    compiled program serves every request). ``passes`` is (the reference's
    ``logits_pass``, the control's or None). Returns the widest gap by
    which a served token's logit lies below the reference's best at its
    position and, for the control, the same gap for the token that a
    lower precision puts first there."""
    plain, lowered = passes
    seq = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
    tokens = np.zeros((pad_to,), np.int32)
    tokens[:len(seq)] = seq
    n, at = len(served), len(prompt) - 1
    with jax.default_matmul_precision("highest"):
        lg = plain(weights, jnp.asarray(tokens))
        rows = lg[at: at + n]
        best = jnp.max(rows, -1)
        got = rows[jnp.arange(n), jnp.asarray(served, jnp.int32)]
        out = {"gap": float(jnp.max(best - got)), "tokens": n}
        if lowered is not None:
            lo = lowered(weights, jnp.asarray(tokens))
            first = jnp.argmax(lo[at: at + n], -1)
            out["control_gap"] = float(jnp.max(
                best - rows[jnp.arange(n), first]))
    return out
