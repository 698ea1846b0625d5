"""The plain reference's first training steps, in float32.

Imports nothing of the program. Given a family's ``loss_sum(params, rows)
-> (sum of per-position losses, number of positions)`` it follows the
trainer's first steps: gradient of the batch mean, taken in blocks of rows
so that it fits beside nothing else on one chip; global-norm clipping;
AdamW with decoupled decay on matrices only; warm-up/cosine schedule — each
written out here as the published recipes state them."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def schedule(count: int, *, peak_lr: float, warmup_steps: int,
             total_steps: int) -> float:
    """Linear warm-up from 0 to ``peak_lr``, then cosine decay to 0 at
    ``total_steps``; ``count`` is the number of updates already made."""
    if count < warmup_steps:
        return peak_lr * count / warmup_steps
    frac = min((count - warmup_steps) / max(total_steps - warmup_steps, 1), 1.0)
    return peak_lr * 0.5 * (1.0 + math.cos(math.pi * frac))


def _rows(batch: dict, start: int, stop: int) -> dict:
    return {k: v[start:stop] for k, v in batch.items()}


def _adamw(params, grads, mu, nu, count, lr, *, b1, b2, eps, weight_decay):
    t = count + 1.0

    def leaf(p, g, m, v):
        m = b1 * m + (1.0 - b1) * g
        v = b2 * v + (1.0 - b2) * g * g
        step = (m / (1.0 - b1 ** t)) / (jnp.sqrt(v / (1.0 - b2 ** t)) + eps)
        if p.ndim > 1:  # decoupled decay on matrices, not on biases/scales
            step = step + weight_decay * p
        return p - lr * step, m, v

    out = jax.tree_util.tree_map(leaf, params, grads, mu, nu)
    pick = lambda i: jax.tree_util.tree_map(lambda _, o: o[i], params, out)
    return pick(0), pick(1), pick(2)


def _norms(tree):
    return jax.tree_util.tree_map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x))), tree
    )


def first_steps(loss_sum, params: dict, batches: list[dict], recipe: dict,
                *, block_rows: int = 2) -> dict:
    """Follow ``len(batches)`` steps from ``params``. Returns the losses,
    the per-leaf norms of the first gradient as the optimizer gets it
    (after clipping), and the per-leaf norms of the parameters' change."""
    opt = recipe["optimizer"]
    clip = opt.get("clip_norm")
    tmap = jax.tree_util.tree_map
    grad_fn = jax.jit(jax.value_and_grad(loss_sum, has_aux=True))
    add = jax.jit(lambda a, b: tmap(jnp.add, a, b), donate_argnums=0)

    @jax.jit
    def mean_and_clip(grads, weight):
        grads = tmap(lambda x: x / weight, grads)
        if clip is None:
            return grads
        norm = jnp.sqrt(sum(
            jnp.sum(jnp.square(x)) for x in jax.tree_util.tree_leaves(grads)
        ))
        return tmap(lambda x: jnp.where(norm < clip, x, x / norm * clip),
                    grads)

    update = jax.jit(
        lambda p, g, m, v, count, lr: _adamw(
            p, g, m, v, count, lr, b1=opt["b1"], b2=opt["b2"],
            eps=opt["eps"], weight_decay=opt["weight_decay"]),
        donate_argnums=(1, 2, 3),
    )
    norms = jax.jit(_norms)
    # share of a leaf's elements whose gradient is nought to rounding
    dead_share = jax.jit(lambda t: tmap(
        lambda x: jnp.mean(
            jnp.abs(x) < 1e-3 * jnp.sqrt(jnp.mean(jnp.square(x)))
        ), t))
    change = jax.jit(lambda a, b: _norms(tmap(jnp.subtract, a, b)))

    start = params
    mu = tmap(jnp.zeros_like, params)
    nu = tmap(jnp.zeros_like, params)
    losses, grad_norms, dead = [], None, None
    for count, batch in enumerate(batches):
        n = len(next(iter(batch.values())))
        total, weight, grads = 0.0, 0.0, None
        # the batch mean and its gradient, a block of rows at a time
        for lo in range(0, n, block_rows):
            (s, w), g = grad_fn(params, _rows(batch, lo, lo + block_rows))
            total, weight = total + s, weight + w
            grads = g if grads is None else add(grads, g)
        losses.append(float(total / weight))
        grads = mean_and_clip(grads, weight)
        if grad_norms is None:
            grad_norms = {k: float(v) for k, v in norms(grads).items()}
            dead = {k: float(v) for k, v in dead_share(grads).items()}
        lr = schedule(
            count, peak_lr=opt["lr"], warmup_steps=opt["warmup_steps"],
            total_steps=opt["total_steps"],
        )
        params, mu, nu = update(
            params, grads, mu, nu, jnp.float32(count), jnp.float32(lr)
        )
    return {
        "losses": losses,
        "grad_norms": grad_norms,
        "grad_dead_share": dead,
        "change_norms": {
            k: float(v) for k, v in change(params, start).items()
        },
    }
