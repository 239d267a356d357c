"""Plain reference of the diffusion training step: epsilon- or
v-prediction (as the configuration states) on a cosine VP schedule, classifier-free-guidance dropout of the text
context, mean squared error, AdamW, all in float32. It derives the
step's noise, timesteps and dropout mask from the run's seed by the
rule the training configuration states (one key per run, folded with
the step counter, split four ways), so that it follows the program's
first steps on the same numbers.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, List, Optional

import numpy as np

import jax
import jax.numpy as jnp


def cosine_tables(timesteps: int, s: float = 0.008):
    """sqrt(alpha_bar), sqrt(1 - alpha_bar), alpha_bar (Nichol &
    Dhariwal's cosine schedule; betas clipped to [1e-8, 0.999])."""
    steps = np.arange(timesteps + 1, dtype=np.float64) / timesteps
    alpha_bar = np.cos((steps + s) / (1 + s) * np.pi / 2) ** 2
    betas = np.clip(1.0 - alpha_bar[1:] / alpha_bar[:-1], 1e-8, 0.999)
    ac = np.cumprod(1.0 - betas)
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    return f32(np.sqrt(ac)), f32(np.sqrt(1.0 - ac)), f32(ac)


def run_keys(seed32: int):
    """(key the weights are drawn from, key the steps are drawn from)."""
    init_key, train_key = jax.random.split(jax.random.PRNGKey(seed32))
    return init_key, train_key


def step_inputs(train_key, step: int, batch: Dict[str, Any], null_ctx,
                uncond_prob: float, timesteps: int, tables,
                predictor: str = "epsilon"):
    """(x_t, t, text, target) of training step `step` (0-based); the
    target is the noise, or v = signal * noise - sigma * x0."""
    rng = jax.random.fold_in(train_key, step)
    noise_key, t_key, uncond_key, _ = jax.random.split(rng, 4)
    x0 = jnp.asarray(batch["sample"], jnp.float32)
    text = jnp.asarray(batch["cond"]["text"], jnp.float32)
    b = x0.shape[0]
    if uncond_prob > 0:
        mask = jax.random.bernoulli(uncond_key, uncond_prob, (b,))
        text = jnp.where(mask[:, None, None],
                         jnp.broadcast_to(jnp.asarray(null_ctx), text.shape),
                         text)
    t = jax.random.randint(t_key, (b,), 0, timesteps)
    noise = jax.random.normal(noise_key, x0.shape, jnp.float32)
    sqrt_ac, sqrt_1mac, _ = tables
    signal = sqrt_ac[t][:, None, None, None]
    sigma = sqrt_1mac[t][:, None, None, None]
    x_t = signal * x0 + sigma * noise
    if predictor == "epsilon":
        return x_t, t, text, noise
    if predictor == "v":
        return x_t, t, text, signal * noise - sigma * x0
    raise ValueError(f"unknown predictor {predictor!r}")


def _sum_sq_err(params, forward, model_cfg, x_t, t, text, target):
    pred = forward(params, model_cfg, x_t, t.astype(jnp.float32), text)
    per_sample = jnp.mean((pred - target) ** 2, axis=(1, 2, 3))
    return jnp.sum(per_sample)


def loss_and_grads(fn, params, x_t, t, text, target, block_rows: int,
                   mesh=None):
    """Mean loss over the batch and its gradient, in blocks of rows so
    that the float32 activations fit; `fn` is the jitted
    value-and-gradient of `_sum_sq_err`. With `mesh` (a 1-D jax Mesh
    named "rows"), each block's rows are spread over its devices."""
    b = x_t.shape[0]
    if b % block_rows:
        raise ValueError(f"block_rows {block_rows} does not divide {b}")
    total, grads = 0.0, None
    for lo in range(0, b, block_rows):
        blk = [a[lo:lo + block_rows] for a in (x_t, t, text, target)]
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            blk = [jax.device_put(a, NamedSharding(mesh, P("rows")))
                   for a in blk]
        val, g = fn(params, *blk)
        total = total + val
        grads = g if grads is None else _accumulate(grads, g)
    return total / b, _scale(grads, 1.0 / b)


def _adamw(params, grads, mu, nu, count, hyper):
    lr, b1, b2, eps, wd = hyper
    count = count + 1
    mu = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g,
                                nu, grads)
    c1 = 1 - b1 ** count.astype(jnp.float32)
    c2 = 1 - b2 ** count.astype(jnp.float32)
    new = jax.tree_util.tree_map(
        lambda p, m, v: p - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps)
                                  + wd * p), params, mu, nu)
    return new, mu, nu, count


def _spread(tree, mesh):
    """Shardings that spread each leaf over the mesh's devices along its
    first axis that divides (replicated where none does): the gradient
    accumulator and Adam's moments of a model too large to hold three
    more whole copies of on one chip."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    n = mesh.devices.size

    def one(x):
        for ax, d in enumerate(x.shape):
            if d % n == 0 and d >= n:
                return NamedSharding(mesh, P(*([None] * ax + ["rows"])))
        return NamedSharding(mesh, P())
    return jax.tree_util.tree_map(one, tree)


@functools.partial(jax.jit, donate_argnums=(0,))
def _accumulate(acc, g):
    return jax.tree_util.tree_map(jnp.add, acc, g)


@functools.partial(jax.jit, donate_argnums=(0,))
def _scale(tree, k):
    return jax.tree_util.tree_map(lambda x: x * k, tree)


@jax.jit
def leaf_norms(tree):
    return jax.tree_util.tree_map(
        lambda x: jnp.sqrt(jnp.sum(x.astype(jnp.float32) ** 2)), tree)


@jax.jit
def delta_norms(a, b):
    return jax.tree_util.tree_map(
        lambda x, y: jnp.sqrt(jnp.sum((x.astype(jnp.float32)
                                       - y.astype(jnp.float32)) ** 2)), a, b)


def follow(forward: Callable, model_cfg: Dict[str, Any], params0,
           batches: List[Dict[str, Any]], train_key, null_ctx,
           train_cfg: Dict[str, Any], timesteps: int, steps: int,
           block_rows: int, mesh=None,
           predictor: str = "epsilon") -> Dict[str, Any]:
    """Follow the first `steps` training steps. Returns the losses, the
    first gradient (on the host) with its per-leaf norms, and the
    per-leaf norm of the parameters' change after `steps` steps."""
    tables = cosine_tables(timesteps)
    hyper = tuple(jnp.float32(train_cfg[k]) for k in
                  ("learning_rate", "b1", "b2", "eps", "weight_decay"))
    params = params0
    vg = jax.value_and_grad(
        lambda p, *a: _sum_sq_err(p, forward, model_cfg, *a))
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P
        rep = NamedSharding(mesh, P())
        spread = _spread(params0, mesh)
        rep_tree = jax.tree_util.tree_map(lambda _: rep, params0)
        fn = jax.jit(vg, out_shardings=(rep, spread))
        adamw = jax.jit(_adamw, donate_argnums=(0, 2, 3),
                        out_shardings=(rep_tree, spread, spread, rep))
        zeros = lambda: jax.jit(
            lambda t: jax.tree_util.tree_map(jnp.zeros_like, t),
            out_shardings=spread)(params0)
    else:
        fn = jax.jit(vg)
        adamw = jax.jit(_adamw, donate_argnums=(0, 2, 3))
        zeros = lambda: jax.tree_util.tree_map(jnp.zeros_like, params0)
    start = jax.tree_util.tree_map(jnp.copy, params0)
    mu, nu, count = zeros(), zeros(), jnp.zeros((), jnp.int32)
    losses, first_grad = [], None
    with jax.default_matmul_precision("highest"):
        for i in range(steps):
            x_t, t, text, target = step_inputs(
                train_key, i, batches[i % len(batches)], null_ctx,
                train_cfg["uncond_prob"], timesteps, tables, predictor)
            loss, grads = loss_and_grads(fn, params, x_t, t, text, target,
                                         block_rows, mesh)
            losses.append(float(loss))
            if first_grad is None:
                first_grad = leaf_norms(grads)
                first_full = jax.device_get(grads)
            params, mu, nu, count = adamw(params, grads, mu, nu, count,
                                          hyper)
            del grads
    params0 = start
    return {"losses": losses,
            "first_grad": first_full,
            "grad_norms": jax.device_get(first_grad),
            "delta_norms": jax.device_get(delta_norms(params, params0))}
