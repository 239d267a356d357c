"""Plain reference of a served request: DDIM (eta 0) over a cosine VP
schedule with classifier-free guidance on the network's raw output,
epsilon- or v-prediction as the configuration states, linear step
spacing from T-1 to 0, a terminal denoise and a clip to [-1, 1]; the
request's initial noise is drawn from its seed by the rule the serving
configuration states (the second half of the first split of the seed's
key)."""
from __future__ import annotations

from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp

from .train import cosine_tables


def initial_noise(seed: int, shape, tables) -> jax.Array:
    _, noise_key = jax.random.split(jax.random.PRNGKey(seed))
    _, sqrt_1mac, _ = tables
    return jax.random.normal(noise_key, shape) * sqrt_1mac[-1]


def make_eps(forward: Callable, model_cfg: Dict[str, Any], guidance: float):
    """Jitted guided raw prediction pred(params, x, t, cond, uncond)."""
    def eps(params, x, t, cond, uncond):
        t_b = jnp.broadcast_to(t, (x.shape[0],)).astype(jnp.float32)
        x2 = jnp.concatenate([x, x], axis=0)
        t2 = jnp.concatenate([t_b, t_b], axis=0)
        c2 = jnp.concatenate([cond, uncond], axis=0)
        raw = forward(params, model_cfg, x2, t2, c2)
        raw_c, raw_u = jnp.split(raw, 2, axis=0)
        return raw_u + guidance * (raw_c - raw_u)
    return jax.jit(eps)


def _rates(tables, t):
    sqrt_ac, sqrt_1mac, _ = tables
    i = jnp.clip(t.astype(jnp.int32), 0, sqrt_ac.shape[0] - 1)
    return sqrt_ac[i], sqrt_1mac[i]


def ddim(eps_fn, params, x, cond, uncond, nfe: int, timesteps: int, tables,
         predictor: str = "epsilon"):
    """The request's samples, clipped to [-1, 1]."""
    steps = jnp.linspace(float(timesteps - 1), 0.0, nfe + 1)
    steps = steps.at[0].set(float(timesteps - 1)).at[-1].set(0.0)

    def x0_eps(x, t):
        pred = eps_fn(params, x, t, cond, uncond)
        signal, sigma = _rates(tables, t)
        if predictor == "epsilon":
            return (x - sigma * pred) / jnp.maximum(signal, 1e-12), pred
        if predictor == "v":      # v = signal * eps - sigma * x0
            norm = signal ** 2 + sigma ** 2
            return ((signal * x - sigma * pred) / norm,
                    (sigma * x + signal * pred) / norm)
        raise ValueError(f"unknown predictor {predictor!r}")

    for i in range(nfe):
        x0, e = x0_eps(x, steps[i])
        signal_n, sigma_n = _rates(tables, steps[i + 1])
        sh_n = sigma_n / jnp.maximum(signal_n, 1e-12)
        x = signal_n * (x0 + sh_n * e)
    x0, _ = x0_eps(x, steps[-1])
    return jnp.clip(x0, -1.0, 1.0)


def serve(forward: Callable, model_cfg: Dict[str, Any], params, request,
          timesteps: int, eps_fn=None, predictor: str = "epsilon"):
    """`request`: {"seed", "nfe", "guidance", "shape", "cond", "uncond"}."""
    tables = cosine_tables(timesteps)
    eps_fn = eps_fn or make_eps(forward, model_cfg, request["guidance"])
    with jax.default_matmul_precision("highest"):
        x = initial_noise(request["seed"], request["shape"], tables)
        return ddim(eps_fn, params, x, jnp.asarray(request["cond"]),
                    jnp.asarray(request["uncond"]), request["nfe"],
                    timesteps, tables, predictor)
