"""Plain reference of a served request: DDIM (eta 0) over a cosine VP
schedule with classifier-free guidance on the network's raw output,
epsilon- or v-prediction as the configuration states, linear step
spacing from T-1 to 0, a terminal denoise and a clip to [-1, 1]; the
request's initial noise is drawn from its seed by the rule the serving
configuration states (the second half of the first split of the seed's
key)."""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from .train import cosine_tables


def initial_noise(seed: int, shape, tables) -> jax.Array:
    _, noise_key = jax.random.split(jax.random.PRNGKey(seed))
    _, sqrt_1mac, _ = tables
    return jax.random.normal(noise_key, shape) * sqrt_1mac[-1]


def guided_inputs(x, t, cond, uncond):
    """The network's inputs for one guided evaluation: the conditional
    rows, then the same rows with the null context."""
    t_b = jnp.broadcast_to(t, (x.shape[0],)).astype(jnp.float32)
    return {"x": jnp.concatenate([x, x], axis=0),
            "t": jnp.concatenate([t_b, t_b], axis=0),
            "text": jnp.concatenate([cond, uncond], axis=0)}


def guide(raw, guidance: float):
    raw_c, raw_u = jnp.split(raw, 2, axis=0)
    return raw_u + guidance * (raw_c - raw_u)


def make_eps(forward: Callable, model_cfg: Dict[str, Any], guidance: float):
    """Jitted guided raw prediction pred(params, x, t, cond, uncond)."""
    def eps(params, x, t, cond, uncond):
        c = guided_inputs(x, t, cond, uncond)
        return guide(forward(params, model_cfg, c["x"], c["t"], c["text"]),
                     guidance)
    return jax.jit(eps)


def _rates(tables, t):
    sqrt_ac, sqrt_1mac, _ = tables
    i = jnp.clip(t.astype(jnp.int32), 0, sqrt_ac.shape[0] - 1)
    return sqrt_ac[i], sqrt_1mac[i]


def ddim_steps(x, nfe: int, timesteps: int, tables,
               predictor: str = "epsilon"):
    """The trajectory as a generator: yields (x, t), is sent the guided
    prediction there, and returns the samples, clipped to [-1, 1]. A
    caller may hold several and evaluate the network for all of them
    stage by stage (`serve_staged`)."""
    steps = jnp.linspace(float(timesteps - 1), 0.0, nfe + 1)
    steps = steps.at[0].set(float(timesteps - 1)).at[-1].set(0.0)

    def x0_eps(x, t, pred):
        signal, sigma = _rates(tables, t)
        if predictor == "epsilon":
            return (x - sigma * pred) / jnp.maximum(signal, 1e-12), pred
        if predictor == "v":      # v = signal * eps - sigma * x0
            norm = signal ** 2 + sigma ** 2
            return ((signal * x - sigma * pred) / norm,
                    (sigma * x + signal * pred) / norm)
        raise ValueError(f"unknown predictor {predictor!r}")

    for i in range(nfe):
        x0, e = x0_eps(x, steps[i], (yield x, steps[i]))
        signal_n, sigma_n = _rates(tables, steps[i + 1])
        sh_n = sigma_n / jnp.maximum(signal_n, 1e-12)
        x = signal_n * (x0 + sh_n * e)
    x0, _ = x0_eps(x, steps[-1], (yield x, steps[-1]))
    return jnp.clip(x0, -1.0, 1.0)


def ddim(eps_fn, params, x, cond, uncond, nfe: int, timesteps: int, tables,
         predictor: str = "epsilon"):
    """The request's samples, clipped to [-1, 1]."""
    gen = ddim_steps(x, nfe, timesteps, tables, predictor)
    at = next(gen)
    try:
        while True:
            at = gen.send(eps_fn(params, at[0], at[1], cond, uncond))
    except StopIteration as done:
        return done.value


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


def serve_staged(stages_of: Callable, make: Callable, requests,
                 timesteps: int, predictor: str = "epsilon",
                 probe: Optional[Callable] = None):
    """The samples of several requests, the network evaluated a stage at
    a time for all of them, so that one stage's weights are on the
    device at once: `stages_of(shape)` gives the forward pass over inputs
    of `shape` as ordered stages [(name, needs, apply(parts, carry))],
    `make(needs)` that stage's parameter subtrees, made anew each time
    and dropped after the stage. Every request walks its own trajectory;
    at each turn the network is evaluated once for every request still
    under way. `probe(stage name)` is called while a stage's weights and
    every carry are alive."""
    tables = cosine_tables(timesteps)
    stage_lists: Dict[Any, Any] = {}
    jitted: Dict[Any, Any] = {}
    out: Dict[int, Any] = {}
    with jax.default_matmul_precision("highest"):
        gens, at = {}, {}
        for i, r in enumerate(requests):
            gens[i] = ddim_steps(initial_noise(r["seed"], r["shape"], tables),
                                 r["nfe"], timesteps, tables, predictor)
            at[i] = next(gens[i])
        while at:
            carry = {i: guided_inputs(x, t, jnp.asarray(requests[i]["cond"]),
                                      jnp.asarray(requests[i]["uncond"]))
                     for i, (x, t) in at.items()}
            lists = {}
            for i, c in carry.items():
                if c["x"].shape not in stage_lists:
                    stage_lists[c["x"].shape] = stages_of(c["x"].shape)
                lists[i] = stage_lists[c["x"].shape]
            for k, (name, needs, _) in enumerate(next(iter(lists.values()))):
                parts = make(needs)
                for i in carry:
                    apply = lists[i][k][2]
                    if apply not in jitted:
                        jitted[apply] = jax.jit(apply)
                    carry[i] = jitted[apply](parts, carry[i])
                if probe is not None:
                    probe(name)
                del parts
            for i, raw in carry.items():
                try:
                    at[i] = gens[i].send(guide(raw, requests[i]["guidance"]))
                except StopIteration as done:
                    out[i] = done.value
                    del at[i]
    return [out[i] for i in range(len(requests))]
