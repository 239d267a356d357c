"""Build the system under test from a configuration file: the model
through the program's `build_model`, and the `apply_fn` / `init_fn` pair
the trainer and the pipeline take. Weights come from `weights.py`."""
from __future__ import annotations

import copy
from typing import Any, Callable, Dict, Tuple

from . import weights


def effective_config(config: Dict[str, Any], rehearse: bool
                     ) -> Dict[str, Any]:
    """The configuration as run: the file as it is, or, for a CPU
    rehearsal only, with the file's `rehearse` overrides merged in."""
    cfg = copy.deepcopy(config)
    over = cfg.pop("rehearse", None)
    if rehearse and over:
        for section, values in over.items():
            if isinstance(values, dict) and isinstance(cfg.get(section),
                                                       dict):
                cfg[section].update(values)
            else:
                cfg[section] = values
    return cfg


def _tuplify(v):
    return tuple(v) if isinstance(v, list) else v


def build(cfg: Dict[str, Any]) -> Tuple[Any, Callable, Callable, Any]:
    """(model, apply_fn, init_fn, param_shapes) for an effective config.
    `init_fn(key)` fills every leaf from the key (traceable)."""
    import jax
    import jax.numpy as jnp

    from flaxdiff_tpu.inference import build_model

    kwargs = {k: _tuplify(v) for k, v in cfg["model"].items()}
    model = build_model(cfg["registry_name"], **kwargs)
    res, ch = cfg["input"]["resolution"], cfg["input"]["channels"]
    tok, feat = cfg["conditioning"]["tokens"], cfg["conditioning"]["features"]

    def apply_fn(params, x, t, cond):
        text = cond["text"] if cond is not None else jnp.zeros(
            (x.shape[0], tok, feat), x.dtype)
        return model.apply({"params": params}, x, t, text)

    shapes = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, res, res, ch)),
                             jnp.zeros((1,)), jnp.zeros((1, tok, feat))
                             )["params"], jax.random.PRNGKey(0))

    def init_fn(key):
        return weights.fill_params(shapes, key)

    return model, apply_fn, init_fn, shapes


def count_params(shapes) -> int:
    import jax
    import numpy as np
    return int(sum(np.prod(s.shape) for s in jax.tree_util.tree_leaves(shapes)))
