"""Build the system under test from a configuration file: the model
through the program's `build_model`, and the `apply_fn` / `init_fn` pair
the trainer and the pipeline take. Weights come from `weights.py`."""
from __future__ import annotations

import copy
from typing import Any, Callable, Dict, Tuple

from . import spec, weights


def effective_config(config: Dict[str, Any], rehearse: bool
                     ) -> Dict[str, Any]:
    """The configuration as run: the file as it is, or, for a CPU
    rehearsal only, with the file's `rehearse` overrides merged in (a
    section's keys, or a top-level key of the source's: a rehearsal may
    shrink widths that the chip run may not). Where the file carries its
    source's keys at the top level, `model` becomes those keys as they
    stand after `reduced`, with the file's own `model` group (the
    program's: patch size, dtype, which experts are held) over them: what
    `build_model` and the plain reference are both handed."""
    cfg = copy.deepcopy(config)
    over = cfg.pop("rehearse", None)
    if rehearse and over:
        for section, values in over.items():
            if isinstance(values, dict) and isinstance(cfg.get(section),
                                                       dict):
                cfg[section].update(values)
            else:
                cfg[section] = values
    cfg["model"] = {**{k: cfg[k] for k in spec.source_keys(cfg)},
                    **cfg["model"]}
    return cfg


def unread_keys(cfg: Dict[str, Any], fields) -> list:
    """The keys of `model` that the model class has no field for, which
    `build_model` drops. A source key that is a width, or that `reduced`
    names, has to be read: the lint holds the file to its source under
    that name, and a model built at its own default would pass it while
    running another size. So has a key of the file's own `model` group:
    it is nobody else's. The source's other keys (its `model_type`, a
    rope group) may go unread, and are returned to be said."""
    own = set(cfg["model"]) - set(spec.source_keys(cfg))
    unread = sorted(set(cfg["model"]) - set(fields))
    must = [k for k in unread if k in own or k in cfg.get("reduced", ())
            or spec.WIDTH_RE.search(k)]
    if must:
        raise spec.SpecError(
            f"configuration {cfg['name']}: the model "
            f"{cfg['registry_name']!r} has no field for {must}: a width, "
            "a key listed in `reduced` or a key of the `model` group has "
            "to reach the model under its own name, or the file's value "
            "is not the size that runs")
    return unread


def _tuplify(v):
    return tuple(v) if isinstance(v, list) else v


def build(cfg: Dict[str, Any]) -> Tuple[Any, Callable, Callable, Any]:
    """(model, apply_fn, init_fn, param_shapes) for an effective config.
    `init_fn(key)` fills every leaf from the key (traceable)."""
    import warnings

    import jax
    import jax.numpy as jnp

    from flaxdiff_tpu.inference import build_model

    kwargs = {k: _tuplify(v) for k, v in cfg["model"].items()}
    with warnings.catch_warnings():     # what it drops is said, or refused
        warnings.filterwarnings("ignore", ".*ignoring kwargs", UserWarning)
        model = build_model(cfg["registry_name"], **kwargs)
    unread = unread_keys(cfg, type(model).__dataclass_fields__)
    if unread:
        print(f"config: {cfg['name']}: source key(s) the model "
              f"{cfg['registry_name']!r} does not read: {unread}",
              flush=True)
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
