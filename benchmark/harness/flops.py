"""Required operations per image, from the configuration's own numbers:
multiply-adds of every convolution, dense layer and attention product
counted as two operations, nothing for padding, nothing for
recomputation. Each family's count, `forward_flops(cfg)`, sits beside
its plain reference in `reference/<family>.py` and is checked in the
tests against a count of that reference's own jaxpr. A training step
requires three forward passes' worth (forward, and the backward pass's
two products per forward product).
"""
from __future__ import annotations

import importlib
from typing import Any, Dict


def forward_flops(cfg: Dict[str, Any]) -> float:
    return importlib.import_module(
        f"reference.{cfg['family']}").forward_flops(cfg)


def train_flops_per_image(cfg: Dict[str, Any]) -> float:
    return 3.0 * forward_flops(cfg)


def kernel_costs(cfg: Dict[str, Any]) -> Dict[str, Dict[str, float]]:
    """{kernel name: {"flops", "bytes"}} required for one model
    evaluation of one row, from the function beside the family's
    reference."""
    return importlib.import_module(
        f"reference.{cfg['family']}").kernel_costs(cfg)
