"""Model registry + architecture-name parsing.

Reference training.py:383-488 (MODEL_ARCHITECUTRES) and
inference/utils.py:168-180 (+2d/+hilbert/+zigzag suffix canonicalization).
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

from ..models.brumby import BrumbyDenoiser
from ..models.cohere2_moe import Cohere2MoEDenoiser
from ..models.dit import SimpleDiT
from ..models.glm_moe_dsa import GlmMoeDsaDenoiser
from ..models.mmdit import HierarchicalMMDiT, SimpleMMDiT
from ..models.smallthinker import SmallThinkerDenoiser
from ..models.ssm import HybridSSMAttentionDiT
from ..models.unet import Unet
from ..models.unet3d import UNet3D
from ..models.uvit import SimpleUDiT, UViT
from ..typing import resolve_activation, resolve_dtype, resolve_precision

MODEL_REGISTRY: Dict[str, Any] = {
    "unet": Unet,
    "uvit": UViT,
    "simple_dit": SimpleDiT,
    "simple_udit": SimpleUDiT,
    "simple_mmdit": SimpleMMDiT,
    "hierarchical_mmdit": HierarchicalMMDiT,
    "hybrid_ssm": HybridSSMAttentionDiT,
    "unet_3d": UNet3D,
    "cohere2_moe_dn": Cohere2MoEDenoiser,
    "brumby_dn": BrumbyDenoiser,
    "glm_moe_dsa_dn": GlmMoeDsaDenoiser,
    "smallthinker_dn": SmallThinkerDenoiser,
}

# Suffix -> constructor kwarg toggles (reference inference/utils.py:168-180).
_SUFFIX_FLAGS = {
    "hilbert": {"use_hilbert": True},
    "zigzag": {"use_zigzag": True},
    "2d": {"use_2d_fusion": True},
}


def parse_architecture_name(name: str) -> Tuple[str, Dict[str, Any]]:
    """'simple_dit+hilbert' -> ('simple_dit', {'use_hilbert': True})."""
    parts = name.split("+")
    base, suffixes = parts[0], parts[1:]
    flags: Dict[str, Any] = {}
    for s in suffixes:
        if s not in _SUFFIX_FLAGS:
            raise ValueError(f"unknown architecture suffix {s!r} in {name!r}")
        flags.update(_SUFFIX_FLAGS[s])
    return base, flags


def build_model(name: str, **kwargs):
    """Construct a model from its registry name (+suffixes) and kwargs;
    string dtype/precision/activation values resolve through the canonical
    maps (reference inference/utils.py:136-160)."""
    base, flags = parse_architecture_name(name)
    if base not in MODEL_REGISTRY:
        raise ValueError(f"unknown model {base!r}; "
                         f"known: {sorted(MODEL_REGISTRY)}")
    cls = MODEL_REGISTRY[base]
    merged = {**flags, **kwargs}
    if "dtype" in merged:
        merged["dtype"] = resolve_dtype(merged["dtype"])
    if "precision" in merged:
        merged["precision"] = resolve_precision(merged["precision"])
    if "activation" in merged and merged["activation"] is not None:
        merged["activation"] = resolve_activation(merged["activation"])
    valid = set(cls.__dataclass_fields__)
    dropped = set(merged) - valid
    merged = {k: v for k, v in merged.items() if k in valid}
    if dropped:
        import warnings
        warnings.warn(f"{name}: ignoring kwargs {sorted(dropped)}")
    return cls(**merged)
