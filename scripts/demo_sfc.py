#!/usr/bin/env python
"""Visualize space-filling-curve patch serialization orders.

The visual counterpart of the SFC machinery in
flaxdiff_tpu/models/sfc.py (reference demo_hilbert_curve.py and the
matplotlib demos in reference models/hilbert.py:373-714): draws the
raster, zigzag, and Hilbert traversal orders over a patch grid, checks
the patchify/unpatchify round trip to machine precision, and plots the
token-distance locality profile that motivates Hilbert ordering for
1-D sequence models (S5/SSM blocks) over 2-D images.

Usage:
  python scripts/demo_sfc.py --grid 16 --out sfc_demo.png
"""
from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# pure index math + plotting: a chip belongs to one process at a time,
# and this demo must never be the one that takes it
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--grid", type=int, default=16,
                    help="patch grid side (any size; non-powers of two "
                         "exercise the overscan+filter construction)")
    ap.add_argument("--out", default="sfc_demo.png")
    args = ap.parse_args(argv)

    import matplotlib
    matplotlib.use("Agg")
    import matplotlib.pyplot as plt

    from flaxdiff_tpu.models.sfc import (hilbert_indices,
                                         inverse_permutation,
                                         sfc_patchify, sfc_unpatchify,
                                         zigzag_indices)

    g = args.grid
    orders = {
        "raster": np.arange(g * g),
        "zigzag": zigzag_indices(g, g),
        "hilbert": hilbert_indices(g, g),
    }

    fig, axes = plt.subplots(2, 3, figsize=(13, 8.5))
    for ax, (name, idx) in zip(axes[0], orders.items()):
        ys, xs = np.divmod(idx, g)
        ax.plot(xs + 0.5, ys + 0.5, lw=1.1, color="tab:blue")
        ax.scatter([xs[0] + 0.5], [ys[0] + 0.5], color="tab:green",
                   zorder=3, label="start")
        ax.scatter([xs[-1] + 0.5], [ys[-1] + 0.5], color="tab:red",
                   zorder=3, label="end")
        ax.set_xlim(0, g)
        ax.set_ylim(g, 0)
        ax.set_aspect("equal")
        ax.set_title(f"{name} ({g}x{g} patches)")
        ax.legend(loc="lower right", fontsize=8)

    # locality profile: mean 2-D distance between tokens k sequence
    # steps apart — the quantity SFC ordering improves for 1-D scans
    ks = np.unique(np.round(np.logspace(0, np.log10(g * g / 2),
                                        24)).astype(int))
    ax = axes[1][0]
    for name, idx in orders.items():
        ys, xs = np.divmod(idx, g)
        pts = np.stack([xs, ys], 1).astype(float)
        mean_d = [np.mean(np.linalg.norm(pts[k:] - pts[:-k], axis=1))
                  for k in ks]
        ax.plot(ks, mean_d, marker="o", ms=3, label=name)
    ax.set_xscale("log")
    ax.set_xlabel("sequence distance k")
    ax.set_ylabel("mean 2-D patch distance")
    ax.set_title("locality: 2-D distance at sequence distance k")
    ax.legend()

    # round trip on a real image through the jit-compatible path
    rng = np.random.default_rng(0)
    img = rng.normal(size=(1, g * 4, g * 4, 3)).astype(np.float32)
    ax = axes[1][1]
    maes = {}
    for name in ("hilbert", "zigzag"):
        idx = orders[name]
        tokens, inv = sfc_patchify(img, patch_size=4, indices=idx)
        back = sfc_unpatchify(tokens, inv, patch_size=4,
                              h=g * 4, w=g * 4, channels=3)
        maes[name] = float(np.abs(np.asarray(back) - img).mean())
    ax.bar(list(maes), list(maes.values()), color="tab:blue")
    ax.set_title("patchify/unpatchify round-trip MAE (must be ~0)")
    ax.ticklabel_format(axis="y", style="sci", scilimits=(0, 0))

    # what a serialized image looks like: token index as intensity
    ax = axes[1][2]
    rank = inverse_permutation(orders["hilbert"]).reshape(g, g)
    im = ax.imshow(rank, cmap="viridis")
    ax.set_title("hilbert sequence position per patch")
    fig.colorbar(im, ax=ax, shrink=0.8)

    fig.tight_layout()
    fig.savefig(args.out, dpi=110)
    print(f"wrote {args.out}; round-trip MAE: " +
          ", ".join(f"{k}={v:.2e}" for k, v in maes.items()))
    assert all(v < 1e-7 for v in maes.values()), maes
    return 0


if __name__ == "__main__":
    sys.exit(main())
