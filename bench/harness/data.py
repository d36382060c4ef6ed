"""Inputs made from ``--seed``: procedural SVHN-shaped images and noise
rows.

These are the benchmark's own copies, so a later change to the program's
generators cannot move the yardstick.  The image generator follows
``gaussian_mixture_images`` (smooth Gaussian-bump patterns plus pixel
noise, clipped to [0, 1]).
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

def seed_words(seed: int, n: int) -> List[int]:
    """``n`` independent 31-bit seeds from one seed of any size."""
    state = np.random.SeedSequence(int(seed)).generate_state(n, dtype=np.uint32)
    return [int(w) & 0x7FFFFFFF for w in state]


def mixture_images(rng: np.random.Generator, n: int, height: int, width: int,
                   channels: int, components: int = 16) -> np.ndarray:
    """(n, height * width * channels) float32 images in [0, 1]: each row one
    of ``components`` smooth patterns plus N(0, 0.08^2) pixel noise."""
    yy, xx = np.mgrid[0:height, 0:width].astype(np.float32)
    means = []
    for _ in range(components):
        img = np.zeros((height, width, channels), np.float32)
        for _ in range(4):
            cy, cx = rng.random(2) * [height, width]
            s = 2.0 + rng.random() * 6.0
            bump = np.exp(-(((yy - cy) ** 2 + (xx - cx) ** 2) / (2 * s * s)))
            img += bump[:, :, None] * rng.random(channels).astype(np.float32)
        means.append((img / max(img.max(), 1e-6)).reshape(-1))
    z = rng.integers(components, size=n)
    x = np.stack(means)[z]
    x += rng.standard_normal(x.shape, dtype=np.float32) * np.float32(0.08)
    return np.clip(x, 0.0, 1.0, out=x)


def rows(config: Dict, n: int, seed: int) -> np.ndarray:
    """Training rows for a configuration: images for image structures,
    standard-normal noise for the others."""
    rng = np.random.default_rng(seed)
    if config["structure"] == "pd":
        return mixture_images(rng, n, config["height"], config["width"],
                              config["num_channels"])
    return rng.standard_normal((n, config["num_vars"]), dtype=np.float32)
