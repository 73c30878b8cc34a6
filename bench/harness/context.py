"""Formal contexts for the cells, generated on the host from seeds.

:func:`profiles_context` is a copy of the program's synthetic Table-7
stand-in (``repro.data.fca_datasets._synthetic_correlated``), kept here so
that no change to the program can move the benchmark's inputs.  A
configuration fixes the generator's own seed, so every run mines the same
lattice; the run's ``--seed`` only permutes the objects, which changes the
rows' order on the device and nothing that the answer or the work depends
on.
"""

from __future__ import annotations

import numpy as np


def profiles_context(
    n_objects: int, n_attrs: int, density: float, seed: int
) -> np.ndarray:
    """``[n_objects, n_attrs]`` bool: objects drawn from latent attribute
    profiles (kept with probability 0.85), topped up with Bernoulli noise
    so the total density lands on ``density``."""
    rng = np.random.default_rng(seed)
    n_profiles = max(4, n_attrs // 8)
    k = max(1, int(round(density * n_attrs)))
    profiles = np.zeros((n_profiles, n_attrs), dtype=bool)
    for p in range(n_profiles):
        profiles[p, rng.choice(n_attrs, size=k, replace=False)] = True
    assign = rng.integers(0, n_profiles, size=n_objects)
    dense = profiles[assign].copy()
    keep = rng.random(dense.shape) < 0.85
    dense &= keep
    cur = dense.mean()
    if cur < density:
        p_noise = (density - cur) / max(1e-9, 1.0 - cur)
        dense |= rng.random(dense.shape) < p_noise
    return dense


def run_rng(seed: int, stream: int) -> np.random.Generator:
    """The generator of one input stream of a run (0: object order,
    1: arrivals, 2: payloads, …); any integer seed, negative ones too."""
    return np.random.default_rng([seed % 2**64, stream])


def make_context(config: dict, seed: int) -> np.ndarray:
    """The configuration's context with its objects permuted by ``seed``."""
    dense = profiles_context(
        config["objects"], config["attributes"], config["density"],
        config["generator_seed"],
    )
    return dense[run_rng(seed, 0).permutation(dense.shape[0])]


def resolve_min_support(value: float, n_objects: int) -> int:
    """An absolute support count from a fraction of the objects (or a
    count ≥ 1): ``ceil(value · n_objects)``, with a product that lies
    within rounding of an integer taken as that integer."""
    v = float(value)
    if v >= 1:
        if v != int(v):
            raise ValueError(f"min_support count {value!r} is not whole")
        return int(v)
    if v <= 0:
        raise ValueError(f"min_support must be positive, got {value!r}")
    target = v * n_objects
    nearest = round(target)
    if nearest >= 1 and abs(target - nearest) <= 1e-12 * max(1.0, target):
        return int(nearest)
    return max(1, int(np.ceil(target)))
