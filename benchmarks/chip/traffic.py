"""What every traffic generator shares: fixed multisets as equal-probability
quantiles of a stated distribution, laid out so that every block of 8 holds
one item of each eighth of the distribution. The seed's one freedom is the
content (token text, and the weights in the engine): the order is fixed.

A traffic file (`traffic/<name>.json`) names its generator
(`generators/<generator>.py`) and that generator's parameters; nothing in it
is code. The same file therefore offers the same work under every seed.
"""

from __future__ import annotations

import importlib
import json
import math
import os

HERE = os.path.dirname(os.path.abspath(__file__))
BLOCK = 8


def quantiles(dist: dict, n: int) -> list[float]:
    """n equal-probability quantiles ((i + 0.5) / n) of `dist`."""
    qs = [(i + 0.5) / n for i in range(n)]
    kind = dist["dist"]
    if kind == "loguniform":
        lo, hi = dist["lo"], dist["hi"]
        return [lo * (hi / lo) ** q for q in qs]
    raise ValueError(f"unknown distribution {kind!r}")


def int_quantiles(dist: dict, n: int) -> list[int]:
    return [max(1, round(v)) for v in quantiles(dist, n)]


def spread_order(n: int) -> list[int]:
    """Indices 0..n-1 arranged so that every consecutive block of BLOCK
    holds one item from each BLOCK-th of the sorted multiset: block b is
    {b, b + n/8, b + 2n/8, ...}. n must be a multiple of BLOCK."""
    if n % BLOCK:
        raise ValueError(f"multiset size {n} is not a multiple of {BLOCK}")
    per = n // BLOCK
    return [j * per + b for b in range(per) for j in range(BLOCK)]


def decorrelate(n: int, stride: int = 37) -> list[int]:
    """A fixed permutation of 0..n-1 (i -> i*stride + 11 mod n) used to pair
    one sorted multiset with another, so that long prompts do not always
    carry long answers. Never seeded."""
    if math.gcd(stride, n) != 1:
        raise ValueError(f"stride {stride} shares a factor with {n}")
    return [(i * stride + 11) % n for i in range(n)]


def load_traffic(name: str, rehearse: bool = False) -> dict:
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        spec = json.load(f)
    params = dict(spec["params"])
    if rehearse:
        params.update(spec.get("rehearse", {}))
    spec["params"] = params
    return spec


def load_generator(spec: dict):
    """The module `generators/<spec['generator']>.py`, found by name."""
    return importlib.import_module(f"generators.{spec['generator']}")
