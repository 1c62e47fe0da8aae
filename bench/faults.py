"""The precision control and the faults a re-certification cell can have.

``plant(name)`` swaps one piece of the program under the timed path for a
lower-precision or broken one and puts it back on exit.  The tests plant
each under a whole run on the CPU and see ``correct`` come out false;
``bench.control`` reads them on the chip.  The benchmark's own runs plant
nothing.

- ``float32`` (the control): the pricing kernel on float32 values, its
  DP, its pattern values and all, the program's float path (``fixed``
  off) one precision below the float64 prices it is handed;
- ``state_unchanged``: a re-certification that prices nothing and returns
  the last bound;
- ``half_batch``: each dispatch solves the first half of its knapsacks
  and returns nothing for the rest;
- ``answer_altered``: one knapsack's optimum off by one part in 1e9;
- ``bound_weakened`` / ``prices_inflated``: every cell's certified prices
  and bound scaled by 0.99 / 1.001 where the certifier returns them.
"""
from __future__ import annotations

import contextlib
import dataclasses

import numpy as np

CONTROL = "float32"
FAULTS = ("state_unchanged", "half_batch", "answer_altered", "bound_weakened",
          "prices_inflated")
SCALES = {"bound_weakened": 0.99, "prices_inflated": 1.001}


def _float32_kernel(real):
    from repro.kernels import knapsack

    def price(values, weights, bounds, cap_levels, impl="jax"):
        dp = {"jax": knapsack._dp_jax, "numpy": knapsack._dp_numpy}[impl]
        return knapsack._price(np.asarray(values, np.float32), weights, bounds,
                               cap_levels, dp, fixed=False)
    return price


def _half_batch(real):
    def price(values, weights, bounds, cap_levels, impl="jax"):
        half = len(values) // 2
        res = real(values[:half], weights[:half], bounds[:half], cap_levels[:half], impl)
        pad = len(values) - half
        return dataclasses.replace(
            res,
            best=np.concatenate([res.best, np.zeros(pad, res.best.dtype)]),
            counts=np.concatenate([res.counts, np.zeros((pad,) + res.counts.shape[1:],
                                                        res.counts.dtype)]),
        )
    return price


def _altered(real):
    def price(*args, **kwargs):
        res = real(*args, **kwargs)
        best = res.best.copy()
        best[len(best) // 2] *= 1.0 + 1e-9
        return dataclasses.replace(res, best=best)
    return price


def _scaled(real, factor: float):
    def certify(*args, **kwargs):
        return [
            ({k: v * factor for k, v in prices.items()}, bound * factor)
            for prices, bound in real(*args, **kwargs)
        ]
    return certify


@contextlib.contextmanager
def plant(name: str, real_refreshes: int = 0):
    """Plant ``name``; ``state_unchanged`` lets ``real_refreshes``
    re-certifications (set-up's) through first."""
    from repro.core.binpack import colgen
    from repro.core.shard import ShardedController
    from repro.kernels import knapsack

    if name == "state_unchanged":
        owner, attr = ShardedController, "refresh_prices"
        real = ShardedController.refresh_prices
        calls = {"n": 0}

        def fake(self, *, batched=True):
            calls["n"] += 1
            if calls["n"] <= real_refreshes:
                return real(self, batched=batched)
            return sum(self._last_lb.values())
    elif name in SCALES:
        owner, attr = colgen, "batched_dual_prices"
        fake = _scaled(colgen.batched_dual_prices, SCALES[name])
    else:
        wrap = {CONTROL: _float32_kernel, "half_batch": _half_batch,
                "answer_altered": _altered}[name]
        owner, attr = knapsack, "price_knapsacks"
        fake = wrap(knapsack.price_knapsacks)
    real_attr = getattr(owner, attr)
    setattr(owner, attr, fake)
    try:
        yield
    finally:
        setattr(owner, attr, real_attr)
