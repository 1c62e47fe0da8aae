"""Batched bounded multi-dimensional knapsack DP — the colgen pricing kernel.

Column generation for MC-VBP (`repro.core.binpack.colgen`) prices columns
by solving, per bin kind, a bounded multi-dimensional knapsack: maximize
the dual-weighted count of stream classes packed under the kind's capacity
vector.  The pricing problems for all bin kinds *and* all open branch
nodes are independent and share one shape, so they batch into a single
dispatch — exactly the regular, vmappable DP this package already writes
as kernels.

Formulation (all arrays pre-discretized to integer grid units by the
caller; see `colgen._discretize`):

* a batch entry ``b`` has a capacity ``cap_levels[b] ∈ Z^D`` on a shared
  grid of ``S = prod(cap_levels.max(0) + 1)`` states,
* pricing entries ``e`` (one per (class, choice)) carry a value
  ``values[b, e] >= 0`` (the class's dual price), an integer weight vector
  ``weights[b, e] ∈ Z^D`` and a copy bound ``bounds[b, e]``,
* the DP maximizes ``Σ_e n_e · values[b, e]`` s.t. ``Σ_e n_e ·
  weights[b, e] <= cap_levels[b]`` and ``0 <= n_e <= bounds[b, e]``.

Bounded counts are binary-split into 0/1 pseudo-steps (1, 2, 4, …,
remainder), and each step is one simultaneous relax over the flattened
state grid::

    cand = val[s - w] + v;  take = fits & (cand > val);  val' = max

computed from the *previous* step's array, so a pseudo-step is used at
most once.  The take bits are recorded per step and backtracked on the
host into per-entry counts (the actual pattern / column), and ``best`` is
that pattern's value, ``Σ_e counts · values`` summed on the host.

``val[s - w]`` reads each knapsack's whole row shifted by one flat offset
``sh = w · strides``: where ``fits`` holds, ``s - sh`` is exactly the
flat index of ``coord - w``, and elsewhere the value read is discarded.
The jax kernel therefore makes no per-state gather (on a TPU, XLA runs a
gather element by element): it shifts the ``(B, S)`` block through
``⌈log2 S⌉`` static stages, stage ``k`` shifting every row by ``2^k``
and each row keeping the shifted or the unshifted copy by bit ``k`` of
its own ``sh`` (`_shift_rows`).  The stage count depends on ``S`` alone,
so shift values never change what is compiled.

The DP runs on values in fixed point: int64 units of 2^-k, with k chosen
per dispatch so the batch's largest ``Σ_e |values| · bounds`` fills 61
bits.  Integer adds and compares are exact on every backend, whereas a
TPU has no IEEE float64 and its emulated float64 decided some take bits
differently from numpy.  Rounding the values moves a pattern's value by
at most half a unit per copy, about as much as float64 rounds its running
sums, so the two DPs part ways only on near-ties; `_price_float64` keeps
the float DP as the yardstick the certificates are checked against.

Two implementations share this exact op sequence and are bit-equivalent
on ``(best, counts)``:

* `price_knapsacks(..., impl="numpy")` — the reference: a Python loop
  over batch entries,
* ``impl="jax"`` (the default) — one jitted `lax.scan` over steps
  carrying the whole ``(B, S)`` state block: all kinds × nodes in one
  dispatch, fanned across local devices by `jax.pmap` when more than
  one is visible.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from .. import telemetry

__all__ = [
    "PricingResult",
    "build_pricing_steps",
    "price_knapsacks",
]


@dataclasses.dataclass(frozen=True)
class PricingResult:
    """Batched pricing output: per-problem optimum and the argmax pattern."""

    best: np.ndarray  # (B,) best dual value per knapsack
    counts: np.ndarray  # (B, E) int64 copies of each entry in the argmax
    states: int  # grid states per knapsack (DP work metric)
    steps: int  # pseudo-item steps after binary splitting


def _grid(cap_levels: np.ndarray):
    """Shared state grid: levels per dim, C-order strides, (S, D) coords."""
    levels = cap_levels.max(axis=0).astype(np.int64) + 1  # (D,)
    strides = np.ones_like(levels)
    for d in range(levels.size - 2, -1, -1):
        strides[d] = strides[d + 1] * levels[d + 1]
    s_total = int(levels.prod())
    idx = np.arange(s_total, dtype=np.int64)
    coord = (idx[:, None] // strides[None, :]) % levels[None, :]  # (S, D)
    return levels, strides, coord


def build_pricing_steps(
    values: np.ndarray, weights: np.ndarray, bounds: np.ndarray
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Binary-split bounded entries into padded 0/1 pseudo-steps.

    Inputs: ``values (B, E) >= 0``, ``weights (B, E, D)`` int,
    ``bounds (B, E)`` int.  Returns ``(step_values, step_weights,
    step_entry, step_mult)`` with a shared step axis T; padding steps have
    value -1 / weight 0 / entry -1 so the DP provably never takes them.
    """
    values = np.asarray(values)
    weights = np.asarray(weights, dtype=np.int64)
    bounds = np.asarray(bounds, dtype=np.int64)
    b_n, e_n = values.shape
    dim = weights.shape[2]
    chunk_lists: list[list[tuple[int, int]]] = []  # per batch: (entry, mult)
    for b in range(b_n):
        chunks: list[tuple[int, int]] = []
        for e in range(e_n):
            rem = int(bounds[b, e])
            k = 1
            while rem > 0:
                take = min(k, rem)
                chunks.append((e, take))
                rem -= take
                k *= 2
        chunk_lists.append(chunks)
    t_n = max((len(c) for c in chunk_lists), default=0)
    step_values = np.full((b_n, t_n), -1.0, dtype=values.dtype)
    step_weights = np.zeros((b_n, t_n, dim), dtype=np.int64)
    step_entry = np.full((b_n, t_n), -1, dtype=np.int64)
    step_mult = np.zeros((b_n, t_n), dtype=np.int64)
    for b, chunks in enumerate(chunk_lists):
        for t, (e, mult) in enumerate(chunks):
            step_values[b, t] = values[b, e] * mult
            step_weights[b, t] = weights[b, e] * mult
            step_entry[b, t] = e
            step_mult[b, t] = mult
    return step_values, step_weights, step_entry, step_mult


# --------------------------------------------------------------------------
# numpy reference: serial loop over batch entries (the tests' reference)
# --------------------------------------------------------------------------

def _dp_numpy(step_values, step_weights, shifts, coord):
    b_n, t_n = step_values.shape
    s_n = coord.shape[0]
    idx = np.arange(s_n, dtype=np.int64)
    take = np.zeros((t_n, b_n, s_n), dtype=bool)
    for b in range(b_n):
        val = np.zeros(s_n, dtype=step_values.dtype)
        for t in range(t_n):
            pred = np.maximum(idx - shifts[b, t], 0)
            gathered = val[pred]
            fits = (coord >= step_weights[b, t][None, :]).all(axis=-1)
            cand = gathered + step_values[b, t]
            tk = fits & (cand > val)
            take[t, b] = tk
            val = np.where(tk, cand, val)
    return take


# --------------------------------------------------------------------------
# jax: one lax.scan over steps carrying the whole (B, S) state block
# --------------------------------------------------------------------------


def _shift_stages(s_n: int) -> int:
    """Stages of `_shift_rows` over ``s_n`` states: enough bits for any
    shift below ``s_n``, the only shifts whose values are kept."""
    return (s_n - 1).bit_length()


def _shift_rows(val, sh):
    """``out[b, s] = val[b, s - sh[b]]`` wherever ``s >= sh[b]``; zero or
    another row element elsewhere (bits of ``sh`` at or above
    `_shift_stages` are ignored).  Stage ``k`` moves the whole block by the
    constant ``2^k`` (a slice and a zero pad) and each row keeps the moved
    copy where bit ``k`` of its shift is set: dense, static, no gather.
    The stages commute; the highest bit goes first, which ran a full
    certification dispatch (1536 x 18 x 6825) 9% faster on a TPU v5e than
    the lowest first (0.119 s against 0.131 s)."""
    s_n = val.shape[1]
    for k in reversed(range(_shift_stages(s_n))):
        d = 1 << k
        moved = jnp.pad(val[:, : s_n - d], ((0, 0), (d, 0)))
        val = jnp.where(((sh >> k) & 1)[:, None] == 1, moved, val)
    return val


@functools.lru_cache(maxsize=None)
def _jax_kernel():
    def run(step_values, step_weights, shifts, coord):
        b_n, s_n = step_values.shape[0], coord.shape[0]

        def step(val, inp):
            v, w, sh = inp  # (B,), (B, D), (B,)
            # val[s - sh] wherever the step fits at s; what the network
            # leaves elsewhere is masked off by ``fits``.
            shifted = _shift_rows(val, sh)
            fits = (coord[None, :, :] >= w[:, None, :]).all(axis=-1)
            cand = shifted + v[:, None]
            tk = fits & (cand > val)
            return jnp.where(tk, cand, val), tk

        val0 = jnp.zeros((b_n, s_n), dtype=step_values.dtype)
        _val, take = jax.lax.scan(
            step,
            val0,
            (step_values.T, step_weights.transpose(1, 0, 2), shifts.T),
        )
        return take

    return jax.jit(run)


@functools.lru_cache(maxsize=None)
def _jax_pmap_kernel():
    """The scan kernel fanned across local devices: each device runs
    the single-device kernel on its slice of the batch axis, so the take
    bits are bit-identical to `_jax_kernel`."""
    return jax.pmap(_jax_kernel(), in_axes=(0, 0, 0, None))


def _dp_jax(step_values, step_weights, shifts, coord):
    """The DP on the device.  Span ``knapsack.run`` holds the transfer in
    and the kernel, to its end on the device (``shift_stages``: the shift
    network's stages a step runs); ``knapsack.fetch`` the copy of the take
    table back to the host (``bytes``)."""
    n_dev = jax.local_device_count()
    b_n = step_values.shape[0]
    stages = _shift_stages(coord.shape[0])
    with jax.enable_x64(True):
        if n_dev > 1 and b_n >= n_dev:
            # Multi-device fan-out: pad the batch to a device multiple,
            # shard the leading axis, and reassemble (padding knapsacks
            # replicate row 0 and are dropped).
            pad = (-b_n) % n_dev
            per = (b_n + pad) // n_dev

            def shard(a):
                if pad:
                    a = np.concatenate([a, np.repeat(a[:1], pad, axis=0)])
                return jnp.asarray(a.reshape((n_dev, per) + a.shape[1:]))

            with telemetry.span("knapsack.run", shift_stages=stages):
                take = _jax_pmap_kernel()(
                    shard(step_values),
                    shard(step_weights),
                    shard(shifts),
                    jnp.asarray(coord),
                )
                take.block_until_ready()
            with telemetry.span("knapsack.fetch", bytes=int(take.nbytes)):
                # Per-device take is (T, per, S); reassemble to (T, B, S).
                take = np.asarray(jax.device_get(take))
                return take.transpose(1, 0, 2, 3).reshape(
                    take.shape[1], n_dev * per, take.shape[3]
                )[:, :b_n, :]
        with telemetry.span("knapsack.run", shift_stages=stages):
            take = _jax_kernel()(
                jnp.asarray(step_values),
                jnp.asarray(step_weights),
                jnp.asarray(shifts),
                jnp.asarray(coord),
            )
            take.block_until_ready()
        with telemetry.span("knapsack.fetch", bytes=int(take.nbytes)):
            return np.asarray(jax.device_get(take))


#: Bits the largest pattern value of a dispatch may fill in fixed point;
#: the int64 headroom above it absorbs the per-copy rounding.
_VALUE_BITS = 61


def _fixed_point(values, bounds):
    """``values`` as int64 units of 2^-k, k the largest that keeps every
    knapsack's ``Σ_e |values| · bounds`` below 2^_VALUE_BITS."""
    # Entries with no copies never become steps; zero them so the scale
    # cannot overflow them.
    values = np.where(bounds > 0, values.astype(np.float64), 0.0)
    total = (np.abs(values) * bounds).sum(axis=1).max()
    _mantissa, exp = np.frexp(total)  # total < 2^exp
    scale = _VALUE_BITS - int(exp) if total > 0 else 0
    return np.rint(np.ldexp(values, scale)).astype(np.int64)


def _dp_inputs(values, weights, bounds, cap_levels, fixed=True):
    """The DP's inputs for a non-empty batch: ``(step_values, step_weights,
    step_entry, step_mult, shifts, coord, final_idx)``, step values in
    fixed point unless ``fixed`` is false.  Span ``knapsack.prepare``."""
    with telemetry.span("knapsack.prepare"):
        # Entries that cannot fit even once are dropped via a zero bound.
        fits_once = (weights <= cap_levels[:, None, :]).all(axis=-1)
        bounds = np.where(fits_once, bounds, 0)
        if fixed:
            values = _fixed_point(values, bounds)
        steps = build_pricing_steps(values, weights, bounds)
        _levels, strides, coord = _grid(cap_levels)
        shifts = steps[1] @ strides  # (B, T) flat-index offset per step
        final_idx = (cap_levels * strides[None, :]).sum(axis=1)
    return (*steps, shifts, coord, final_idx)


def _backtrack(take, shifts, step_entry, step_mult, final_idx, e_n):
    """Walk the recorded take bits into per-entry counts (B, E).  Span
    ``knapsack.backtrack``."""
    t_n, b_n, _ = take.shape
    counts = np.zeros((b_n, e_n), dtype=np.int64)
    with telemetry.span("knapsack.backtrack"):
        for b in range(b_n):
            s = int(final_idx[b])
            for t in range(t_n - 1, -1, -1):
                if take[t, b, s]:
                    e = int(step_entry[b, t])
                    if e >= 0:
                        counts[b, e] += int(step_mult[b, t])
                    s -= int(shifts[b, t])
    return counts


def price_knapsacks(
    values: np.ndarray,
    weights: np.ndarray,
    bounds: np.ndarray,
    cap_levels: np.ndarray,
    impl: str = "jax",
) -> PricingResult:
    """Solve a batch of bounded multi-dim knapsacks, returning argmax counts.

    ``values (B, E) >= 0`` dual value per entry; ``weights (B, E, D)``
    integer grid units; ``bounds (B, E)`` max copies; ``cap_levels (B, D)``
    per-problem capacity in grid units.  Both implementations return
    bit-identical ``(best, counts)``; ``best`` is in ``values``' dtype.
    """
    dps = {"numpy": _dp_numpy, "jax": _dp_jax}
    if impl not in dps:
        raise ValueError(f"unknown impl {impl!r}")
    return _price(values, weights, bounds, cap_levels, dps[impl], fixed=True)


def _price_float64(values, weights, bounds, cap_levels) -> PricingResult:
    """The numpy DP on the float64 values themselves, as the kernel ran
    before fixed point.  Not bit-stable across backends; kept as the
    yardstick fixed point must match (tests, ``chip_smoke.py``)."""
    values = np.asarray(values, dtype=np.float64)
    return _price(values, weights, bounds, cap_levels, _dp_numpy, fixed=False)


def _price(values, weights, bounds, cap_levels, dp, *, fixed):
    values = np.asarray(values)
    weights = np.asarray(weights, dtype=np.int64)
    bounds = np.asarray(bounds, dtype=np.int64)
    cap_levels = np.asarray(cap_levels, dtype=np.int64)
    b_n, e_n = values.shape
    if b_n == 0 or e_n == 0:
        return PricingResult(
            np.zeros(b_n, dtype=values.dtype),
            np.zeros((b_n, e_n), dtype=np.int64), 0, 0,
        )
    (
        step_values, step_weights, step_entry, step_mult,
        shifts, coord, final_idx,
    ) = _dp_inputs(values, weights, bounds, cap_levels, fixed)
    if step_values.shape[1] == 0:
        return PricingResult(
            np.zeros(b_n, dtype=values.dtype),
            np.zeros((b_n, e_n), dtype=np.int64), int(coord.shape[0]), 0,
        )
    take = dp(step_values, step_weights, shifts, coord)
    counts = _backtrack(take, shifts, step_entry, step_mult, final_idx, e_n)
    best = (counts * values).sum(axis=1, dtype=values.dtype)
    return PricingResult(
        best, counts, int(coord.shape[0]), int(step_values.shape[1])
    )
