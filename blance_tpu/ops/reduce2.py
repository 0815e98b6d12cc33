"""Pallas TPU kernel: fused row-wise (min, argmin, second-min).

The auction round of the tensor planner (blance_tpu/plan/tensor.py) needs,
per partition row of the effective score matrix ``eff[P, N]``:

    best   = min(eff, axis=1)
    choice = argmin(eff, axis=1)              (first occurrence)
    second = min(eff with the argmin POSITION masked out, axis=1)

The stock-XLA spelling materializes a full [P, N] copy for the position
mask (``eff.at[arange, choice].set(inf)``) and runs three separate
reductions — four HBM round-trips over the biggest tensor in the solver.
This kernel fuses all three into ONE pass: each grid step loads a
(TILE_P, TILE_N) block into VMEM, reduces it on the VPU, and merges into
running (best, second, idx) accumulators that stay resident in VMEM
across the N-axis grid dimension.  HBM traffic drops to a single read of
``eff`` plus three [P]-sized writes.

This replaces the hottest memory-bound op of the planner's while-loop; the
reference's analogous work is the per-partition ``sort.Sort(nodeSorter)``
inside its sequential loop (reference plan.go:172, plan.go:617-628).

Correctness notes:
- Ties break toward the LOWEST index (strict ``<`` when merging tiles, and
  ``jnp.argmin``'s first-occurrence rule within a tile) — matching
  ``jnp.argmin`` exactly, which the planner relies on for determinism.
- ``second`` masks the argmin position, not its value: duplicate minima at
  different indices yield ``second == best``, as the planner expects for
  its urgency margin.
- Rows are padded with +inf when P or N is not a multiple of the tile; a
  padded N-tail can never win a min, and padded rows are sliced off.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._tiles import tile_env

__all__ = ["min2_argmin", "min2_argmin_reference", "priced_min2_argmin",
           "pallas_available"]

_INF = float("inf")

# Default tile shape for the priced reduction, overridable for tuning
# sweeps (ops/_tiles.py).  This kernel is the matrix engine's hot
# op in BOTH the cold fixpoint and the warm one-sweep repair, so the
# sweep's tile choice feeds the delta-replan path too.  Read once at
# import (jit-static; see ops/_tiles.py).
_TILE_P = tile_env("BLANCE_REDUCE2_TILE_P", 256, 8)
_TILE_N = tile_env("BLANCE_REDUCE2_TILE_N", 2048, 128)


def min2_argmin_reference(eff: jnp.ndarray):
    """Stock-XLA spelling (the fallback path and the test oracle)."""
    p = eff.shape[0]
    best = jnp.min(eff, axis=1)
    choice = jnp.argmin(eff, axis=1).astype(jnp.int32)
    masked = eff.at[jnp.arange(p), choice].set(jnp.inf)
    second = jnp.min(masked, axis=1)
    return best, choice, second


def _kernel(x_ref, price_ref, best_ref, idx_ref, second_ref, *,
            tile_n: int, n: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        best_ref[:] = jnp.full_like(best_ref, _INF)
        second_ref[:] = jnp.full_like(second_ref, _INF)
        idx_ref[:] = jnp.zeros_like(idx_ref)

    # Fold the per-node price row in VMEM instead of materializing the
    # priced matrix in HBM (the auction re-prices every round; without the
    # fusion each round costs a full [P, N] write + read of `eff`).
    x = x_ref[:] + price_ref[:]  # [TP, TN] + [1, TN]
    tp, tn = x.shape
    cols = jax.lax.broadcasted_iota(jnp.int32, (tp, tn), 1)
    # Mask the ragged N tail (pallas zero-fills partial blocks; a stray 0
    # would beat real scores) so no host-side padding copy is ever needed.
    if n % tn:
        x = jnp.where(j * tile_n + cols < n, x, _INF)

    tile_best = jnp.min(x, axis=1, keepdims=True)  # [TP, 1]
    is_min = x == tile_best
    # First-occurrence argmin within the tile.
    tile_idx = jnp.min(jnp.where(is_min, cols, tn), axis=1, keepdims=True)
    # Second-min masks the argmin POSITION only.
    x_wo = jnp.where(cols == tile_idx, _INF, x)
    tile_second = jnp.min(x_wo, axis=1, keepdims=True)
    tile_idx = tile_idx + j * tile_n

    run_best = best_ref[:]
    run_second = second_ref[:]
    run_idx = idx_ref[:]

    new_best = jnp.minimum(run_best, tile_best)
    # The loser of the best-vs-best match is a second-min candidate.
    new_second = jnp.minimum(jnp.maximum(run_best, tile_best),
                             jnp.minimum(run_second, tile_second))
    # Strict <: on equal values the earlier (lower-index) tile keeps argmin.
    new_idx = jnp.where(tile_best < run_best, tile_idx, run_idx)

    best_ref[:] = new_best
    second_ref[:] = new_second
    idx_ref[:] = new_idx


@functools.partial(jax.jit, static_argnames=("tile_p", "tile_n", "interpret"))
def priced_min2_argmin(
    score: jnp.ndarray,
    price: jnp.ndarray,
    *,
    tile_p: int = _TILE_P,
    tile_n: int = _TILE_N,
    interpret: bool = False,
):
    """Fused (best, argmin, second-min) over axis 1 of ``score + price``.

    ``price[N]`` is the auction's per-node additive term (in-slot price +
    closed-node penalty); it is broadcast-added inside the kernel so the
    priced matrix never exists in HBM.  Returns ``(best[P] f32,
    choice[P] i32, second[P] f32)`` — bit-identical to
    ``min2_argmin_reference(score + price[None, :])``.
    """
    p, n = score.shape
    if n == 0:
        # A zero-size row reduction has no defined argmin; fail loudly like
        # the XLA oracle instead of returning never-written buffers.
        raise ValueError("min2_argmin requires N >= 1 (got shape %r)"
                         % ((p, n),))
    tp = min(tile_p, max(p, 1))
    tn = min(tile_n, n)

    grid = (pl.cdiv(p, tp), pl.cdiv(n, tn))
    out_shape = [
        jax.ShapeDtypeStruct((p, 1), jnp.float32),  # best
        jax.ShapeDtypeStruct((p, 1), jnp.int32),    # idx
        jax.ShapeDtypeStruct((p, 1), jnp.float32),  # second
    ]
    # Output blocks ignore the N grid index, so the accumulators stay
    # resident in VMEM across the whole N sweep of a P tile.  Ragged tails
    # need no padding: partial P blocks reduce row-wise (garbage rows never
    # touch real rows) and the ragged N tail is masked in-kernel.
    out_spec = pl.BlockSpec((tp, 1), lambda i, j: (i, 0))
    best, idx, second = pl.pallas_call(
        functools.partial(_kernel, tile_n=tn, n=n),
        out_shape=out_shape,
        grid=grid,
        in_specs=[pl.BlockSpec((tp, tn), lambda i, j: (i, j)),
                  pl.BlockSpec((1, tn), lambda i, j: (0, j))],
        out_specs=[out_spec, out_spec, out_spec],
        interpret=interpret,
    )(score, price.reshape(1, n).astype(jnp.float32))

    return best[:, 0], idx[:, 0], second[:, 0]


@functools.partial(jax.jit, static_argnames=("tile_p", "tile_n", "interpret"))
def min2_argmin(
    eff: jnp.ndarray,
    *,
    tile_p: int = _TILE_P,
    tile_n: int = _TILE_N,
    interpret: bool = False,
):
    """Fused (best, argmin, second-min) over axis 1 of ``eff[P, N]``."""
    return priced_min2_argmin(
        eff, jnp.zeros(eff.shape[1], jnp.float32),
        tile_p=tile_p, tile_n=tile_n, interpret=interpret)


def pallas_available() -> bool:
    """True when the Pallas path should be used (a real TPU backend)."""
    try:
        return jax.default_backend() == "tpu"
    except RuntimeError:  # pragma: no cover — backend init failed: no
        return False      # usable device at all, so no Pallas either
