"""Pallas TPU kernel: fused priced (min, argmin, second, raw) over a
gathered [P, K] candidate score block.

The sparse auction round (plan/tensor.py, shortlist path) needs, per
partition row of the gathered score block ``score[P, K]`` (K candidate
columns per row, K << N) and its gathered per-candidate price
``price[P, K]``:

    eff    = score + price
    best   = min(eff, axis=1)
    kidx   = argmin(eff, axis=1)              (first occurrence)
    second = min(eff with the argmin POSITION masked out, axis=1)
    raw    = score[row, kidx]                 (UNPRICED score at the pick)

The stock-XLA spelling costs four [P, K] HBM passes (min, argmin, a full
masked copy for the second, a take for raw).  This kernel fuses all four
into one: each grid step loads a (TILE_P, TILE_K) block pair into VMEM,
reduces on the VPU, and merges into running accumulators resident in
VMEM across the K-axis grid dimension — the [P, K] shape of the sparse
solve is exactly what makes the whole sweep O(P*K) instead of the dense
engine's O(P*N), so its reduction must not re-read the block.

Unlike ops/reduce2.py the price is a per-(row, candidate) MATRIX, not a
broadcast [N] row: the candidate ids differ per row, so the caller
gathers ``price_full[cand]`` once per round (that gather IS the sparse
memory budget) and this kernel fuses everything downstream of it.

Correctness notes:
- Ties break toward the LOWEST candidate index (strict ``<`` across
  tiles, ``jnp.argmin`` first-occurrence within a tile) — matching
  :func:`sparse_min2_reference` exactly, which the planner's saturating
  K = N bit-identity contract relies on (candidate column k IS node k
  there, so tie order matches the dense engine's lowest-node-id rule).
- ``second`` masks the argmin position, not its value: duplicate minima
  at different candidates yield ``second == best``.
- Ragged K tails are masked in-kernel with +inf; padded rows reduce
  garbage into garbage and are sliced off by pallas itself.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._tiles import tile_env
from .reduce2 import pallas_available

__all__ = ["sparse_min2_reference", "sparse_priced_min2",
           "pallas_available"]

_INF = float("inf")

# Tile shape for the sparse reduction, overridable for tuning sweeps.
# K is small by design (tens), so the default K tile covers the whole
# candidate axis in one block for every realistic shortlist; the P tile
# matches the other kernels' sublane-aligned default.  Read once at
# import (jit-static; see ops/_tiles.py).
_TILE_P = tile_env("BLANCE_SPARSE2_TILE_P", 512, 8)
_TILE_K = tile_env("BLANCE_SPARSE2_TILE_K", 512, 128)


def sparse_min2_reference(score: jnp.ndarray, price: jnp.ndarray):
    """Stock-XLA spelling (fallback path and test oracle).

    Returns ``(best[P] f32, kidx[P] i32, second[P] f32, raw[P] f32)``
    over ``eff = score + price`` with raw = the UNPRICED score at the
    argmin — the exact tuple the sparse auction consumes.
    """
    p = score.shape[0]
    eff = score + price
    best = jnp.min(eff, axis=1)
    kidx = jnp.argmin(eff, axis=1).astype(jnp.int32)
    masked = eff.at[jnp.arange(p), kidx].set(jnp.inf)
    second = jnp.min(masked, axis=1)
    raw = jnp.take_along_axis(score, kidx[:, None], axis=1)[:, 0]
    return best, kidx, second, raw


def _kernel(score_ref, price_ref, best_ref, idx_ref, second_ref, raw_ref,
            *, tile_k: int, k: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        best_ref[:] = jnp.full_like(best_ref, _INF)
        second_ref[:] = jnp.full_like(second_ref, _INF)
        idx_ref[:] = jnp.zeros_like(idx_ref)
        raw_ref[:] = jnp.zeros_like(raw_ref)

    score = score_ref[:]
    x = score + price_ref[:]  # [TP, TK]
    tp, tk = x.shape
    cols = jax.lax.broadcasted_iota(jnp.int32, (tp, tk), 1)
    # Mask the ragged K tail (pallas zero-fills partial blocks; a stray 0
    # would beat real scores) so no host-side padding copy is needed.
    if k % tk:
        x = jnp.where(j * tile_k + cols < k, x, _INF)

    tile_best = jnp.min(x, axis=1, keepdims=True)  # [TP, 1]
    is_min = x == tile_best
    # First-occurrence argmin within the tile.
    tile_idx = jnp.min(jnp.where(is_min, cols, tk), axis=1, keepdims=True)
    # Second-min masks the argmin POSITION only.
    x_wo = jnp.where(cols == tile_idx, _INF, x)
    tile_second = jnp.min(x_wo, axis=1, keepdims=True)
    # Unpriced score at the tile argmin (a masked sum: exactly one hit).
    tile_raw = jnp.sum(jnp.where(cols == tile_idx, score, 0.0), axis=1,
                       keepdims=True)
    tile_idx = tile_idx + j * tile_k

    run_best = best_ref[:]
    run_second = second_ref[:]

    # The loser of the best-vs-best match is a second-min candidate.
    new_second = jnp.minimum(jnp.maximum(run_best, tile_best),
                             jnp.minimum(run_second, tile_second))
    # Strict <: on equal values the earlier (lower-index) tile keeps argmin.
    win = tile_best < run_best
    best_ref[:] = jnp.minimum(run_best, tile_best)
    second_ref[:] = new_second
    idx_ref[:] = jnp.where(win, tile_idx, idx_ref[:])
    raw_ref[:] = jnp.where(win, tile_raw, raw_ref[:])


@functools.partial(jax.jit,
                   static_argnames=("tile_p", "tile_k", "interpret"))
def sparse_priced_min2(
    score: jnp.ndarray,  # [P, K] gathered candidate scores
    price: jnp.ndarray,  # [P, K] gathered per-candidate prices
    *,
    tile_p: int = _TILE_P,
    tile_k: int = _TILE_K,
    interpret: bool = False,
):
    """Fused (best, argmin, second, raw) over ``score + price``.

    Bit-identical to :func:`sparse_min2_reference` (pinned by
    tests/test_sparse.py in interpret mode; chip_smoke.py's kernels
    phase checks the compiled kernel on the chip).
    """
    p, k = score.shape
    if k == 0:
        # A zero-size row reduction has no defined argmin; fail loudly
        # like the XLA oracle instead of returning never-written buffers.
        raise ValueError("sparse_priced_min2 requires K >= 1 (got shape "
                         "%r)" % ((p, k),))
    if price.shape != score.shape:
        raise ValueError(f"price shape {price.shape} != score shape "
                         f"{score.shape}")
    tp = min(tile_p, max(p, 1))
    tk = min(tile_k, k)

    grid = (pl.cdiv(p, tp), pl.cdiv(k, tk))
    out_shape = [
        jax.ShapeDtypeStruct((p, 1), jnp.float32),  # best
        jax.ShapeDtypeStruct((p, 1), jnp.int32),    # idx
        jax.ShapeDtypeStruct((p, 1), jnp.float32),  # second
        jax.ShapeDtypeStruct((p, 1), jnp.float32),  # raw
    ]
    # Output blocks ignore the K grid index, so the accumulators stay
    # resident in VMEM across the whole K sweep of a P tile.
    out_spec = pl.BlockSpec((tp, 1), lambda i, j: (i, 0))
    block = pl.BlockSpec((tp, tk), lambda i, j: (i, j))
    best, idx, second, raw = pl.pallas_call(
        functools.partial(_kernel, tile_k=tk, k=k),
        out_shape=out_shape,
        grid=grid,
        in_specs=[block, block],
        out_specs=[out_spec, out_spec, out_spec, out_spec],
        interpret=interpret,
    )(score, price.astype(jnp.float32))

    return best[:, 0], idx[:, 0], second[:, 0], raw[:, 0]
