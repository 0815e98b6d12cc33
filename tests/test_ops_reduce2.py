"""Pallas fused (min, argmin, second-min) kernel vs the XLA oracle.

Runs the kernel in interpret mode (tests run on the CPU platform,
tests/conftest.py); compiled-mode parity on a real chip is exercised by
chip_smoke.py's kernels phase.  The contract under test is the one the
auction loop (blance_tpu/plan/tensor.py) depends on:

- argmin ties break to the lowest index (determinism of the planner);
- ``second`` masks the argmin POSITION, so duplicate minima at different
  indices give second == best (the urgency margin must be 0 then);
- ragged P and N tails change nothing (no host-side padding).
"""

import numpy as np
import pytest

import jax.numpy as jnp

from blance_tpu.ops.reduce2 import min2_argmin, min2_argmin_reference


def _check(x, tile_p=8, tile_n=128):
    b0, i0, s0 = min2_argmin_reference(jnp.asarray(x))
    b1, i1, s1 = min2_argmin(
        jnp.asarray(x), tile_p=tile_p, tile_n=tile_n, interpret=True)
    np.testing.assert_array_equal(np.asarray(b0), np.asarray(b1))
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_array_equal(np.asarray(s0), np.asarray(s1))


@pytest.mark.parametrize("shape", [(7, 5), (16, 128), (130, 300), (33, 513)])
def test_matches_oracle_random(shape):
    rng = np.random.default_rng(hash(shape) % 2**32)
    _check(rng.standard_normal(shape).astype(np.float32))


def test_ties_break_low_index_across_tiles():
    # The global min appears in several N tiles; argmin must pick the first.
    x = np.ones((9, 300), np.float32)
    x[:, 37] = x[:, 157] = x[:, 290] = -2.0
    b, i, s = min2_argmin(jnp.asarray(x), tile_p=8, tile_n=128,
                          interpret=True)
    assert np.asarray(i).tolist() == [37] * 9
    # Duplicate minimum elsewhere => second == best.
    np.testing.assert_array_equal(np.asarray(s), np.asarray(b))


def test_second_masks_position_not_value():
    x = np.full((3, 10), 5.0, np.float32)
    x[0, 4] = 1.0          # unique min: second is 5
    x[1, 2] = x[1, 7] = 1.0  # duplicate min: second is 1
    b, i, s = min2_argmin(jnp.asarray(x), tile_p=8, tile_n=8, interpret=True)
    assert np.asarray(b).tolist() == [1.0, 1.0, 5.0]
    assert np.asarray(i).tolist() == [4, 2, 0]
    assert np.asarray(s).tolist() == [5.0, 1.0, 5.0]


def test_inf_rows():
    # Fully forbidden rows (all +inf) must not crash and keep index 0.
    x = np.full((4, 20), np.inf, np.float32)
    x[1, 3] = 7.0
    _check(x, tile_p=2, tile_n=16)


@pytest.mark.parametrize("shape", [(7, 5), (33, 513)])
def test_priced_variant_matches_materialized(shape):
    """priced_min2_argmin(score, price) == oracle(score + price[None, :]) —
    the auction-loop contract (price folded in VMEM, never in HBM)."""
    from blance_tpu.ops.reduce2 import priced_min2_argmin

    rng = np.random.default_rng(hash(shape) % 2**31)
    score = rng.standard_normal(shape).astype(np.float32)
    price = (rng.random(shape[1]) * 3).astype(np.float32)
    price[::4] = 1e9  # closed nodes
    b0, i0, s0 = min2_argmin_reference(jnp.asarray(score + price[None, :]))
    b1, i1, s1 = priced_min2_argmin(
        jnp.asarray(score), jnp.asarray(price), tile_p=8, tile_n=128,
        interpret=True)
    np.testing.assert_array_equal(np.asarray(b0), np.asarray(b1))
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_array_equal(np.asarray(s0), np.asarray(s1))
