"""The Pallas kernels compile for the chip at the north-star widths.

Every other kernel test runs in interpret mode, which cannot see what
the TPU compiler refuses (misaligned tiles, too much VMEM).  These AOT-
compile each kernel of the main path for one chip of a DESCRIBED v5e
(no chip attached) and check that the kernel survived as a Mosaic
``tpu_custom_call``.  The topology is described inside a module fixture,
never at import: only one process may load the TPU library, and the
test workers must all collect the same tests.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

P, N = 100_000, 10_000  # the north star


@pytest.fixture(scope="module")
def one_chip():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A described chip's executables can be written to the persistent
    # cache but never read back without the chip: keep them out of it.
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("k", [16, 512])  # the north star's auto K; a tile
def test_sparse2_compiles(one_chip, k):
    from blance_tpu.ops.sparse2 import sparse_priced_min2

    x = _sds((P, k), jnp.float32, one_chip)
    _assert_kernel(sparse_priced_min2, x, x)


def test_reduce2_compiles(one_chip):
    from blance_tpu.ops.reduce2 import priced_min2_argmin

    _assert_kernel(priced_min2_argmin,
                   _sds((8192, N), jnp.float32, one_chip),
                   _sds((N,), jnp.float32, one_chip))


def test_score_fused_compiles(one_chip):
    """The in-kernel score for the replica slot under the rack rule, fed
    through the real input packer."""
    from blance_tpu.ops.score_fused import fused_score_min2, pack_score_inputs

    rules = ((2, 1),)

    def step(price, total, w_div, valid, stick, prev_state, taken,
             anchors, gids, gid_valid, pbase):
        si = pack_score_inputs(
            total_l=total, total_p=jnp.float32(P), w_div_l=w_div,
            neg_boost_l=jnp.zeros_like(total), valid_l=valid,
            stickiness_si=stick, prev_slot=prev_state[:, 0],
            prev_state=prev_state, taken_ids=[taken], anchors=anchors,
            gids_l=gids, gid_valid=gid_valid, gids=gids, rules=rules)
        return fused_score_min2(price, si, pbase, pbase, nrules=len(rules),
                                jitter_scale=1.0e-5)

    f32, i32 = jnp.float32, jnp.int32
    _assert_kernel(
        step,
        *(_sds(s, d, one_chip) for s, d in (
            ((N,), f32), ((N,), f32), ((N,), f32), ((N,), np.bool_),
            ((P,), f32), ((P, 1), i32), ((P,), i32), ((P, 2), i32),
            ((3, N), i32), ((3, N), np.bool_), ((1, 1), i32))))
