"""Shared tile-size plumbing for the Pallas kernels.

Both kernels (the priced min2 reduction in reduce2.py and the in-kernel
score in score_fused.py) tile [P, N] work into (TILE_P, TILE_N) VMEM
blocks.  The tile shape is a pure throughput knob — results are
bit-identical across tiles — so it is tunable per deployment via
environment variables, read ONCE at import: the values are jit-static,
and changing them mid-process would silently recompile rather than
retune.  A tile is chosen on the chip: run the benchmark
(``benchmark/run.py``) with the ``BLANCE_*_TILE_*`` variables set, one
candidate per process.
"""

from __future__ import annotations

import os

__all__ = ["tile_env"]


def tile_env(name: str, default: int, multiple: int) -> int:
    """Read a tile size from the environment, validated for TPU
    sublane/lane alignment (an unaligned tile dies deep inside Mosaic
    with an opaque lowering error; reject it here with the env var's
    name instead)."""
    raw = os.environ.get(name)
    if raw is None:
        return default
    try:
        v = int(raw)
    except ValueError:
        raise ValueError(f"{name}={raw!r} is not an integer") from None
    if v < 1:
        raise ValueError(f"{name}={v} must be >= 1")
    if v % multiple:
        raise ValueError(
            f"{name}={v} must be a multiple of {multiple} (TPU "
            f"sublane/lane alignment)")
    return v
