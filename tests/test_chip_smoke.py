"""CPU rehearsal of chip_smoke.py: every phase of the one-chip and the
four-chip runs at a tiny size (kernels in interpret mode, 4 of the
virtual devices for the mesh), and the refusal to run without a TPU."""

import dataclasses

import jax
import pytest

import chip_smoke


@pytest.mark.parametrize("sparse", [None, True], ids=["auto", "sparse"])
def test_single_chip_phases(capsys, sparse):
    """``sparse=True`` steers the tiny problem onto the sparse engine,
    which the chip's north-star size selects on its own."""
    dep = chip_smoke.deployment(600, 60)
    dep.opts = dataclasses.replace(dep.opts, sparse=sparse)
    chip_smoke.phase_rebalance(dep, require_chip=False)
    chip_smoke.phase_pipeline(dep)
    chip_smoke.phase_dense(dep, require_chip=False)
    chip_smoke.phase_kernels(dep, interpret=True)
    out = capsys.readouterr().out
    assert ('"engine": "sparse/xla' in out) == bool(sparse)
    for name in ("rebalance", "pipeline", "dense", "kernels"):
        assert f"phase {name}: " in out


def test_four_chip_phases_on_virtual_devices(capsys):
    chip_smoke.four_chips(640, 64, jax.devices()[:4])
    out = capsys.readouterr().out
    for name in ("one-chip", "sharded-sparse", "sharded-pipeline"):
        assert f"phase {name}: " in out


def test_refuses_without_tpu(capsys):
    assert chip_smoke.main([]) == 2
    assert capsys.readouterr().out == ""
