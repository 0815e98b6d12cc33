"""Build-regression guard for the native layers.

The marshal and planner test files skip wholesale when their native
module is unavailable — correct on machines with no toolchain, but a
silent hole when a compiler exists and the build itself regressed (a
syntax error in marshal.c would otherwise just skip 10 parity tests).
These tests FAIL, not skip, whenever a C/C++ toolchain is present but
the native layer won't load.
"""

import shutil

import pytest


def _has(*names):
    return any(shutil.which(n) for n in names)


@pytest.mark.skipif(not _has("cc", "gcc", "clang"),
                    reason="no C compiler on this machine")
def test_marshal_extension_builds():
    from blance_tpu.core import marshal

    assert marshal.available(), (
        "C toolchain present but the marshal extension failed to "
        "build/load — check the compile log under core/_native_build")


@pytest.mark.skipif(not _has("c++", "g++", "clang++"),
                    reason="no C++ compiler on this machine")
def test_native_planner_builds():
    from blance_tpu.plan.native import native_available

    assert native_available(), (
        "C++ toolchain present but the native planner failed to "
        "build/load")


@pytest.mark.skipif(not _has("gcc"), reason="no C compiler on this machine")
def test_compile_cached_keys_on_source_and_command(tmp_path):
    """The cached binary is found by a hash of the source and the command:
    an mtime bump reuses it, an edited source or a changed command
    builds a new one (and retires the old), a failed build reports None."""
    import os

    from blance_tpu.utils.nativebuild import compile_cached

    src = tmp_path / "k.c"
    src.write_text("int k(void) { return 1; }\n")
    out = str(tmp_path / "build" / "k.so")

    def build(*flags):
        return compile_cached(str(src), out, ["gcc", *flags, "-shared",
                                              "-fPIC", "-o", out, str(src)])

    first = build()
    assert first and os.path.exists(first) and first != out
    os.utime(src, (0, 2 ** 31 - 1))  # newer than any binary
    assert build() == first
    src.write_text("int k(void) { return 2; }\n")
    edited = build()
    assert edited != first and not os.path.exists(first)
    assert build("-O2") not in (edited, None)
    src.write_text("int k(void) { return }\n")
    assert build() is None
    assert compile_cached(str(tmp_path / "missing.c"), out, ["gcc"]) is None
