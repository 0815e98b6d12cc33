"""Shared compile-and-cache helper for the repo's native components.

Both native loaders — the ctypes planner core (plan/native.py) and the
CPython marshalling extension (core/marshal.py) — need the same shape:
compile the source once, cache the binary next to the package, and never
hard-fail when the toolchain is missing.  A cached binary is found by a
hash of the source bytes and the compiler command, never by file times:
a copied tree carries whatever binaries sat in it, and only the hash
says they were built from the source that is there now.
"""

from __future__ import annotations

import glob
import hashlib
import os
import subprocess
from typing import Optional

__all__ = ["compile_cached"]

_OUT = "{out}"  # the output path's place in a command, for the key


def compile_cached(source: str, out_path: str,
                   command: list[str]) -> Optional[str]:
    """Build ``source`` into a binary keyed by its content; return the
    binary's path, or None when the source is missing or the build
    failed — callers fall back to their pure-Python paths.

    ``command`` is the full compiler invocation, naming ``out_path`` as
    its output.  The binary lands at ``<stem>.<key><ext>`` beside
    ``out_path``, where ``key`` hashes the source and the command (with
    the output path masked), and is rebuilt only when no binary carries
    that key.  The compiler writes to a process-unique temp path,
    published with an atomic os.replace(), so concurrent importers only
    ever load a fully-written object; binaries of other keys are then
    removed.
    """
    try:
        with open(source, "rb") as f:
            src = f.read()
    except OSError:
        return None
    h = hashlib.sha256(src)
    h.update("\0".join(_OUT if c == out_path else c
                       for c in command).encode())
    stem, ext = os.path.splitext(out_path)
    built = f"{stem}.{h.hexdigest()[:16]}{ext}"
    if os.path.exists(built):
        return built
    tmp_path = f"{built}.{os.getpid()}.tmp"
    try:
        os.makedirs(os.path.dirname(out_path), exist_ok=True)
        subprocess.run([tmp_path if c == out_path else c for c in command],
                       check=True, capture_output=True)
        os.replace(tmp_path, built)
    except (OSError, subprocess.CalledProcessError):
        return None
    finally:
        if os.path.exists(tmp_path):
            try:
                os.remove(tmp_path)
            except OSError:
                pass
    for stale in glob.glob(f"{glob.escape(stem)}.*{ext}"):
        if stale != built:
            try:
                os.remove(stale)
            except OSError:
                pass
    return built
