"""Build the native C++ baselines (native/*.cc) on the machine that
loads them.

The artefact name carries a hash of the committed source, the compiler
flags and the host CPU, so a library built elsewhere — the tree is
copied between machines as it stands on disk, git-ignored
``native/build/`` included — is never loaded: ``-march=native`` code
from another CPU would be wrong here at best and SIGILL at worst.
Deleting ``native/build/`` and rerunning rebuilds the same code from
the same sources.
"""

from __future__ import annotations

import hashlib
import os
import platform
import subprocess
from typing import Sequence

_NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "native")


def _host_cpu() -> str:
    """What ``-march=native`` resolves against: the CPU model and its
    feature flags (Linux), else the platform's own description."""
    try:
        with open("/proc/cpuinfo") as f:
            lines = f.read().splitlines()
    except OSError:
        return f"{platform.machine()}|{platform.processor()}"
    keep = {}
    for ln in lines:
        key = ln.split(":", 1)[0].strip()
        if key in ("model name", "flags", "Features") and key not in keep:
            keep[key] = ln
    return "|".join(keep[k] for k in sorted(keep)) or platform.machine()


def build_native(source: str, flags: Sequence[str]) -> str:
    """Compile ``native/<source>`` with g++ into ``native/build/`` unless
    this exact (source, flags, host CPU) was already built here;
    returns the shared library's path."""
    src = os.path.join(_NATIVE_DIR, source)
    with open(src, "rb") as f:
        digest = hashlib.sha256(f.read())
    digest.update("\0".join(flags).encode())
    digest.update(_host_cpu().encode())
    stem = os.path.splitext(source)[0]
    so = os.path.join(_NATIVE_DIR, "build",
                      f"lib{stem}.{digest.hexdigest()[:16]}.so")
    if not os.path.exists(so):
        os.makedirs(os.path.dirname(so), exist_ok=True)
        # build aside, then rename: a concurrent process (xdist worker,
        # fleet member) never loads a half-written library
        tmp = f"{so}.{os.getpid()}.tmp"
        subprocess.run(["g++", *flags, "-shared", "-fPIC", src, "-o", tmp],
                       check=True, capture_output=True)
        os.replace(tmp, so)
    return so
