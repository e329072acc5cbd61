"""Build the native runtime lib: python -m butterfly_tpu.native.build."""
from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent.parent


def build(verbose: bool = True) -> Path:
    out = Path(__file__).parent / "libbutterfly_native.so"
    src = REPO / "native" / "allocator.cc"
    # compile beside the target, then rename: a process loading the lib
    # while another builds it never sees a half-written file
    tmp = out.with_suffix(".so.part")
    cmd = ["g++", "-O2", "-fPIC", "-std=c++17", "-Wall", "-Wextra",
           "-shared", "-o", str(tmp), str(src)]
    if verbose:
        print(" ".join(cmd))
    subprocess.run(cmd, check=True)
    os.replace(tmp, out)
    return out


if __name__ == "__main__":
    path = build()
    print(f"built {path}")
    sys.exit(0)
