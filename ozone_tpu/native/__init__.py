"""Native (C++) kernels: build-on-demand + ctypes bindings.

Build model mirrors the reference's native-loader pattern
(ErasureCodeNative.java:42-63 — probe for the native library, fall back
gracefully): each .so is compiled from its tracked source with g++ on
first use and cached next to it (git-ignored, so a fresh checkout
builds everything it needs). A host with no toolchain simply goes
without the native backends; a toolchain that FAILS to build is an
error the caller sees (NativeBuildError, out of build_shared and out
of every loader built on it — none turns it into "unavailable").
"""

from __future__ import annotations

import ctypes
import fcntl
import hashlib
import logging
import os
import shutil
import subprocess
import threading
from pathlib import Path
from typing import Optional

log = logging.getLogger(__name__)

_HERE = Path(__file__).parent
_SRC = _HERE / "gf_coder.cpp"
_SO = _HERE / "libgf_coder.so"
_lock = threading.RLock()
_lib: Optional[ctypes.CDLL] = None
_tried = False


class NativeBuildError(OSError):
    """The toolchain is present and the compile failed."""


def _cpu_id() -> str:
    """The host CPU as the kernel names it. Several libraries build
    with -march=native, so a .so is only valid on the CPU that built it
    — one copied in from another machine can SIGILL."""
    keep = {}
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                key = line.split(":", 1)[0].strip()
                if key in ("model name", "flags") and key not in keep:
                    keep[key] = line.strip()
                if len(keep) == 2:
                    break
    except OSError:
        pass
    return "\n".join(keep.values()) or os.uname().machine


def build_shared(src: Path, so: Path, compiler: str = "g++",
                 extra: tuple = ()) -> Optional[Path]:
    """Compile `src` into shared library `so` unless an up-to-date one
    is there; returns the path, or None when no toolchain is available.
    Raises NativeBuildError when the compile fails. One shared
    implementation of the build-on-demand probe used by every native
    component (coder, datapath, failure injector, libo3fs).

    Up to date means the stamp beside the .so matches source bytes +
    compiler + flags + host CPU — not mtimes, which a copied or
    checked-out tree scrambles. Safe across processes: the build holds
    an flock on the library's directory (N datanodes starting together
    on a fresh checkout compile once) and lands by os.replace, so a
    concurrent loader never maps a half-written file."""
    flags = ["-O2", "-shared", "-fPIC", *extra]
    want = hashlib.sha256("\0".join(
        [compiler, " ".join(flags), _cpu_id()]).encode()
        + b"\0" + src.read_bytes()).hexdigest()
    stamp = so.with_name(so.name + ".stamp")

    def fresh() -> bool:
        try:
            return so.exists() and stamp.read_text() == want
        except OSError:
            return False

    if fresh():
        return so
    if shutil.which(compiler) is None:
        log.warning("no %s on this host: %s not built", compiler, so.name)
        return None
    dir_fd = os.open(so.parent, os.O_RDONLY)
    try:
        fcntl.flock(dir_fd, fcntl.LOCK_EX)
        if fresh():  # built by another process while this one waited
            return so
        tmp = so.with_name(f".{so.name}.{os.getpid()}.tmp")
        try:
            subprocess.run(
                [compiler, *flags, "-o", str(tmp), str(src)],
                check=True, capture_output=True, timeout=300,
            )
            os.replace(tmp, so)
            tmp.write_text(want)
            os.replace(tmp, stamp)
        except subprocess.CalledProcessError as e:
            raise NativeBuildError(
                f"native build of {src.name} failed: "
                f"{e.stderr.decode(errors='replace')[-2000:]}") from e
        except subprocess.TimeoutExpired as e:
            raise NativeBuildError(
                f"native build of {src.name} timed out") from e
        finally:
            tmp.unlink(missing_ok=True)
        return so
    finally:
        os.close(dir_fd)  # releases the flock


def load() -> Optional[ctypes.CDLL]:
    """Load (building if needed) the native library; None where the
    host has no toolchain or cannot map the library. A compile that
    fails raises NativeBuildError, now and on every later call."""
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        # -O3 -march=native: the coder kernels are the datapath on hosts
        # without a chip; later flags override build_shared's -O2
        so = build_shared(_SRC, _SO,
                          extra=("-O3", "-march=native", "-pthread"))
        _tried = True
        if so is None:
            return None
        try:
            lib = ctypes.CDLL(str(so))
            lib.gf_matrix_apply.argtypes = [
                ctypes.c_void_p, ctypes.c_int, ctypes.c_int,
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
            ]
            lib.gf_matrix_apply_batch.argtypes = [
                ctypes.c_void_p, ctypes.c_int, ctypes.c_int,
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
                ctypes.c_int64,
            ]
            lib.gf_matrix_apply_batch_mt.argtypes = [
                ctypes.c_void_p, ctypes.c_int, ctypes.c_int,
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
                ctypes.c_int64, ctypes.c_int,
            ]
            lib.crc32c_hw.argtypes = [
                ctypes.c_void_p, ctypes.c_int64, ctypes.c_uint32,
            ]
            lib.crc32c_hw.restype = ctypes.c_uint32
            lib.crc32c_slices.argtypes = [
                ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
                ctypes.c_void_p,
            ]
            lib.native_probe.restype = ctypes.c_int
            _lib = lib
            log.info("native coder loaded (simd level %d)", lib.native_probe())
        except OSError as e:
            log.warning("native coder unavailable: %s", e)
            _lib = None
        return _lib
