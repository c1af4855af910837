"""Native (C++) runtime components, built on demand with the system
toolchain and loaded via ctypes — no pybind11 dependency.

``load_msgnet()`` compiles ``msgnet.cpp`` once and returns the ctypes
library with argtypes configured. Build artefacts live in the ignored
``_build/`` under a name keyed on a hash of their sources and compiler
flags, so a binary built from other sources or flags — or one that came
along when the checkout was copied from another machine — has another
name and can never load.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

_HERE = os.path.dirname(os.path.abspath(__file__))
_BUILD = os.path.join(_HERE, "_build")
_LOCK = threading.Lock()
_LIB = None


def _artefact(srcs, flags, stem: str, suffix: str = "") -> str:
    """Path of the artefact for (``srcs``, ``flags``), compiling it if it
    is not there yet. The compiler writes a private temporary that is
    renamed into place, so ranks starting together never load a
    half-written file."""
    digest = hashlib.sha256(" ".join(flags).encode())
    for src in srcs:
        with open(src, "rb") as f:
            digest.update(f.read())
    out = os.path.join(_BUILD, f"{stem}-{digest.hexdigest()[:16]}{suffix}")
    if os.path.isfile(out):
        return out
    os.makedirs(_BUILD, exist_ok=True)
    tmp = f"{out}.{os.getpid()}.tmp"
    cmd = ["g++", *flags, *srcs, "-o", tmp]
    proc = subprocess.run(cmd, capture_output=True, text=True)
    if proc.returncode != 0:
        raise RuntimeError(
            f"native build failed: {' '.join(cmd)}\n{proc.stderr[-4000:]}"
        )
    os.replace(tmp, out)
    return out


def build_stress(sanitize: str = "thread") -> str:
    """Build the msgnet stress binary (linked with the transport sources)
    with a sanitizer — the race-detection harness. Returns the binary path."""
    srcs = [os.path.join(_HERE, "msgnet.cpp"),
            os.path.join(_HERE, "msgnet_stress.cpp")]
    flags = ["-O1", "-g", "-pthread", "-std=c++17", f"-fsanitize={sanitize}"]
    return _artefact(srcs, flags, f"msgnet_stress_{sanitize}")


def load_msgnet() -> ctypes.CDLL:
    """Build (if not built from these sources yet) + load the
    message-transport library."""
    global _LIB
    with _LOCK:
        if _LIB is not None:
            return _LIB
        lib = ctypes.CDLL(_artefact(
            [os.path.join(_HERE, "msgnet.cpp")],
            ["-O2", "-fPIC", "-shared", "-pthread", "-std=c++17"],
            "libmsgnet", ".so"))
        lib.mn_server_create.argtypes = [ctypes.c_int, ctypes.c_int]
        lib.mn_server_create.restype = ctypes.c_int
        lib.mn_server_port.argtypes = [ctypes.c_int]
        lib.mn_server_port.restype = ctypes.c_int
        lib.mn_server_recv.argtypes = [
            ctypes.c_int, ctypes.c_int, ctypes.POINTER(ctypes.c_uint64)
        ]
        lib.mn_server_recv.restype = ctypes.POINTER(ctypes.c_uint8)
        lib.mn_server_stop.argtypes = [ctypes.c_int]
        lib.mn_sender_create.restype = ctypes.c_int
        # data as c_char_p: a Python bytes object passes zero-copy (the C
        # side takes const uint8* + explicit length; embedded NULs are fine).
        lib.mn_send.argtypes = [
            ctypes.c_int, ctypes.c_char_p, ctypes.c_int,
            ctypes.c_char_p, ctypes.c_uint64,
        ]
        lib.mn_send.restype = ctypes.c_int
        lib.mn_sender_destroy.argtypes = [ctypes.c_int]
        lib.mn_free.argtypes = [ctypes.POINTER(ctypes.c_uint8)]
        _LIB = lib
        return lib
