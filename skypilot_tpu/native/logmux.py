"""ctypes wrapper for the C++ log mux (native/logmux.cpp).

`LogMux` fans N stream fds into per-rank files + one combined, prefixed
log on a single native thread (no GIL on the hot loop). Builds
liblogmux.so on first use; returns None from the loader when no compiler
is available, and the gang driver falls back to Python pump threads.
"""
from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading
from typing import List, Optional

logger = logging.getLogger(__name__)

_SRC_DIR = os.path.dirname(os.path.abspath(__file__))
_SO_PATH = os.path.join(_SRC_DIR, 'liblogmux.so')
_BUILD_LOCK = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_load_failed = False


def _build() -> bool:
    src = os.path.join(_SRC_DIR, 'logmux.cpp')
    cmd = ['g++', '-O2', '-shared', '-fPIC', '-std=c++17', '-o', _SO_PATH,
           src, '-lpthread']
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=120, check=False)
    except (OSError, subprocess.TimeoutExpired) as e:
        logger.warning('logmux build skipped (python pump in use): %s',
                       e)
        return False
    if proc.returncode != 0:
        logger.warning('logmux build failed:\n%s', proc.stderr)
        return False
    return True


def load_logmux_library() -> Optional[ctypes.CDLL]:
    """Load (building if needed) liblogmux.so; None if unavailable."""
    global _lib, _load_failed
    if _lib is not None:
        return _lib
    if _load_failed:
        return None
    with _BUILD_LOCK:
        if _lib is not None:
            return _lib
        src = os.path.join(_SRC_DIR, 'logmux.cpp')
        needs_build = (not os.path.exists(_SO_PATH) or
                       (os.path.exists(src) and
                        os.path.getmtime(src) > os.path.getmtime(_SO_PATH)))
        if needs_build and not _build():
            _load_failed = True
            return None
        try:
            lib = ctypes.CDLL(_SO_PATH)
        except OSError as e:
            logger.warning('logmux load failed: %s', e)
            _load_failed = True
            return None
        lib.logmux_create.restype = ctypes.c_void_p
        lib.logmux_create.argtypes = [ctypes.c_char_p]
        lib.logmux_add_stream.restype = ctypes.c_int
        lib.logmux_add_stream.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.c_char_p, ctypes.c_char_p
        ]
        lib.logmux_start.restype = ctypes.c_int
        lib.logmux_start.argtypes = [ctypes.c_void_p]
        lib.logmux_stop.restype = None
        lib.logmux_stop.argtypes = [ctypes.c_void_p]
        lib.logmux_wait.restype = None
        lib.logmux_wait.argtypes = [ctypes.c_void_p]
        lib.logmux_lines.restype = ctypes.c_long
        lib.logmux_lines.argtypes = [ctypes.c_void_p]
        lib.logmux_destroy.restype = None
        lib.logmux_destroy.argtypes = [ctypes.c_void_p]
        _lib = lib
        return _lib


class LogMux:
    """One muxing session: add streams, start, wait, destroy."""

    def __init__(self, combined_path: str) -> None:
        lib = load_logmux_library()
        if lib is None:
            raise RuntimeError('native logmux unavailable')
        self._lib = lib
        self._handle = lib.logmux_create(
            os.path.expanduser(combined_path).encode())
        if not self._handle:
            raise RuntimeError(f'logmux_create({combined_path!r}) failed')
        self._fds: List[int] = []

    def add_stream(self, fd: int, rank_log_path: str,
                   prefix: str = '') -> int:
        index = self._lib.logmux_add_stream(
            self._handle, fd, os.path.expanduser(rank_log_path).encode(),
            prefix.encode())
        if index < 0:
            raise RuntimeError(f'logmux_add_stream({rank_log_path}) failed')
        self._fds.append(fd)
        return index

    def start(self) -> None:
        if self._lib.logmux_start(self._handle) != 0:
            raise RuntimeError('logmux_start failed')

    def stop(self) -> None:
        """Ask the native thread to exit at its next poll tick. Call this
        (then wait()) BEFORE closing stream fds from Python — never close
        an fd the native thread might still be polling."""
        self._lib.logmux_stop(self._handle)

    def wait(self) -> None:
        self._lib.logmux_wait(self._handle)

    @property
    def lines(self) -> int:
        return self._lib.logmux_lines(self._handle)

    def close(self) -> None:
        if self._handle:
            self._lib.logmux_destroy(self._handle)
            self._handle = None

    def __enter__(self) -> 'LogMux':
        return self

    def __exit__(self, *exc) -> None:
        self.close()
