"""Loader for the native C++ helpers under native/.

Compiles each shared library on first use (g++ is baked into the image;
pybind11 is not, so the bindings are ctypes over extern-C surfaces) and
caches it next to the source. Falls back cleanly: callers check
``get_*_lib() is not None`` and use the pure-Python/numpy path otherwise.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Callable, Optional

from faabric_tpu.util.logging import get_logger

logger = get_logger(__name__)

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

_lock = threading.Lock()
# name → loaded lib, or None after a failed attempt (one try per process)
_cache: dict[str, Optional[ctypes.CDLL]] = {}
# name → Event while a build/load is in flight: the compile (up to
# 120 s of subprocess.run) must not run under the module lock — that
# would serialize every other native lib's first use behind it and is
# exactly the blocking-call-under-lock pattern tools/concheck.py flags.
# Losers of the build race park on the event, then re-read the cache.
_in_progress: dict[str, threading.Event] = {}

# Sanitizer build mode (ISSUE 7 satellite): FAABRIC_NATIVE_SAN=tsan|asan
# compiles every native helper with the matching -fsanitize flag into a
# suffixed .so. Loading one into an unsanitized interpreter requires the
# runtime preloaded (LD_PRELOAD=$(g++ -print-file-name=libtsan.so)) —
# tests/unit/test_native_san.py drives that in a subprocess; an
# in-process load attempt without the preload fails cleanly into the
# usual pure-Python fallback.
_SAN_FLAGS = {
    "tsan": ("-fsanitize=thread", "-O1", "-g", "-fno-omit-frame-pointer"),
    "asan": ("-fsanitize=address", "-O1", "-g",
             "-fno-omit-frame-pointer"),
}


def _san_mode() -> str:
    mode = os.environ.get("FAABRIC_NATIVE_SAN", "").strip().lower()
    return mode if mode in _SAN_FLAGS else ""


def _build_and_load(name: str, src_file: str, so_file: str,
                    declare: Callable[[ctypes.CDLL], None],
                    install: Optional[Callable[[ctypes.CDLL], bool]],
                    extra_args: tuple,
                    fail_note: str) -> Optional[ctypes.CDLL]:
    """Compile-if-stale / load / declare / install — no locks held."""
    src = os.path.join(_REPO_ROOT, "native", src_file)
    san = _san_mode()
    if san:
        so_file = f"{so_file.removesuffix('.so')}.{san}.so"
    so = os.path.join(_REPO_ROOT, "native", "build", so_file)
    if not os.path.exists(src):
        return None
    if not os.path.exists(so) or (os.path.getmtime(so)
                                  < os.path.getmtime(src)):
        os.makedirs(os.path.dirname(so), exist_ok=True)
        # No -march=native: native/build/ travels with a copied checkout
        # (it is ignored by git, not by a disk copy), and a library built
        # for one machine's CPU must still load on the next
        opt_args: tuple = _SAN_FLAGS[san] if san else ("-O3",)
        cmd = ["g++", *opt_args, "-shared", "-fPIC",
               src, "-o", so, *extra_args]
        # Never compile under an inherited sanitizer preload: cc1plus/
        # as/ld running through libtsan's interceptors turns a 5 s build
        # into minutes (observed hang when a TSAN-preloaded test process
        # triggered the first sanitized build)
        env = {k: v for k, v in os.environ.items() if k != "LD_PRELOAD"}
        try:
            subprocess.run(cmd, check=True, capture_output=True,
                           timeout=120, env=env)
        except (subprocess.SubprocessError, OSError) as e:
            logger.warning("Native %s build failed (%s); %s",
                           name, e, fail_note)
            return None
    try:
        lib = ctypes.CDLL(so)
    except OSError as e:
        logger.warning("Could not load %s: %s", so, e)
        return None
    declare(lib)
    if install is not None and not install(lib):
        return None
    return lib


def _load_native(name: str, src_file: str, so_file: str,
                 declare: Callable[[ctypes.CDLL], None],
                 install: Optional[Callable[[ctypes.CDLL], bool]] = None,
                 extra_args: tuple = (),
                 fail_note: str = "") -> Optional[ctypes.CDLL]:
    """Shared load path for every native helper; one attempt per process
    per lib, with the build itself running outside the module lock."""
    while True:
        with _lock:
            if name in _cache:
                return _cache[name]
            ev = _in_progress.get(name)
            if ev is None:
                _in_progress[name] = threading.Event()
                break
        # Another thread owns this lib's build: park until it publishes
        # its verdict, then re-read the cache
        ev.wait()
    lib: Optional[ctypes.CDLL] = None
    try:
        lib = _build_and_load(name, src_file, so_file, declare, install,
                              extra_args, fail_note)
    finally:
        with _lock:
            _cache[name] = lib
            _in_progress.pop(name).set()
    return lib


def _declare_pagediff(lib: ctypes.CDLL) -> None:
    # void* arguments: callers pass numpy buffer addresses
    lib.diff_pages.restype = ctypes.c_size_t
    lib.diff_pages.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                               ctypes.c_size_t, ctypes.c_size_t,
                               ctypes.c_void_p]
    lib.diff_ranges.restype = ctypes.c_size_t
    lib.diff_ranges.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                ctypes.c_size_t, ctypes.c_size_t,
                                ctypes.c_void_p, ctypes.c_void_p,
                                ctypes.c_size_t]
    lib.xor_buffers.restype = None
    lib.xor_buffers.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                ctypes.c_void_p, ctypes.c_size_t]


def get_pagediff_lib() -> Optional[ctypes.CDLL]:
    return _load_native("pagediff", "pagediff.cpp", "libpagediff.so",
                        _declare_pagediff, fail_note="using numpy path")


def _declare_shmring(lib: ctypes.CDLL) -> None:
    lib.ring_init.restype = ctypes.c_int
    lib.ring_init.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
    lib.ring_check.restype = ctypes.c_int64
    lib.ring_check.argtypes = [ctypes.c_void_p]
    lib.ring_free_space.restype = ctypes.c_int64
    lib.ring_free_space.argtypes = [ctypes.c_void_p]
    lib.ring_try_pushv.restype = ctypes.c_int
    lib.ring_try_pushv.argtypes = [ctypes.c_void_p,
                                   ctypes.POINTER(ctypes.c_void_p),
                                   ctypes.POINTER(ctypes.c_uint64),
                                   ctypes.c_uint64]
    lib.ring_peek.restype = ctypes.c_int64
    lib.ring_peek.argtypes = [ctypes.c_void_p]
    lib.ring_pop.restype = ctypes.c_int64
    lib.ring_pop.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                             ctypes.c_uint64]
    lib.ring_pop_batch.restype = ctypes.c_int64
    lib.ring_pop_batch.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                   ctypes.c_uint64,
                                   ctypes.POINTER(ctypes.c_uint64),
                                   ctypes.c_uint64]
    lib.ring_wait_data.restype = ctypes.c_int
    lib.ring_wait_data.argtypes = [ctypes.c_void_p, ctypes.c_uint32]
    lib.ring_wait_space.restype = ctypes.c_int
    lib.ring_wait_space.argtypes = [ctypes.c_void_p, ctypes.c_uint64,
                                    ctypes.c_uint32]


def get_shmring_lib() -> Optional[ctypes.CDLL]:
    """The SPSC shared-memory ring (native/shm_ring.cpp) — the
    same-machine bulk data plane's hot path. None when g++ or the source
    is unavailable; callers fall back to the TCP plane."""
    return _load_native("shm_ring", "shm_ring.cpp", "libshmring.so",
                        _declare_shmring,
                        fail_note="same-machine bulk stays on TCP")


def _declare_tracker(prefix: str) -> Callable[[ctypes.CDLL], None]:
    def declare(lib: ctypes.CDLL) -> None:
        install = getattr(lib, f"{prefix}_install")
        install.restype = ctypes.c_int
        install.argtypes = []
        start = getattr(lib, f"{prefix}_start")
        start.restype = ctypes.c_int
        start.argtypes = [ctypes.c_void_p, ctypes.c_uint64, ctypes.c_void_p]
        stop = getattr(lib, f"{prefix}_stop")
        stop.restype = ctypes.c_int
        stop.argtypes = [ctypes.c_int]
    return declare


def get_segv_lib() -> Optional[ctypes.CDLL]:
    """The SIGSEGV write-fault dirty tracker (native/segv_tracker.cpp) —
    O(dirty) page tracking with no baseline copy. None when g++ or the
    source is unavailable; callers fall back to comparison tracking."""
    def install(lib: ctypes.CDLL) -> bool:
        if lib.segv_install() != 0:
            logger.warning("segv_tracker handler install failed")
            return False
        return True

    return _load_native("segv_tracker", "segv_tracker.cpp",
                        "libsegvtracker.so", _declare_tracker("segv"),
                        install=install,
                        fail_note="segv dirty mode unavailable")


def get_uffd_lib() -> Optional[ctypes.CDLL]:
    """The userfaultfd write-protect dirty tracker
    (native/uffd_tracker.cpp) — O(dirty) like the segv mode but faults
    are resolved by a dedicated event thread instead of a process-wide
    signal handler (the reference's uffd-thread-wp mode). None when the
    kernel lacks uffd-wp or the native build fails."""
    def install(lib: ctypes.CDLL) -> bool:
        rc = lib.uffd_install()
        if rc != 0:
            logger.info("userfaultfd write-protect unavailable (rc=%d); "
                        "DIRTY_TRACKING_MODE=uffd falls back", rc)
            return False
        return True

    return _load_native("uffd_tracker", "uffd_tracker.cpp",
                        "libuffdtracker.so", _declare_tracker("uffd"),
                        install=install, extra_args=("-lpthread",),
                        fail_note="uffd dirty mode unavailable")


def loaded_helpers() -> dict[str, bool]:
    """Which native helpers this process has tried, and whether each
    built and loaded (False: the caller is on its pure-Python path)."""
    with _lock:
        return {name: lib is not None for name, lib in _cache.items()}


def reset_for_tests() -> None:
    with _lock:
        # segv/uffd deliberately NOT reset: the SIGSEGV handler and the
        # uffd event thread are process-wide state that must not be
        # re-installed per test
        _cache.pop("pagediff", None)
        _cache.pop("shm_ring", None)
