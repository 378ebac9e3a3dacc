"""Native host-runtime kernels: build + ctypes binding.

Compiles native/nomad_native.cpp with g++ on first use (cached by source
content hash under native/build/), exposing:

  allocs_fit(capacity, used, demand) -> bool[N]
  score_fit(capacity, used, demand, spread=False) -> f32[N]
  ports_check(port_words, row, ports, freed) -> bool
  ports_set(port_words, row, ports, value)
  scatter_add(used, rows, deltas)
  scatter_add_rank1(used, rows, counts, demand)
  validate_plan(...) -> bool[G]     (the EvaluatePool equivalent)
  expand_pairs(rows, counts, scores) -> (i32[K], f32[K])
  format_uuids(n) -> list[str]      (batch generate_uuid)

A missing or unbuildable library is an ERROR (`NativeBuildError`, carrying
the compiler's stderr), never a quiet switch of implementation:
`NATIVE_AVAILABLE` is true once the library is loaded.  The numpy twins
below stay as the oracles tests compare the C++ against, and as the
per-call path once the circuit breaker has opened on repeated faults —
every fault and the trip itself are logged.
"""
from __future__ import annotations

import ctypes
import functools
import hashlib
import logging
import os
import subprocess
import threading
from typing import List, Optional, Sequence, Tuple

import numpy as np

from nomad_tpu import chaos, knobs, tracing

log = logging.getLogger("nomad_tpu.native")

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.normpath(os.path.join(_HERE, "..", "..", "native",
                                     "nomad_native.cpp"))
_BUILD_DIR = os.path.join(os.path.dirname(_SRC), "build")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
NATIVE_AVAILABLE = False

_f32p = np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS")
_u32p = np.ctypeslib.ndpointer(np.uint32, flags="C_CONTIGUOUS")
_i32p = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")
_u8p = np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")


class NativeBuildError(RuntimeError):
    """The native library could not be built or loaded."""


def _build(build_dir: str = _BUILD_DIR) -> str:
    """Compile the native library, cached by source *content hash* (an
    mtime check could silently prefer a stale or foreign-toolchain binary
    after a checkout).  NOMAD_TPU_NATIVE_LIB overrides with a prebuilt
    .so (the sanitizer CI leg points this at an ASan/UBSan build).
    Processes that start together on an empty `build_dir` (xdist
    workers on a fresh checkout) each compile to a temporary name of
    their own and `os.replace` it into place; whoever finds the library
    already there returns it.
    Raises NativeBuildError with the compiler's output on failure."""
    override = knobs.get_str("NOMAD_TPU_NATIVE_LIB")
    if override:
        if not os.path.exists(override):
            raise NativeBuildError(
                f"NOMAD_TPU_NATIVE_LIB={override}: no such file")
        return override
    if not os.path.exists(_SRC):
        raise NativeBuildError(f"native source missing: {_SRC}")
    os.makedirs(build_dir, exist_ok=True)
    with open(_SRC, "rb") as fh:
        digest = hashlib.sha256(fh.read()).hexdigest()[:16]
    lib_path = os.path.join(build_dir, f"libnomad_native-{digest}.so")
    if os.path.exists(lib_path):
        return lib_path
    tmp = f"{lib_path}.{os.getpid()}.{os.urandom(4).hex()}.tmp"
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", "-o", tmp, _SRC]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, lib_path)
    except subprocess.CalledProcessError as e:
        raise NativeBuildError(
            f"{' '.join(cmd)} exited {e.returncode}:\n"
            f"{e.stderr.decode(errors='replace')}") from e
    except (OSError, subprocess.TimeoutExpired) as e:
        raise NativeBuildError(f"{' '.join(cmd)}: {e}") from e
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)
    # prune superseded digests so the build dir doesn't grow unboundedly
    for name in os.listdir(build_dir):
        if name.startswith("libnomad_native") and name.endswith(".so") \
                and name != os.path.basename(lib_path):
            try:
                os.remove(os.path.join(build_dir, name))
            except OSError:
                pass
    return lib_path


def _load() -> ctypes.CDLL:
    """The loaded library; builds it on first use.  Raises
    NativeBuildError when it cannot be built or opened."""
    global _lib, NATIVE_AVAILABLE
    with _lock:
        if _lib is not None:
            return _lib
        path = _build()
        try:
            lib = ctypes.CDLL(path)
        except OSError as e:
            raise NativeBuildError(f"cannot load {path}: {e}") from e
        lib.nomad_native_abi_version.restype = ctypes.c_int32
        got = lib.nomad_native_abi_version()
        if got != 2:
            # a wrong-ABI library silently misreading argument layouts is
            # far worse than no library: fail loudly, never fall back
            raise RuntimeError(
                f"nomad_native ABI mismatch: {path} reports version "
                f"{got}, bindings require 2 — rebuild the library "
                f"(delete {_BUILD_DIR}) or fix NOMAD_TPU_NATIVE_LIB")
        lib.allocs_fit_dense.restype = None
        lib.allocs_fit_dense.argtypes = [
            _f32p, _f32p, _f32p, ctypes.c_int, ctypes.c_int, _u8p]
        lib.score_fit_dense.restype = None
        lib.score_fit_dense.argtypes = [
            _f32p, _f32p, _f32p, ctypes.c_int, ctypes.c_int,
            ctypes.c_int, _f32p]
        lib.ports_check.restype = ctypes.c_int32
        lib.ports_check.argtypes = [
            _u32p, ctypes.c_int, ctypes.c_int, _i32p, ctypes.c_int,
            _i32p, ctypes.c_int]
        lib.ports_set.restype = None
        lib.ports_set.argtypes = [
            _u32p, ctypes.c_int, ctypes.c_int, _i32p, ctypes.c_int,
            ctypes.c_int]
        lib.scatter_add.restype = None
        lib.scatter_add.argtypes = [
            _f32p, ctypes.c_int, _i32p, _f32p, ctypes.c_int]
        lib.validate_plan.restype = None
        lib.validate_plan.argtypes = [
            _f32p, _f32p, _u32p, ctypes.c_int, ctypes.c_int,
            _i32p, _f32p, _f32p, _i32p, _i32p, _i32p, _i32p,
            ctypes.c_int, _u8p]
        lib.expand_pairs.restype = ctypes.c_int32
        lib.expand_pairs.argtypes = [
            _i32p, _i32p, _f32p, ctypes.c_int, _i32p, _f32p,
            ctypes.c_int32]
        lib.format_uuids.restype = None
        lib.format_uuids.argtypes = [
            _u8p, ctypes.c_int, ctypes.c_char_p]
        lib.scatter_add_rank1.restype = None
        lib.scatter_add_rank1.argtypes = [
            _f32p, ctypes.c_int, _i32p, _i32p, _f32p, ctypes.c_int]
        _lib = lib
        NATIVE_AVAILABLE = True
        return lib


class CircuitBreaker:
    """Trips to the numpy twins after `threshold` consecutive native
    call faults — a library that faults on every call costs an exception
    (or a crash risk) per call, and one trip beats that.  Nothing here is
    quiet: each fault is logged with its traceback and the trip is an
    error record; `open` stays readable for health checks.  `reset()`
    closes the circuit again (e.g. after a rebuild)."""

    def __init__(self, threshold: int = 3):
        self.threshold = max(1, int(threshold))
        self._lock = threading.Lock()
        self._consecutive = 0
        self.open = False
        self.stats = {"failures": 0, "trips": 0}

    def record_ok(self) -> None:
        if self._consecutive:
            with self._lock:
                self._consecutive = 0

    def record_failure(self) -> None:
        """Called from the `except` block of a faulted native call."""
        log.warning("native call faulted; this call runs its numpy twin",
                    exc_info=True)
        with self._lock:
            self._consecutive += 1
            self.stats["failures"] += 1
            tripped = not self.open and self._consecutive >= self.threshold
            if tripped:
                self.open = True
                self.stats["trips"] += 1
        if tripped:
            log.error("native circuit breaker OPEN after %d consecutive "
                      "faults: every native call now runs its numpy twin "
                      "until reset()", self.threshold)

    def reset(self) -> None:
        with self._lock:
            self._consecutive = 0
            self.open = False


breaker = CircuitBreaker(knobs.get_int("NOMAD_TPU_NATIVE_BREAKER"))


def _native_lib() -> Optional[ctypes.CDLL]:
    """The library iff the circuit is closed; every native call site goes
    through here so an open breaker routes everything to Python.  A
    library that cannot be built raises (see _load) — None means only
    "breaker open"."""
    if breaker.open:
        return None
    return _load()


_EMPTY_I32 = np.zeros(0, np.int32)


def _spanned(fn):
    """Every call of the wrapper is the span `native.<fn>` (the C++
    call, its argument marshalling and, with the breaker open, the numpy
    twin)."""
    name = "native." + fn.__name__

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        with tracing.span(name):
            return fn(*args, **kwargs)
    return wrapper


@_spanned
def allocs_fit(capacity: np.ndarray, used: np.ndarray,
               demand: np.ndarray) -> np.ndarray:
    """bool[N]: demand fits in capacity-used per row
    (structs.AllocsFit over the node axis)."""
    capacity = np.ascontiguousarray(capacity, np.float32)
    used = np.ascontiguousarray(used, np.float32)
    demand = np.ascontiguousarray(demand, np.float32)
    lib = _native_lib()
    if lib is not None:
        try:
            if chaos.active is not None:
                chaos.fire("native.fail")
            out = np.empty(capacity.shape[0], np.uint8)
            lib.allocs_fit_dense(capacity, used, demand,
                                 capacity.shape[0], capacity.shape[1], out)
            breaker.record_ok()
            return out.astype(bool)
        except Exception:                          # noqa: BLE001
            breaker.record_failure()
    return np.all(used + demand <= capacity + 1e-6, axis=1)


@_spanned
def score_fit(capacity: np.ndarray, used: np.ndarray,
              demand: np.ndarray, spread: bool = False) -> np.ndarray:
    """f32[N] binpack/spread score (structs.ScoreFitBinPack/Spread)."""
    capacity = np.ascontiguousarray(capacity, np.float32)
    used = np.ascontiguousarray(used, np.float32)
    demand = np.ascontiguousarray(demand, np.float32)
    lib = _native_lib()
    if lib is not None:
        try:
            if chaos.active is not None:
                chaos.fire("native.fail")
            out = np.empty(capacity.shape[0], np.float32)
            lib.score_fit_dense(capacity, used, demand, capacity.shape[0],
                                capacity.shape[1], int(spread), out)
            breaker.record_ok()
            return out
        except Exception:                          # noqa: BLE001
            breaker.record_failure()
    cap = np.maximum(capacity[:, :2], 1e-9)
    free = np.clip((cap - (used[:, :2] + demand[:2])) / cap, 0.0, 1.0)
    exp = 1.0 - free if spread else free
    total = np.power(10.0, exp).sum(axis=1)
    total = np.where((capacity[:, :2] <= 0).any(axis=1), 40.0, total)
    return np.clip((20.0 - total) / 18.0, 0.0, 1.0).astype(np.float32)


@_spanned
def ports_check(port_words: np.ndarray, row: int,
                ports: Sequence[int],
                freed: Sequence[int] = ()) -> bool:
    """All `ports` free on `row` (ports in `freed` count as free)?"""
    ports_a = np.asarray(list(ports), np.int32)
    freed_a = np.asarray(list(freed), np.int32)
    lib = _native_lib()
    if lib is not None:
        try:
            if chaos.active is not None:
                chaos.fire("native.fail")
            pw = np.ascontiguousarray(port_words, np.uint32)
            ok = bool(lib.ports_check(pw, pw.shape[1], row,
                                      ports_a, len(ports_a),
                                      freed_a, len(freed_a)))
            breaker.record_ok()
            return ok
        except Exception:                          # noqa: BLE001
            breaker.record_failure()
    seen = set()
    for p in ports_a:
        p = int(p)
        if p in seen:
            return False
        seen.add(p)
        if p < 0 or (p >> 5) >= port_words.shape[1]:
            return False
        if (port_words[row, p >> 5] >> np.uint32(p & 31)) & 1:
            if p not in set(int(x) for x in freed_a):
                return False
    return True


@_spanned
def ports_set(port_words: np.ndarray, row: int,
              ports: Sequence[int], value: bool) -> None:
    ports_a = np.asarray(list(ports), np.int32)
    lib = _native_lib()
    if lib is not None and port_words.flags["C_CONTIGUOUS"]:
        # per-port bit sets are idempotent, so retrying the whole batch in
        # Python after a mid-call native failure is safe
        try:
            if chaos.active is not None:
                chaos.fire("native.fail")
            lib.ports_set(port_words, port_words.shape[1], row,
                          ports_a, len(ports_a), int(value))
            breaker.record_ok()
            return
        except Exception:                          # noqa: BLE001
            breaker.record_failure()
    for p in ports_a:
        p = int(p)
        if p < 0 or (p >> 5) >= port_words.shape[1]:
            continue
        if value:
            port_words[row, p >> 5] |= np.uint32(1 << (p & 31))
        else:
            port_words[row, p >> 5] &= ~np.uint32(1 << (p & 31))


@_spanned
def scatter_add(used: np.ndarray, rows: Sequence[int],
                deltas: np.ndarray) -> None:
    """used[rows[k]] += deltas[k] in place."""
    rows_a = np.asarray(list(rows), np.int32)
    deltas = np.ascontiguousarray(deltas, np.float32)
    lib = _native_lib()
    if lib is not None and used.flags["C_CONTIGUOUS"]:
        # += is not idempotent, so failures must surface before the native
        # call touches `used`: ctypes argtype errors and injected faults
        # both raise pre-entry
        try:
            if chaos.active is not None:
                chaos.fire("native.fail")
            lib.scatter_add(used, used.shape[1], rows_a, deltas,
                            len(rows_a))
            breaker.record_ok()
            return
        except Exception:                          # noqa: BLE001
            breaker.record_failure()
    np.add.at(used, rows_a, deltas)


@_spanned
def validate_plan(capacity: np.ndarray, used: np.ndarray,
                  port_words: np.ndarray,
                  rows: Sequence[int],
                  demand: np.ndarray, freed: np.ndarray,
                  group_ports: List[Sequence[int]],
                  group_freed_ports: List[Sequence[int]]) -> np.ndarray:
    """bool[G]: per placement-group validation (fit + ports), the
    EvaluatePool fan-out as one native call."""
    g = len(rows)
    rows_a = np.asarray(list(rows), np.int32)
    demand = np.ascontiguousarray(demand, np.float32)
    freed = np.ascontiguousarray(freed, np.float32)
    ports_off = np.zeros(g + 1, np.int32)
    freed_off = np.zeros(g + 1, np.int32)
    flat_ports: List[int] = []
    flat_freed: List[int] = []
    for i in range(g):
        flat_ports.extend(int(p) for p in group_ports[i])
        flat_freed.extend(int(p) for p in group_freed_ports[i])
        ports_off[i + 1] = len(flat_ports)
        freed_off[i + 1] = len(flat_freed)
    ports_a = np.asarray(flat_ports, np.int32) if flat_ports else _EMPTY_I32
    freed_a = np.asarray(flat_freed, np.int32) if flat_freed else _EMPTY_I32
    lib = _native_lib()
    if lib is not None:
        try:
            if chaos.active is not None:
                chaos.fire("native.fail")
            cap_c = np.ascontiguousarray(capacity, np.float32)
            used_c = np.ascontiguousarray(used, np.float32)
            pw_c = np.ascontiguousarray(port_words, np.uint32)
            out = np.empty(g, np.uint8)
            lib.validate_plan(cap_c, used_c, pw_c, pw_c.shape[1],
                              cap_c.shape[1], rows_a, demand, freed,
                              ports_a, ports_off, freed_a, freed_off, g,
                              out)
            breaker.record_ok()
            return out.astype(bool)
        except Exception:                          # noqa: BLE001
            breaker.record_failure()
    out = np.zeros(g, bool)
    for i in range(g):
        r = int(rows_a[i])
        if r < 0:
            continue
        fits = np.all(used[r] + demand[i] - freed[i]
                      <= capacity[r] + 1e-6)
        out[i] = fits and ports_check(
            port_words, r, group_ports[i], group_freed_ports[i])
    return out


@_spanned
def expand_pairs(rows: np.ndarray, counts: np.ndarray,
                 scores: Optional[np.ndarray] = None
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """Flatten resolved sparse bulk output — (row, count, score)
    triples — into per-alloc (rows i32[K], scores f32[K]) arrays in
    placement order; K = counts.clip(0).sum().  The bulk materializer's
    one-call-per-dispatch expansion."""
    rows_a = np.ascontiguousarray(rows, np.int32)
    counts_a = np.ascontiguousarray(counts, np.int32)
    if scores is None:
        scores_a = np.zeros(rows_a.shape[0], np.float32)
    else:
        scores_a = np.ascontiguousarray(scores, np.float32)
    total = int(np.clip(counts_a, 0, None).sum())
    lib = _native_lib()
    if lib is not None and total > 0:
        try:
            if chaos.active is not None:
                chaos.fire("native.fail")
            out_rows = np.empty(total, np.int32)
            out_scores = np.empty(total, np.float32)
            w = lib.expand_pairs(rows_a, counts_a, scores_a,
                                 rows_a.shape[0], out_rows, out_scores,
                                 total)
            breaker.record_ok()
            if w == total:              # defensive; cap == exact total
                return out_rows, out_scores
        except Exception:                          # noqa: BLE001
            breaker.record_failure()
    keep = counts_a > 0
    return (np.repeat(rows_a[keep], counts_a[keep]),
            np.repeat(scores_a[keep], counts_a[keep]))


@_spanned
def format_uuids(n: int) -> List[str]:
    """n fresh uuid strings in one call, byte-identical in format to
    utils.generate_uuid (hex of os.urandom(16), 8-4-4-4-12)."""
    if n <= 0:
        return []
    rnd = np.frombuffer(os.urandom(16 * n), np.uint8)
    lib = _native_lib()
    if lib is not None:
        try:
            if chaos.active is not None:
                chaos.fire("native.fail")
            out = ctypes.create_string_buffer(36 * n)
            lib.format_uuids(np.ascontiguousarray(rnd), n, out)
            raw = out.raw
            breaker.record_ok()
            return [raw[i * 36:(i + 1) * 36].decode("ascii")
                    for i in range(n)]
        except Exception:                          # noqa: BLE001
            breaker.record_failure()
    h = rnd.tobytes().hex()
    return [f"{s[:8]}-{s[8:12]}-{s[12:16]}-{s[16:20]}-{s[20:]}"
            for s in (h[i * 32:(i + 1) * 32] for i in range(n))]


@_spanned
def scatter_add_rank1(used: np.ndarray, rows: np.ndarray,
                      counts: np.ndarray, demand: np.ndarray) -> None:
    """used[rows[k]] += counts[k] * demand in place, without building
    the [K, dims] delta matrix."""
    rows_a = np.ascontiguousarray(rows, np.int32)
    counts_a = np.ascontiguousarray(counts, np.int32)
    demand_a = np.ascontiguousarray(demand, np.float32)
    lib = _native_lib()
    if lib is not None and used.flags["C_CONTIGUOUS"] \
            and used.dtype == np.float32:
        try:
            if chaos.active is not None:
                chaos.fire("native.fail")
            lib.scatter_add_rank1(used, used.shape[1], rows_a, counts_a,
                                  demand_a, rows_a.shape[0])
            breaker.record_ok()
            return
        except Exception:                          # noqa: BLE001
            breaker.record_failure()
    np.add.at(used, rows_a,
              counts_a[:, None].astype(used.dtype) * demand_a)
