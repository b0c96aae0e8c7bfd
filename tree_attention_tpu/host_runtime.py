"""ctypes bindings for the native host runtime (``tree_attention_tpu/native/treeattn_host.cc``).

The reference gets its host-side native capability for free from libtorch:
ATen's Philox RNG (``/root/reference/model.py:50``) and multiprocessing's
fork/exec layer (``model.py:165``). This module binds the framework's own C++
equivalents — counter-based RNG fills, a prefetching batch pipeline, and a
local process launcher — compiling the shared library on first use (g++ is
part of the toolchain; there is no pybind11 in this image, hence ctypes).

Everything degrades gracefully: if the compiler or library is unavailable,
:func:`philox_tokens` / :class:`HostDataPipeline` fall back to NumPy's own
Philox implementation (same counter-based construction, different stream),
and :func:`launch_local` falls back to ``subprocess``. The contract is
"deterministic in (seed, index) within a backend", not cross-backend
bit-equality — synthetic data needs no more.
"""

from __future__ import annotations

import ctypes
import os
import shutil
import signal
import subprocess
import tempfile
import time
import threading
from typing import List, Mapping, Optional, Sequence, Tuple

import numpy as np

from tree_attention_tpu import obs
from tree_attention_tpu.utils.logging import get_logger

log = get_logger("host_runtime")

# Launcher/watchdog observability (all host-side, execution-true — nothing
# here is ever traced by JAX). Exit statuses are classified with the same
# conventions the supervisor reports: 124 deadline, 125 heartbeat stall,
# 128+sig crash-by-signal.
_HEARTBEATS = obs.counter(
    "heartbeat_ticks_total",
    "host-visible progress marks (one per train step / fenced timing "
    "iteration)",
)
_GANG_LAUNCHES = obs.counter(
    "gang_launches_total", "launch_local invocations"
)
_GANG_ATTEMPTS = obs.counter(
    "gang_attempts_total",
    "gang launch attempts, including elastic relaunches",
)
_RANK_EXITS = obs.counter(
    "rank_exits_total",
    "child rank exits by outcome (ok / crash / deadline / stall)",
    labels=("outcome",),
)
_WATCHDOG_STALLS = obs.counter(
    "watchdog_stalls_total",
    "heartbeat watchdog firings (whole-gang kills, status 125)",
)


def _rank_exit_outcome(status: int) -> str:
    if status == 0:
        return "ok"
    if status == 124:
        return "deadline"
    if status == 125:
        return "stall"
    return "crash"


def _account_gang_result(statuses: Sequence[int]) -> None:
    if obs.REGISTRY.enabled:
        for s in statuses:
            _RANK_EXITS.labels(outcome=_rank_exit_outcome(s)).inc()
        if any(s == 125 for s in statuses):
            _WATCHDOG_STALLS.inc()
    if obs.TRACER.active and any(s == 125 for s in statuses):
        # Own guard: a tracer-only run used to lose the stall instant to
        # the registry early-return above.
        obs.instant("watchdog_stall", cat="launcher",
                    args={"statuses": list(statuses)})

# The native sources ship inside the package (``tree_attention_tpu/native``
# is package data, pyproject ``[tool.setuptools.package-data]``) so an
# installed wheel can build the runtime on first use, same as a source
# checkout. When the install location is read-only (system site-packages),
# the build lands in ``~/.cache/tree-attention-tpu`` instead. Either way the
# build directory is keyed by the SOURCE's content hash.
_NATIVE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "native")
_SRC_PATH = os.path.join(_NATIVE_DIR, "treeattn_host.cc")


def _build_dir() -> str:
    """Where this source's library is built: a directory named by the
    source's content hash, so a library is only ever loaded if it was built
    from exactly this ``treeattn_host.cc``. An mtime says nothing after a
    copy or a checkout, and ctypes would bind today's prototypes to a stale
    or foreign ``.so``; two installs with different sources never share
    one either."""
    import hashlib

    try:
        with open(_SRC_PATH, "rb") as f:
            key = hashlib.sha256(f.read()).hexdigest()[:12]
    except OSError:
        key = "unknown"
    base = (
        os.path.join(_NATIVE_DIR, "build")
        if os.access(_NATIVE_DIR, os.W_OK)
        else os.path.join(
            os.path.expanduser("~"), ".cache", "tree-attention-tpu"
        )
    )
    return os.path.join(base, key)


def _so_path() -> str:
    return os.path.join(_build_dir(), "libtreeattn_host.so")


_lib_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_lib_tried = False


def _compile() -> bool:
    try:
        proc = subprocess.run(
            ["make", "-C", _NATIVE_DIR, "BUILD=" + _build_dir()],
            capture_output=True, text=True, timeout=120,
        )
        if proc.returncode != 0:
            log.warning("native build failed:\n%s", proc.stderr[-2000:])
            return False
        return True
    except (OSError, subprocess.TimeoutExpired) as e:
        log.warning("native build unavailable: %s", e)
        return False


def load_native() -> Optional[ctypes.CDLL]:
    """Load (building if needed) the native library; None if unavailable."""
    global _lib, _lib_tried
    with _lib_lock:
        if _lib is not None or _lib_tried:
            return _lib
        _lib_tried = True
        so = _so_path()
        if not os.path.exists(so) and not _compile():
            return None
        try:
            lib = ctypes.CDLL(so)
        except OSError as e:
            log.warning("native library load failed: %s", e)
            return None
        lib.ta_fill_u32.argtypes = [
            ctypes.POINTER(ctypes.c_uint32), ctypes.c_size_t,
            ctypes.c_uint64, ctypes.c_uint64,
        ]
        lib.ta_fill_normal_f32.argtypes = [
            ctypes.POINTER(ctypes.c_float), ctypes.c_size_t,
            ctypes.c_uint64, ctypes.c_uint64,
        ]
        lib.ta_fill_tokens_i32.argtypes = [
            ctypes.POINTER(ctypes.c_int32), ctypes.c_size_t, ctypes.c_uint32,
            ctypes.c_uint64, ctypes.c_uint64,
        ]
        lib.ta_pipeline_create.restype = ctypes.c_void_p
        lib.ta_pipeline_create.argtypes = [
            ctypes.c_size_t, ctypes.c_uint32, ctypes.c_uint64,
            ctypes.c_int, ctypes.c_int, ctypes.c_uint64,
        ]
        lib.ta_pipeline_next.restype = ctypes.c_int64
        lib.ta_pipeline_next.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_int32),
        ]
        lib.ta_pipeline_destroy.argtypes = [ctypes.c_void_p]
        lib.ta_launch_processes.restype = ctypes.c_int
        lib.ta_launch_processes.argtypes = [
            ctypes.POINTER(ctypes.c_char_p), ctypes.c_int,
            ctypes.POINTER(ctypes.c_int),
        ]
        lib.ta_launch_processes_supervised.restype = ctypes.c_int
        lib.ta_launch_processes_supervised.argtypes = [
            ctypes.POINTER(ctypes.c_char_p), ctypes.c_int,
            ctypes.c_int, ctypes.c_int,
            ctypes.POINTER(ctypes.c_int),
        ]
        if hasattr(lib, "ta_launch_processes_watched"):
            lib.ta_launch_processes_watched.restype = ctypes.c_int
            lib.ta_launch_processes_watched.argtypes = [
                ctypes.POINTER(ctypes.c_char_p), ctypes.c_int,
                ctypes.c_int, ctypes.c_int,
                ctypes.c_char_p, ctypes.c_int,
                ctypes.POINTER(ctypes.c_int),
            ]
        if hasattr(lib, "ta_launch_processes_elastic"):
            lib.ta_launch_processes_elastic.restype = ctypes.c_int
            lib.ta_launch_processes_elastic.argtypes = [
                ctypes.POINTER(ctypes.c_char_p), ctypes.c_int,
                ctypes.c_int, ctypes.c_int,
                ctypes.c_char_p, ctypes.c_int,
                ctypes.c_int,
                ctypes.POINTER(ctypes.c_int),
                ctypes.POINTER(ctypes.c_int),
            ]
        if hasattr(lib, "ta_corpus_open"):
            lib.ta_corpus_open.restype = ctypes.c_void_p
            lib.ta_corpus_open.argtypes = [ctypes.c_char_p, ctypes.c_int]
            lib.ta_corpus_len.restype = ctypes.c_int64
            lib.ta_corpus_len.argtypes = [ctypes.c_void_p]
            lib.ta_corpus_close.argtypes = [ctypes.c_void_p]
            lib.ta_corpus_fill_batch.restype = ctypes.c_int
            lib.ta_corpus_fill_batch.argtypes = [
                ctypes.c_void_p, ctypes.POINTER(ctypes.c_int32),
                ctypes.c_size_t, ctypes.c_size_t,
                ctypes.c_uint64, ctypes.c_uint64,
            ]
            lib.ta_pipeline_create_corpus.restype = ctypes.c_void_p
            lib.ta_pipeline_create_corpus.argtypes = [
                ctypes.c_void_p, ctypes.c_size_t, ctypes.c_size_t,
                ctypes.c_uint64, ctypes.c_int, ctypes.c_int, ctypes.c_uint64,
            ]
        _lib = lib
        log.info("native host runtime loaded: %s", so)
        return _lib


def native_available() -> bool:
    return load_native() is not None


# ---------------------------------------------------------------------------
# RNG fills
# ---------------------------------------------------------------------------


def philox_normal(shape: Sequence[int], seed: int, stream: int = 0) -> np.ndarray:
    """Standard normals, deterministic in (seed, stream)."""
    n = int(np.prod(shape))
    lib = load_native()
    if lib is not None:
        out = np.empty(n, np.float32)
        lib.ta_fill_normal_f32(
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            n, seed & (2**64 - 1), stream & (2**64 - 1),
        )
        return out.reshape(shape)
    gen = np.random.Generator(np.random.Philox(key=seed, counter=[0, 0, 0, stream]))
    return gen.standard_normal(n, dtype=np.float32).reshape(shape)


def philox_tokens(
    shape: Sequence[int], vocab: int, seed: int, stream: int = 0
) -> np.ndarray:
    """Token ids in [0, vocab), deterministic in (seed, stream)."""
    n = int(np.prod(shape))
    lib = load_native()
    if lib is not None:
        out = np.empty(n, np.int32)
        lib.ta_fill_tokens_i32(
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            n, vocab, seed & (2**64 - 1), stream & (2**64 - 1),
        )
        return out.reshape(shape)
    gen = np.random.Generator(np.random.Philox(key=seed, counter=[0, 0, 0, stream]))
    return gen.integers(0, vocab, size=n, dtype=np.int32).reshape(shape)


# ---------------------------------------------------------------------------
# Prefetching batch pipeline
# ---------------------------------------------------------------------------


class _PipelineBase:
    """Shared native-handle lifecycle for the prefetching pipelines.

    Subclasses set ``self._handle`` (or leave it None for the pure-python
    fallback), ``self._elems`` and ``self._out_shape`` before returning from
    ``__init__``, and implement ``_fallback_batch(idx)``. Delivery, the
    stopped-pipeline error path, close, and context-manager/``__del__``
    safety live here once.
    """

    _handle = None  # class default: __del__ is safe pre-__init__
    _fallback_idx = 0

    def next(self) -> np.ndarray:
        if self._handle:
            out = np.empty(self._elems, np.int32)
            idx = self._lib.ta_pipeline_next(
                self._handle, out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))
            )
            if idx < 0:
                raise RuntimeError("pipeline stopped")
            return out.reshape(self._out_shape)
        idx = self._fallback_idx
        self._fallback_idx += 1
        return self._fallback_batch(idx)

    def close(self) -> None:
        if self._handle:
            self._lib.ta_pipeline_destroy(self._handle)
            self._handle = None

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def __del__(self):
        self.close()


class HostDataPipeline(_PipelineBase):
    """Prefetching token-batch source: C++ worker threads fill ahead.

    Batch ``i`` always has the content of ``philox_tokens(shape, vocab,
    seed, stream=i)`` (native stream) regardless of worker count or timing;
    only the prefetch overlap is concurrent, never the content.

    Use as a context manager::

        with HostDataPipeline((B, T), vocab, seed) as pipe:
            for _ in range(steps):
                batch = pipe.next()          # np.int32 (B, T)
    """

    def __init__(
        self,
        batch_shape: Sequence[int],
        vocab: int,
        seed: int,
        *,
        depth: int = 4,
        workers: int = 2,
        start: int = 0,
    ):
        self.batch_shape = tuple(int(s) for s in batch_shape)
        self.vocab = int(vocab)
        self.seed = int(seed)
        self._elems = int(np.prod(self.batch_shape))
        self._out_shape = self.batch_shape
        if self._elems <= 0 or self.vocab <= 0:
            raise ValueError(
                f"bad pipeline config: shape={batch_shape} vocab={vocab}"
            )
        if start < 0:
            raise ValueError(f"start must be >= 0, got {start}")
        self._lib = load_native()
        self._fallback_idx = start
        if self._lib is not None:
            self._handle = self._lib.ta_pipeline_create(
                self._elems, self.vocab, self.seed & (2**64 - 1),
                int(depth), int(workers), int(start),
            )
            if not self._handle:
                raise RuntimeError("ta_pipeline_create failed")

    def _fallback_batch(self, idx: int) -> np.ndarray:
        return philox_tokens(self.batch_shape, self.vocab, self.seed, idx)


# ---------------------------------------------------------------------------
# Memory-mapped token corpus
# ---------------------------------------------------------------------------

_CORPUS_DTYPES = {"int32": (4, np.dtype("<i4")), "uint16": (2, np.dtype("<u2"))}


def _philox4x32(seed: int, ctr_hi: int, ctr_lo: int):
    """Pure-python Philox4x32-10 block, bit-identical to the native one —
    the fallback corpus sampler must pick the same offsets the C++ workers
    would, so native and fallback deliver identical batches."""
    M0, M1 = 0xD2511F53, 0xCD9E8D57
    k0, k1 = seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF
    c = [ctr_lo & 0xFFFFFFFF, (ctr_lo >> 32) & 0xFFFFFFFF,
         ctr_hi & 0xFFFFFFFF, (ctr_hi >> 32) & 0xFFFFFFFF]
    for _ in range(10):
        p0, p1 = M0 * c[0], M1 * c[2]
        c = [((p1 >> 32) ^ c[1] ^ k0) & 0xFFFFFFFF, p1 & 0xFFFFFFFF,
             ((p0 >> 32) ^ c[3] ^ k1) & 0xFFFFFFFF, p0 & 0xFFFFFFFF]
        k0 = (k0 + 0x9E3779B9) & 0xFFFFFFFF
        k1 = (k1 + 0xBB67AE85) & 0xFFFFFFFF
    return c


class TokenCorpus:
    """A flat on-disk array of token ids, memory-mapped (zero-copy reads).

    The native handle mmaps via C++ (``ta_corpus_open``); without the native
    library a ``np.memmap`` serves the same windows with the same
    (bit-identical) Philox offsets. ``fill_batch`` returns ``(rows,
    seqlen+1)`` int32 windows — input and next-token target share the
    buffer. Batch content is a pure function of ``(seed, batch_idx)``.
    """

    def __init__(self, path: str, dtype: str = "int32"):
        self._handle = None
        self._mm = None
        if dtype not in _CORPUS_DTYPES:
            raise ValueError(
                f"dtype must be one of {sorted(_CORPUS_DTYPES)}, got {dtype!r}"
            )
        code, np_dtype = _CORPUS_DTYPES[dtype]
        self.path = path
        self.dtype = dtype
        self._lib = load_native()
        if self._lib is not None and hasattr(self._lib, "ta_corpus_open"):
            self._handle = self._lib.ta_corpus_open(path.encode(), code)
            if not self._handle:
                raise OSError(f"cannot open corpus {path!r} (dtype {dtype})")
            self.n_tokens = int(self._lib.ta_corpus_len(self._handle))
        else:
            self._mm = np.memmap(path, dtype=np_dtype, mode="r")
            self.n_tokens = int(self._mm.shape[0])

    def __len__(self) -> int:
        return self.n_tokens

    def fill_batch(
        self, rows: int, seqlen: int, seed: int, batch_idx: int
    ) -> np.ndarray:
        window = seqlen + 1
        if self.n_tokens < window:
            raise ValueError(
                f"corpus has {self.n_tokens} tokens < one {window}-token window"
            )
        if self._handle:
            out = np.empty(rows * window, np.int32)
            rc = self._lib.ta_corpus_fill_batch(
                self._handle,
                out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
                rows, seqlen, seed & (2**64 - 1), batch_idx & (2**64 - 1),
            )
            if rc != 0:
                raise RuntimeError("ta_corpus_fill_batch failed")
            return out.reshape(rows, window)
        span = self.n_tokens - window + 1
        out = np.empty((rows, window), np.int32)
        for r in range(rows):
            blk = _philox4x32(seed, batch_idx, r)
            off = ((blk[0] << 32) | blk[1]) % span
            out[r] = self._mm[off:off + window].astype(np.int32)
        return out

    def close(self) -> None:
        if self._handle:
            self._lib.ta_corpus_close(self._handle)
            self._handle = None
        self._mm = None

    def __enter__(self) -> "TokenCorpus":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def __del__(self):
        self.close()


class HostCorpusPipeline(_PipelineBase):
    """Prefetching corpus-batch source: the corpus analogue of
    :class:`HostDataPipeline` (same ordered-window machinery, same
    resume-at-``start`` contract). The corpus must stay open for the
    pipeline's lifetime."""

    def __init__(
        self,
        corpus: TokenCorpus,
        batch: int,
        seq_len: int,
        seed: int,
        *,
        depth: int = 4,
        workers: int = 2,
        start: int = 0,
    ):
        if batch < 1 or seq_len < 1:
            raise ValueError(f"bad pipeline config: batch={batch} seq_len={seq_len}")
        if start < 0:
            raise ValueError(f"start must be >= 0, got {start}")
        self.corpus = corpus
        self.batch = int(batch)
        self.seq_len = int(seq_len)
        self.seed = int(seed)
        self._elems = self.batch * (self.seq_len + 1)
        self._out_shape = (self.batch, self.seq_len + 1)
        self._fallback_idx = start
        self._lib = load_native()
        if (
            self._lib is not None
            and corpus._handle
            and hasattr(self._lib, "ta_pipeline_create_corpus")
        ):
            self._handle = self._lib.ta_pipeline_create_corpus(
                corpus._handle, self.batch, self.seq_len,
                self.seed & (2**64 - 1), int(depth), int(workers), int(start),
            )
            if not self._handle:
                raise RuntimeError("ta_pipeline_create_corpus failed")

    def _fallback_batch(self, idx: int) -> np.ndarray:
        return self.corpus.fill_batch(self.batch, self.seq_len, self.seed, idx)


# ---------------------------------------------------------------------------
# Local process launcher
# ---------------------------------------------------------------------------


def heartbeat() -> None:
    """Mark this rank as making progress (cheap; call once per train step).

    No-op unless the process was launched with heartbeat watching
    (``launch_local(heartbeat_stall=...)`` exports ``TA_HEARTBEAT_FILE``).
    Touching the file is the whole protocol: the supervisor compares its
    mtime against the stall window.
    """
    _HEARTBEATS.inc()  # one flag check when telemetry is off
    path = os.environ.get("TA_HEARTBEAT_FILE")
    if not path:
        return
    try:
        with open(path, "a"):
            os.utime(path, None)
    except OSError:
        pass  # never let observability kill the workload


def last_launch_attempts() -> int:
    """Attempts used by the most recent :func:`launch_local` in this process
    (1 = no restart was needed). Observability for the elastic path."""
    return _LAST_LAUNCH["attempts"]


_LAST_LAUNCH = {"attempts": 1}


_FAULT_SPEC_CACHE: dict = {}


def _fault_spec():
    """(step, rank) to die at, or None. Parsed from the environment once per
    process — this runs on the production per-step path, and a typo'd
    TA_FAULT_STEP must surface as one clear warning, not a ValueError
    traceback mid-train on every step (ADVICE r3)."""
    raw_step = os.environ.get("TA_FAULT_STEP")
    raw_rank = os.environ.get("TA_FAULT_RANK", "0")
    key = (raw_step, raw_rank)
    if key not in _FAULT_SPEC_CACHE:
        spec = None
        if raw_step is not None:
            try:
                spec = (int(raw_step), int(raw_rank))
            except ValueError:
                log.warning(
                    "fault injection disarmed: unparsable TA_FAULT_STEP=%r / "
                    "TA_FAULT_RANK=%r (expected integers)",
                    raw_step,
                    raw_rank,
                )
        _FAULT_SPEC_CACHE.clear()  # at most one armed spec per process
        _FAULT_SPEC_CACHE[key] = spec
    return _FAULT_SPEC_CACHE[key]


def maybe_inject_fault(step: int) -> None:
    """Fault injection for exercising the supervision/recovery machinery
    (SURVEY §5: the reference has no failure handling at all — a crashed
    rank hangs its peers' allreduce forever).

    Armed by environment, so production runs pay two getenvs and a dict
    lookup per step:

    - ``TA_FAULT_STEP`` (int): the step index at which to die; unset = off.
    - ``TA_FAULT_RANK`` (int, default 0): which rank dies.
    - ``TA_FAULT_ONCE_FILE`` (path, optional): the fault fires only if this
      file exists, and consumes (unlinks) it when it does — so a restarted
      gang does NOT re-crash. This turns an elastic-recovery test into a
      proof of *recovery* (resume + complete) rather than retry-until-luck.

    Dies via ``os._exit(86)`` — no atexit, no JAX teardown — the honest
    shape of a real crash. 86 is distinct from the supervisor's other
    statuses (124 deadline, 125 stall, 128+sig).
    """
    spec = _fault_spec()
    if spec is None or step != spec[0]:
        return
    rank = spec[1]
    try:
        my_rank = int(os.environ.get("JAX_PROCESS_INDEX", "0"))
    except ValueError:
        return  # non-numeric launcher rank: never crash the step loop
    if my_rank != rank:
        return
    once = os.environ.get("TA_FAULT_ONCE_FILE")
    if once:
        try:
            os.unlink(once)
        except FileNotFoundError:
            return  # already fired on a previous attempt
    log.error("fault injection: rank %d exiting at step %d", rank, step)
    if obs.TRACER.active:
        obs.instant("fault_injection", cat="launcher",
                    args={"rank": rank, "step": step})
    obs.TRACER.flush()  # os._exit skips atexit; don't lose the event
    os._exit(86)


def require_cpu_children(what: str, argv: Sequence[str],
                         env: Optional[Mapping[str, str]] = None) -> None:
    """Refuse to start several JAX child processes that are not held to
    the CPU platform.

    A TPU host's chips belong to one process at a time: N children that
    each take whatever JAX finds would each claim every chip, and all but
    the first fail or hang. The parent cannot look for a TPU itself without
    claiming it, so the rule is syntactic — the children's platform must be
    pinned by ``JAX_PLATFORMS=cpu`` in their environment or by ``--device
    cpu`` / ``--n-virtual-cpu N`` in their arguments. On a TPU, one process
    drives every chip (``--mesh seq=N``, or in-process replicas).
    """
    env = os.environ if env is None else env
    if env.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        return
    args = list(argv)
    for i, a in enumerate(args):
        flag, _, val = a.partition("=")
        if not val and i + 1 < len(args):
            val = args[i + 1]
        if (flag == "--device" and val == "cpu") or (
            flag == "--n-virtual-cpu" and val not in ("", "0")
        ):
            return
    raise RuntimeError(
        f"{what} starts several JAX processes on this host, and a TPU "
        "host's chips belong to one process at a time: children that are "
        "not pinned to the CPU would each claim every chip and fail or "
        "hang. Pin them (--device cpu, --n-virtual-cpu N, or "
        "JAX_PLATFORMS=cpu) for the emulated multi-process shape; on a "
        "TPU run ONE process over the chips (--mesh seq=N)."
    )


def launch_local(
    argv: Sequence[str],
    nprocs: int,
    *,
    timeout: Optional[float] = None,
    grace: float = 2.0,
    failfast: bool = True,
    heartbeat_stall: Optional[float] = None,
    restarts: int = 0,
) -> Tuple[int, List[int]]:
    """Run ``nprocs`` copies of ``argv``, each with ``JAX_PROCESS_INDEX`` /
    ``TA_NUM_PROCESSES`` exported; returns (failure_count, per-rank statuses).

    The reference's ``mp.spawn(main, nprocs=N)`` (``model.py:165``), as an
    exec-based launcher (no fork-inheriting a possibly-initialised JAX) with
    **fail-fast rank supervision**: the first rank to die non-zero gets its
    peers SIGTERMed (SIGKILL after ``grace`` seconds) instead of leaving them
    blocked forever in their next collective — the reference's failure mode
    (a crashed rank deadlocks the allreduce at ``model.py:108``). With
    ``timeout`` set, ranks still running at the deadline are killed and
    report status 124 (the ``timeout(1)`` convention). ``failfast=False``
    restores run-to-completion semantics (every rank's own exit status, no
    peer killing) — for workloads whose ranks are independent.

    ``heartbeat_stall`` (seconds) arms the hang watchdog — the failure the
    crash supervisor cannot see: every rank alive but wedged in a collective
    (SPMD deadlocks stall *all* ranks, so one stalled heartbeat is a
    reliable whole-job symptom). Each rank gets ``TA_HEARTBEAT_FILE``
    exported and should call :func:`heartbeat` as it makes progress (the
    CLI train loop does, once per step); a rank silent for longer than the
    window — counted from launch until its first beat, so size it for jit
    compile — gets the job killed, stalled ranks reporting status **125**
    (vs 124 deadline, 128+sig crash). Requires ``failfast``.

    ``restarts`` arms **elastic recovery**: after a failed attempt (rank
    crash, deadline, heartbeat stall) the whole gang is relaunched with the
    same argv, up to ``restarts`` additional attempts. Whole-gang restart is
    the right granularity for SPMD — a surviving rank is wedged in a
    collective the moment any peer dies, so there is nothing to rejoin. The
    workload must be *resumable*: restore its latest checkpoint on start
    (the CLI train mode's ``--resume`` contract), making a restart a resume
    rather than a redo. ``timeout`` is per attempt. Requires ``failfast``;
    :func:`last_launch_attempts` reports how many attempts the last call
    used. The reference has no recovery story at all — a crashed rank hangs
    its peers' allreduce forever (``model.py:108,163``).
    """
    if nprocs < 1:
        raise ValueError(f"nprocs must be >= 1, got {nprocs}")
    if not failfast and timeout:
        raise ValueError("timeout requires failfast=True")
    if restarts < 0:
        raise ValueError(f"restarts must be >= 0, got {restarts}")
    if restarts and not failfast:
        raise ValueError("restarts requires failfast=True")
    if heartbeat_stall is not None:
        if not failfast:
            raise ValueError("heartbeat_stall requires failfast=True")
        if heartbeat_stall <= 0:
            raise ValueError(
                f"heartbeat_stall must be > 0, got {heartbeat_stall}"
            )
    hb_dir = None
    if heartbeat_stall is not None:
        hb_dir = tempfile.mkdtemp(prefix="ta_hb_")
    _LAST_LAUNCH["attempts"] = 1
    _GANG_LAUNCHES.inc()
    try:
        with obs.span("launch_local", cat="launcher",
                      args=None if not obs.TRACER.active else
                      {"nprocs": nprocs, "restarts": restarts,
                       "watched": heartbeat_stall is not None}):
            failures, statuses = _launch_elastic(
                argv, nprocs, timeout, grace, failfast, heartbeat_stall,
                hb_dir, restarts,
            )
        if obs.REGISTRY.enabled:
            _GANG_ATTEMPTS.inc(_LAST_LAUNCH["attempts"])
            _account_gang_result(statuses)
        return failures, statuses
    finally:
        if hb_dir is not None:
            shutil.rmtree(hb_dir, ignore_errors=True)


def _native_launch_args(argv, nprocs, timeout, grace, heartbeat_stall):
    """ctypes marshalling shared by every native launch entry — one home,
    so conventions (timeout 0 = no deadline, ms floors) cannot diverge
    between the single-attempt and elastic paths."""
    c_argv = (ctypes.c_char_p * (len(argv) + 1))(
        *[a.encode() for a in argv], None
    )
    statuses = (ctypes.c_int * nprocs)()
    timeout_ms = 0 if not timeout else max(1, int(timeout * 1000))
    grace_ms = max(1, int(grace * 1000))
    hb_ms = (
        0 if heartbeat_stall is None else max(1, int(heartbeat_stall * 1000))
    )
    return c_argv, statuses, timeout_ms, grace_ms, hb_ms


def _launch_elastic(
    argv, nprocs, timeout, grace, failfast, heartbeat_stall, hb_dir, restarts
) -> Tuple[int, List[int]]:
    """Dispatch the (possibly restarted) gang launch.

    The native elastic entry runs the whole restart loop in C++; hosts
    without it (or the subprocess fallback) retry in Python around the
    single-attempt impl — same semantics, same per-attempt deadline.
    """
    lib = load_native()
    if (
        restarts
        and lib is not None
        and hasattr(lib, "ta_launch_processes_elastic")
    ):
        c_argv, statuses, timeout_ms, grace_ms, hb_ms = _native_launch_args(
            argv, nprocs, timeout, grace, heartbeat_stall
        )
        attempts = ctypes.c_int(1)
        failures = lib.ta_launch_processes_elastic(
            c_argv, nprocs, timeout_ms, grace_ms,
            hb_dir.encode() if hb_dir is not None else None,
            hb_ms, restarts, statuses, ctypes.byref(attempts),
        )
        if failures < 0:
            raise OSError("fork failed in the native launcher")
        # No summary log here: last_launch_attempts() is the API and the
        # CLI owns the one "recovered after N attempts" message, so native
        # and fallback paths log the same shape. (The fallback additionally
        # logs each failed attempt as it happens — per-attempt visibility
        # the C++ loop cannot provide.)
        _LAST_LAUNCH["attempts"] = attempts.value
        return failures, list(statuses)
    for attempt in range(1, restarts + 2):
        _LAST_LAUNCH["attempts"] = attempt
        failures, statuses = _launch_local_impl(
            argv, nprocs, timeout, grace, failfast, heartbeat_stall, hb_dir
        )
        if failures == 0 or attempt > restarts:
            return failures, statuses
        log.warning(
            "gang attempt %d/%d failed (statuses %s); restarting",
            attempt, restarts + 1, statuses,
        )
        # Retried attempts' exits must land in the counters too — the
        # caller only accounts the FINAL attempt's statuses, and a stall
        # that elastic recovery papered over is exactly what
        # watchdog_stalls_total exists to surface. (The native C++
        # elastic path runs its retry loop opaquely; its intermediate
        # statuses never reach Python and stay uncounted.)
        _account_gang_result(statuses)
    raise AssertionError("unreachable")


def _launch_local_impl(
    argv, nprocs, timeout, grace, failfast, heartbeat_stall, hb_dir
) -> Tuple[int, List[int]]:
    lib = load_native()
    if lib is not None and (
        heartbeat_stall is None or hasattr(lib, "ta_launch_processes_watched")
    ):
        c_argv, statuses, timeout_ms, grace_ms, hb_ms = _native_launch_args(
            argv, nprocs, timeout, grace, heartbeat_stall
        )
        if heartbeat_stall is not None:
            failures = lib.ta_launch_processes_watched(
                c_argv, nprocs, timeout_ms, grace_ms, hb_dir.encode(), hb_ms,
                statuses,
            )
        elif failfast:
            # timeout in (None, 0) = no deadline, the timeout(1) convention.
            failures = lib.ta_launch_processes_supervised(
                c_argv, nprocs, timeout_ms, grace_ms, statuses,
            )
        else:
            failures = lib.ta_launch_processes(c_argv, nprocs, statuses)
        if failures < 0:
            raise OSError("fork failed in the native launcher")
        return failures, list(statuses)
    # Pure-python fallback, subprocess-based.
    procs = []
    for r in range(nprocs):
        env = dict(os.environ)
        env["JAX_PROCESS_INDEX"] = str(r)
        env["TA_NUM_PROCESSES"] = str(nprocs)
        if hb_dir is not None:
            env["TA_HEARTBEAT_FILE"] = os.path.join(hb_dir, f"hb.{r}")
        procs.append(subprocess.Popen(list(argv), env=env))
    if not failfast:
        sts = [p.wait() for p in procs]
        sts = [128 - s if s < 0 else s for s in sts]
        return sum(1 for s in sts if s != 0), sts
    deadline = None if not timeout else time.monotonic() + timeout
    statuses: List[Optional[int]] = [None] * nprocs
    timed_out = False
    stalled = False
    terminating = False
    kill_at = None
    # Heartbeat tracking, clock-skew-robust: the mtime is only compared
    # against its previous value (a change marks progress) and aged with
    # the monotonic clock — never against wall-clock now, which NTP steps.
    hb_mtime: List[Optional[float]] = [None] * nprocs
    hb_changed = [time.monotonic()] * nprocs
    while any(s is None for s in statuses):
        for i, p in enumerate(procs):
            if statuses[i] is None and p.poll() is not None:
                statuses[i] = p.returncode
                if p.returncode != 0 and not terminating:
                    terminating = True
                    kill_at = time.monotonic() + grace
                    for q in procs:
                        if q.poll() is None:
                            q.terminate()
        now = time.monotonic()
        if not terminating and deadline is not None and now >= deadline:
            terminating = True
            timed_out = True
            kill_at = now + grace
            for q in procs:
                if q.poll() is None:
                    q.terminate()
        if not terminating and hb_dir is not None:
            for i, p in enumerate(procs):
                if statuses[i] is not None:
                    continue
                try:
                    m = os.path.getmtime(os.path.join(hb_dir, f"hb.{i}"))
                except OSError:
                    m = None
                if m is not None and m != hb_mtime[i]:
                    hb_mtime[i] = m  # progress = the mtime changed
                    hb_changed[i] = now
                if now - hb_changed[i] >= heartbeat_stall:
                    terminating = True
                    stalled = True
                    kill_at = now + grace
                    for q in procs:
                        if q.poll() is None:
                            q.terminate()
                    break
        if terminating and kill_at is not None and now >= kill_at:
            for q in procs:
                if q.poll() is None:
                    q.kill()
            kill_at = now + 60.0
        time.sleep(0.02)
    out = []
    for s in statuses:
        c = s if s is not None else 255
        if c < 0:
            c = 128 - c  # Popen reports -SIGNUM
        if timed_out and c in (128 + signal.SIGTERM, 128 + signal.SIGKILL):
            c = 124
        if stalled and c in (128 + signal.SIGTERM, 128 + signal.SIGKILL):
            c = 125
        out.append(c)
    return sum(1 for c in out if c != 0), out
