"""Pipelined I/O runtime: shared bounded thread pool + prefetch iterator.

Analogue of the reference engine's parallel scan units + streaming
ArrowReader (bodo/io/parquet_reader.cpp distributes scan units over a
reader thread pool; bodo/io/arrow_reader.h streams batches while the
pipeline consumes) and of Pathways-style asynchronous dataflow: host
decode work runs on pool threads so the device never waits for Arrow.

Three pieces:

  * ``io_pool()`` — one process-wide bounded ``ThreadPoolExecutor``
    (``config.io_threads`` workers) shared by every parallel decode site
    (parquet row groups, CSV byte-range chunks).
  * ``pool_map_ordered(fn, items)`` — map on the pool with a bounded
    in-flight window and ORDERED reassembly, so parallel reads are
    byte-identical to the serial reader.
  * ``Prefetcher`` — wraps a batch iterator; a worker thread decodes
    batch k+1 while the consumer (device compute) runs batch k. The
    queue depth is admission-charged against the memory governor
    (depth x batch bytes, non-blocking: under pressure the effective
    depth derates instead of stalling). Exceptions — including armed
    ``io.read`` faults fired on the worker — are captured and re-raised
    at the consumer; ``close()`` shuts the worker down promptly even
    mid-decode (no leaked threads).

All ``io:*`` observability counters (decode/stall seconds, prefetch
hits, footer-cache hits, parallel decode units) live here so
``tracing.profile()`` and ``dump()`` read one registry.
"""

from __future__ import annotations

import queue
import threading
import time
from collections import deque
from typing import Callable, Iterable, Iterator, Optional

from bodo_tpu.config import config

# ---------------------------------------------------------------------------
# io:* counter registry
# ---------------------------------------------------------------------------

_stats_lock = threading.Lock()


def _zero() -> dict:
    return {
        "decode_s": 0.0,        # worker-side time spent decoding batches
        "decode_batches": 0,
        "decode_bytes": 0,
        "stall_s": 0.0,         # consumer-side time blocked on the queue
        "stalls": 0,
        "prefetch_hits": 0,     # batches served with zero consumer wait
        "prefetch_streams": 0,
        "prefetch_depth": 0,    # max effective depth seen
        "footer_hits": 0,       # parquet footer cache
        "footer_misses": 0,
        "parallel_units": 0,    # row groups / csv chunks decoded on pool
        "parallel_reads": 0,
        # device-side parquet decode (io/device_decode.py)
        "device_decode_s": 0.0,       # consumer-side on-chip decode time
        "device_decode_pages": 0,     # pages decoded by jitted programs
        "device_decode_dispatches": 0,  # decode programs run (page runs)
        "device_decode_cols": 0,      # column chunks decoded on device
        "device_fallback_cols": 0,    # column chunks demoted to host
        "device_decode_errors": 0,    # planned-but-failed device decodes
        "device_decode_bytes": 0,     # decoded bytes produced on device
        "host_decode_bytes": 0,       # decoded bytes produced by pyarrow
        "raw_bytes": 0,               # raw (compressed) page bytes shipped
        # Pallas kernel engagement inside page programs (trace-time
        # counters: bumped when the kernel routes into a compiled spec)
        "pallas_dict_gather": 0,      # dictionary-decode gather
    }


_io = _zero()


def count(key: str, n: int = 1) -> None:
    with _stats_lock:
        _io[key] += n


def add_time(key: str, seconds: float) -> None:
    with _stats_lock:
        _io[key] += seconds


def record_depth(depth: int) -> None:
    with _stats_lock:
        _io["prefetch_depth"] = max(_io["prefetch_depth"], int(depth))


def io_stats() -> dict:
    """Snapshot of the io:* counters plus the derived overlap figures:
    ``overlap_s`` is decode time hidden behind consumer compute
    (decode_s - stall_s, floored at 0), ``overlap_ratio`` its fraction
    of total decode time."""
    with _stats_lock:
        out = dict(_io)
    overlap = max(out["decode_s"] - out["stall_s"], 0.0)
    out["overlap_s"] = overlap
    out["overlap_ratio"] = (overlap / out["decode_s"]
                            if out["decode_s"] > 0 else 0.0)
    # fraction of decoded output bytes produced on device rather than by
    # host pyarrow (the scan target from ROADMAP item 3)
    dd, hd = out["device_decode_bytes"], out["host_decode_bytes"]
    out["device_decode_frac"] = dd / (dd + hd) if (dd + hd) > 0 else 0.0
    return out


def reset_io_stats() -> None:
    global _io
    with _stats_lock:
        _io = _zero()


# ---------------------------------------------------------------------------
# shared bounded pool
# ---------------------------------------------------------------------------

_pool = None
_pool_threads = 0
_pool_lock = threading.Lock()


def io_thread_count() -> int:
    """Resolved worker count: ``config.io_threads``; <= 0 means auto
    (min(8, cpu_count), at least 2 so decode can overlap I/O even on a
    single-core host — Arrow releases the GIL while parsing)."""
    n = int(config.io_threads)
    if n <= 0:
        import os
        n = min(8, max(2, os.cpu_count() or 1))
    return n


def io_pool():
    """The process-wide I/O executor (rebuilt when io_threads changes)."""
    global _pool, _pool_threads
    n = io_thread_count()
    with _pool_lock:
        if _pool is None or _pool_threads != n:
            if _pool is not None:
                _pool.shutdown(wait=False)
            from concurrent.futures import ThreadPoolExecutor
            _pool = ThreadPoolExecutor(max_workers=n,
                                       thread_name_prefix="bodo-tpu-io")
            _pool_threads = n
        return _pool


def reset_pool() -> None:
    """Shut down the shared pool (tests / set_config(io_threads=...))."""
    global _pool
    with _pool_lock:
        if _pool is not None:
            _pool.shutdown(wait=False)
            _pool = None


def pool_map_ordered(fn: Callable, items: Iterable,
                     window: Optional[int] = None) -> Iterator:
    """Map `fn` over `items` on the shared pool, yielding results IN
    ORDER with at most `window` tasks in flight (default: pool width +
    1). A task exception propagates at its ordered position; remaining
    in-flight tasks are cancelled/abandoned."""
    ex = io_pool()
    w = max(int(window or (io_thread_count() + 1)), 1)
    pending: deque = deque()
    src = iter(items)
    try:
        for item in src:
            pending.append(ex.submit(fn, item))
            count("parallel_units")
            if len(pending) >= w:
                yield pending.popleft().result()
        while pending:
            yield pending.popleft().result()
    finally:
        for f in pending:
            f.cancel()


# ---------------------------------------------------------------------------
# prefetching iterator
# ---------------------------------------------------------------------------

def _default_nbytes(item) -> int:
    """Best-effort size of a prefetched item for governor accounting."""
    try:
        from bodo_tpu.runtime.memory_governor import table_device_bytes
        if hasattr(item, "columns") and hasattr(item, "nrows"):
            return table_device_bytes(item)
    except Exception:
        pass
    nb = getattr(item, "nbytes", None)
    try:
        return int(nb) if nb is not None else 0
    except Exception:
        return 0


_ITEM, _DONE, _ERR = "item", "done", "err"


class Prefetcher:
    """Bounded-queue lookahead over a batch iterator.

    Lazy: the worker thread starts on the first ``__next__`` (so a
    stream that is built but never consumed costs nothing and leaks
    nothing). The first decoded batch sizes a governor admission of
    depth x batch-bytes; under memory pressure the grant derates the
    EFFECTIVE depth rather than blocking the stream. Worker-side
    exceptions (armed ``io.read`` faults included) re-raise at the
    consumer in stream position."""

    def __init__(self, src: Iterator, depth: Optional[int] = None,
                 label: str = "stream",
                 nbytes_of: Optional[Callable] = None):
        self._src = src
        self._depth = max(int(depth if depth is not None
                              else config.prefetch_depth), 1)
        self._label = label
        self._nbytes_of = nbytes_of or _default_nbytes
        self._q: queue.Queue = queue.Queue()
        self._cv = threading.Condition()
        self._produced = 0
        self._consumed = 0
        self._eff = self._depth
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._grant = None
        self._closed = False

    # -- worker side ---------------------------------------------------------

    def _run(self) -> None:
        src = self._src
        first = True
        try:
            while not self._stop.is_set():
                t0 = time.perf_counter()
                try:
                    item = next(src)
                except StopIteration:
                    self._q.put((_DONE, None))
                    return
                except BaseException as e:  # noqa: BLE001 - re-raised at consumer
                    self._q.put((_ERR, e))
                    return
                dt = time.perf_counter() - t0
                nb = 0
                try:
                    nb = int(self._nbytes_of(item))
                except Exception:
                    nb = 0
                with _stats_lock:
                    _io["decode_s"] += dt
                    _io["decode_batches"] += 1
                    _io["decode_bytes"] += nb
                if first:
                    first = False
                    self._admit(nb)
                with self._cv:
                    while (self._produced - self._consumed) >= self._eff \
                            and not self._stop.is_set():
                        self._cv.wait(0.05)
                    if self._stop.is_set():
                        return
                    self._q.put((_ITEM, item))
                    self._produced += 1
        finally:
            self._release_grant()

    def _admit(self, nbytes: int) -> None:
        """Charge depth x batch-bytes against the governor's derived
        budget. Non-blocking: a reduced grant derates the effective
        lookahead depth instead of stalling the stream."""
        if nbytes <= 0:
            record_depth(self._eff)
            return
        try:
            from bodo_tpu.runtime.memory_governor import governor
            g = governor().admit(f"io_prefetch:{self._label}",
                                 want=self._depth * nbytes, wait=False)
        except Exception:
            record_depth(self._eff)
            return
        self._grant = g
        if g.budget:
            self._eff = max(1, min(self._depth,
                                   int(g.budget) // max(nbytes, 1)))
        g.update(self._eff * nbytes)
        record_depth(self._eff)

    def _release_grant(self) -> None:
        g = self._grant
        if g is not None:
            try:
                g.release()
            except Exception:
                pass

    # -- consumer side -------------------------------------------------------

    def _ensure_started(self) -> None:
        if self._thread is None and not self._closed:
            count("prefetch_streams")
            t = threading.Thread(target=self._run,
                                 name="bodo-tpu-prefetch", daemon=True)
            self._thread = t
            t.start()

    def __iter__(self) -> "Prefetcher":
        return self

    def __next__(self):
        if self._closed:
            raise StopIteration
        self._ensure_started()
        try:
            kind, payload = self._q.get_nowait()
            count("prefetch_hits")
        except queue.Empty:
            t0 = time.perf_counter()
            while True:
                try:
                    kind, payload = self._q.get(timeout=0.05)
                    break
                except queue.Empty:
                    if self._stop.is_set():
                        raise StopIteration from None
                    t = self._thread
                    if t is not None and not t.is_alive():
                        # worker died without a sentinel (defensive)
                        raise StopIteration from None
            with _stats_lock:
                _io["stall_s"] += time.perf_counter() - t0
                _io["stalls"] += 1
        with self._cv:
            self._consumed += 1
            self._cv.notify_all()
        if kind is _DONE:
            self._closed = True
            raise StopIteration
        if kind is _ERR:
            self._closed = True
            raise payload
        return payload

    def close(self) -> None:
        """Stop the worker, release the governor charge, and close the
        wrapped source. Safe to call repeatedly and from any thread."""
        self._closed = True
        self._stop.set()
        with self._cv:
            self._cv.notify_all()
        t = self._thread
        if t is not None and t is not threading.current_thread():
            t.join(timeout=5.0)
        self._release_grant()
        if t is None or not t.is_alive():
            close = getattr(self._src, "close", None)
            if close is not None:
                try:
                    close()
                except Exception:
                    pass

    def __del__(self):  # pragma: no cover - GC safety net
        try:
            self.close()
        except Exception:
            pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


def prefetched(src: Iterator, label: str = "stream",
               depth: Optional[int] = None) -> Iterator:
    """Wrap a batch source with prefetching when enabled
    (``config.prefetch_depth`` > 0; 0 disables and returns `src`
    unchanged). Returned as a generator so abandonment (GC of a
    half-consumed stream) still closes the worker via ``finally``."""
    d = int(depth if depth is not None else config.prefetch_depth)
    if d <= 0:
        return src

    def gen():
        pf = Prefetcher(src, depth=d, label=label)
        try:
            yield from pf
        finally:
            pf.close()
    return gen()
