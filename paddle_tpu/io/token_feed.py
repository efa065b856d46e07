"""Token-corpus feed: native C++ prefetcher with a numpy fallback.

``TokenFeed(path, sample_elems, batch_size)`` iterates ``[batch,
sample_elems]`` numpy batches over a flat binary corpus of fixed-size
samples — the host-side input path for pretraining recipes
(`examples/llama_pretrain.py`). When the native library is available
(`paddle_tpu/native/src/data_feed.cc` — the analog of the reference's
C++ feed threads, `fluid/framework/data_feed.cc`), batches are filled by
a C++ prefetch thread over an mmap; otherwise :class:`PyTokenFeed`
serves the same contract from ``np.memmap`` synchronously.
"""

from __future__ import annotations

import queue
import threading
import time
import warnings

import numpy as np

from .. import native as _native

__all__ = ["TokenFeed", "PyTokenFeed", "DevicePrefetcher"]


class PyTokenFeed:
    """Pure-numpy fallback with identical iteration semantics to
    :class:`paddle_tpu.native.TokenFeed` (same per-epoch permutation is
    NOT guaranteed — the native feed shuffles with C++ mt19937 — but the
    visit-each-sample-once / drop-last contract is)."""

    def __init__(self, path, sample_elems, batch_size, dtype=np.int32,
                 shuffle=True, seed=0, prefetch_depth=4, epochs=-1):
        self.dtype = np.dtype(dtype)
        self.sample_elems = int(sample_elems)
        self.batch_size = int(batch_size)
        data = np.memmap(path, dtype=self.dtype, mode="r")
        n = data.size // self.sample_elems
        if n < self.batch_size:
            raise ValueError(
                f"TokenFeed: cannot open {path!r} (too small for one "
                f"batch of {batch_size} x {sample_elems} {self.dtype})")
        self._data = data[:n * self.sample_elems].reshape(
            n, self.sample_elems)
        self.shuffle, self.seed = shuffle, seed
        self.epochs = epochs
        self._epoch = 0
        self._step = 0
        self._order = self._epoch_order()

    @property
    def num_samples(self):
        return self._data.shape[0]

    @property
    def batches_per_epoch(self):
        return self.num_samples // self.batch_size

    def _epoch_order(self):
        if not self.shuffle:
            return np.arange(self.num_samples)
        return np.random.RandomState(
            self.seed + self._epoch).permutation(self.num_samples)

    def __iter__(self):
        return self

    def __next__(self):
        if self._step >= self.batches_per_epoch:
            self._epoch += 1
            if self.epochs > 0 and self._epoch >= self.epochs:
                raise StopIteration
            self._step = 0
            self._order = self._epoch_order()
        idx = self._order[self._step * self.batch_size:
                          (self._step + 1) * self.batch_size]
        self._step += 1
        return np.ascontiguousarray(self._data[idx])

    def close(self):
        pass


class DevicePrefetcher:
    """Double-buffered async host->device prefetch over any host-batch
    iterator.

    A background thread pulls the next host batch from ``source``,
    applies ``transform`` (e.g. split ``[B, S+1]`` ids into the train
    step's ``(ids, labels)`` views), and ``put``s every array leaf onto
    the device — so the NEXT batch's host work and H2D copy overlap the
    CURRENT step's device compute. Combined with
    ``jit.to_static(donate_inputs=True)`` this is the input half of the
    training hot loop: the step consumes a fresh donated device batch
    while the prefetcher is already copying the following one.

    ``depth`` bounds the queue (default 2: one batch in flight on
    device, one being filled — classic double buffering). Iteration
    ends when ``source`` does; a source exception re-raises in the
    consumer.

    Stall accounting: :meth:`mark` returns ``(stall_seconds,
    wall_seconds)`` since the previous mark — time the CONSUMER spent
    blocked waiting for a batch vs wall time — and publishes the ratio
    as the ``train_input_stall_frac`` gauge. A fraction near 0 means
    the input pipeline hides behind compute; anything above a few
    percent is headroom the accelerator is not getting.
    """

    def __init__(self, source, transform=None, depth=2, put=None):
        if put is None:
            import jax
            put = jax.device_put
        self._put = put
        self._transform = transform
        self._src = iter(source)
        self._q: "queue.Queue" = queue.Queue(maxsize=max(1, int(depth)))
        self._stop = threading.Event()
        self._stall = 0.0
        self._mark_stall = 0.0
        self._mark_t = time.perf_counter()
        self._terminal = None   # sticky: StopIteration / source error
        self.batches = 0
        self._thread = threading.Thread(target=self._worker, daemon=True,
                                        name="device-prefetch")
        self._thread.start()

    def _device_put_tree(self, item):
        import jax
        return jax.tree_util.tree_map(
            lambda leaf: self._put(np.ascontiguousarray(leaf))
            if isinstance(leaf, np.ndarray) else leaf, item)

    def _enqueue(self, entry):
        """put with a stop-aware timeout so close() never deadlocks on a
        full queue with no consumer."""
        while not self._stop.is_set():
            try:
                self._q.put(entry, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def _worker(self):
        try:
            while not self._stop.is_set():
                try:
                    item = next(self._src)
                except StopIteration:
                    self._enqueue(("end", None))
                    return
                if self._transform is not None:
                    item = self._transform(item)
                if not self._enqueue(("ok", self._device_put_tree(item))):
                    return
        except Exception as e:  # surface in the consumer, not the log
            self._enqueue(("err", e))

    def __iter__(self):
        return self

    def __next__(self):
        if self._terminal is not None:
            raise self._terminal
        if self._stop.is_set():
            raise StopIteration
        t0 = time.perf_counter()
        kind, payload = self._q.get()
        self._stall += time.perf_counter() - t0
        if kind == "end":
            # sticky: later next() calls re-raise instead of blocking
            # on a queue the worker will never fill again
            self._terminal = StopIteration()
            raise self._terminal
        if kind == "err":
            self._terminal = payload
            raise payload
        self.batches += 1
        return payload

    @property
    def stall_seconds(self):
        """Total consumer time spent blocked waiting for a batch."""
        return self._stall

    def mark(self):
        """(stall_seconds, wall_seconds) since the previous mark; also
        sets the ``train_input_stall_frac`` gauge to their ratio."""
        now = time.perf_counter()
        stall = self._stall - self._mark_stall
        wall = max(now - self._mark_t, 1e-9)
        self._mark_stall = self._stall
        self._mark_t = now
        try:
            from ..observability import metrics as om
            if om.enabled():
                om.gauge("train_input_stall_frac",
                         "fraction of the window the train loop spent "
                         "blocked on input prefetch").set(
                    min(1.0, stall / wall))
        except Exception:
            pass
        return stall, wall

    def close(self):
        self._stop.set()
        # drain so a worker blocked on put can observe the stop
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass
        self._thread.join(timeout=5.0)
        src_close = getattr(self._src, "close", None)
        if callable(src_close):
            src_close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def TokenFeed(path, sample_elems, batch_size, dtype=np.int32, shuffle=True,
              seed=0, prefetch_depth=4, epochs=-1):
    """Factory: the native prefetching feed when buildable, else the
    numpy fallback — which says so, with the reason the native library
    gave. Both yield ``[batch_size, sample_elems]`` arrays."""
    if _native.available():
        cls = _native.TokenFeed
    else:
        warnings.warn("TokenFeed: native feed unavailable "
                      f"({_native.load_error()}); using the numpy feed")
        cls = PyTokenFeed
    return cls(path, sample_elems, batch_size, dtype=dtype, shuffle=shuffle,
               seed=seed, prefetch_depth=prefetch_depth, epochs=epochs)
