"""Live runtime backend — the JAX analogue of the CUPTI plugin.

The paper's plugins have two paths: synchronous host-API callbacks and
asynchronous device activity records. On a JAX stack:

  * host path: timing scopes around dispatch / ``block_until_ready`` /
    ``device_put`` (JAX has no user-visible per-kernel callback API, but
    dispatch boundaries are exactly the host-blocked-in-runtime windows
    the paper measures);
  * device path: execution windows of dispatched computations, buffered
    as activity records and delivered on ``flush()``. JAX dispatch is
    asynchronous (like CUDA streams), so ``launch()`` + ``wait()``
    reproduces the overlap semantics of use case 7: the device record
    spans launch→ready while the host is only charged for the blocked
    portion.

This is a proof-of-concept on CPU (the container's "device" is the host
CPU), faithful in mechanics; on a real TPU the same scopes wrap the same
dispatch boundaries.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, List, Optional, Tuple

import numpy as np

from ..states import DeviceActivity, DeviceRecord
from .base import register_backend

__all__ = ["RuntimeBackend", "AsyncHandle"]


class _DeviceColumns:
    """Per-device scalar-append column buffer (kind/start/end/stream).

    Append is O(1) Python-list work — no object per record; drain
    converts to NumPy columns in one shot.
    """

    __slots__ = ("kinds", "starts", "ends", "streams")

    def __init__(self):
        self.kinds: List[int] = []
        self.starts: List[float] = []
        self.ends: List[float] = []
        self.streams: List[int] = []

    def append(self, kind: int, start: float, end: float, stream: int) -> None:
        self.kinds.append(kind)
        self.starts.append(start)
        self.ends.append(end)
        self.streams.append(stream)

    def drain(self):
        cols = (
            np.asarray(self.kinds, dtype=np.uint8),
            np.asarray(self.starts, dtype=np.float64),
            np.asarray(self.ends, dtype=np.float64),
            np.asarray(self.streams, dtype=np.uint32),
        )
        self.kinds, self.starts, self.ends, self.streams = [], [], [], []
        return cols


@dataclass
class AsyncHandle:
    """Tracks one asynchronous dispatch (≙ work on a CUDA stream)."""

    out: Any
    launch_t: float
    device: int
    name: str
    stream: int = 0
    done_t: Optional[float] = None


@register_backend("runtime")
class RuntimeBackend:
    """Collects device activity records from live JAX execution."""

    def __init__(self, clock: Callable[[], float] = time.perf_counter):
        self.clock = clock
        self._columns: dict = {}  # dev -> _DeviceColumns
        self._pending: List[AsyncHandle] = []
        self.enabled = False

    # -- plugin lifecycle ------------------------------------------------
    def start(self) -> None:
        self.enabled = True

    def stop(self) -> None:
        # Drain pending asynchronous work before disabling.
        for h in list(self._pending):
            self.wait(h)
        self.enabled = False

    def _record(self, dev: int, kind: DeviceActivity, start: float,
                end: float, stream: int = 0) -> None:
        cols = self._columns.get(dev)
        if cols is None:
            cols = self._columns[dev] = _DeviceColumns()
        cols.append(kind.code, start, end, stream)

    def flush_arrays(self):
        """Drain buffered activity as per-device column batches."""
        return [
            (dev, *self._columns[dev].drain())
            for dev in sorted(self._columns)
        ]

    def flush(self):
        """Legacy object path: materialize ``DeviceRecord`` per event."""
        out = []
        for dev, kinds, starts, ends, streams in self.flush_arrays():
            out.extend(
                (dev, DeviceRecord(DeviceActivity.from_code(k), float(s),
                                   float(e), int(st)))
                for k, s, e, st in zip(kinds, starts, ends, streams)
            )
        return out

    # -- device activity (async path) ------------------------------------
    def launch(self, fn: Callable, *args, device: int = 0, name: str = "",
               stream: int = 0, **kwargs) -> AsyncHandle:
        """Dispatch without blocking; the device record is completed at
        ``wait()``. Host cost of the launch call itself is whatever the
        caller's scope charges (typically microseconds)."""
        t0 = self.clock()
        out = fn(*args, **kwargs)
        h = AsyncHandle(out=out, launch_t=t0, device=device,
                        name=name or getattr(fn, "__name__", "fn"), stream=stream)
        self._pending.append(h)
        return h

    def wait(self, handle: AsyncHandle) -> Any:
        """Block until ready; emit the kernel activity record."""
        import jax

        out = jax.block_until_ready(handle.out)
        handle.done_t = self.clock()
        if self.enabled:
            self._record(
                handle.device, DeviceActivity.KERNEL,
                handle.launch_t, handle.done_t, handle.stream,
            )
        if handle in self._pending:
            self._pending.remove(handle)
        return out

    # -- synchronous helpers ----------------------------------------------
    def run_sync(self, fn: Callable, *args, device: int = 0, name: str = "",
                 **kwargs) -> Any:
        h = self.launch(fn, *args, device=device, name=name, **kwargs)
        return self.wait(h)

    def record_transfer(self, fn: Callable, *args, device: int = 0,
                        name: str = "transfer", **kwargs) -> Any:
        """Time a host↔device data movement as a MEMORY record."""
        import jax

        t0 = self.clock()
        out = jax.block_until_ready(fn(*args, **kwargs))
        t1 = self.clock()
        if self.enabled:
            self._record(device, DeviceActivity.MEMORY, t0, t1)
        return out
