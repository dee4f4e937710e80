"""DeviceLoader — double-buffered host->device prefetch.

Analog of the reference's C++ BufferedReader
(operators/reader/buffered_reader.cc): while the accelerator computes on
batch N, batch N+1 is already being copied to device memory. On TPU the
copy is `jax.device_put` (async under the hood); a background thread
keeps `depth` batches in flight so the training step never waits on the
host->device copy.

Optionally shards each batch across a mesh axis (`jax.device_put` with a
NamedSharding) so the loader feeds GSPMD data-parallel steps directly.
"""

from __future__ import annotations

import queue
import threading
from typing import Iterable, Optional

import jax


_STOP = object()


class DeviceLoader:
    def __init__(self, loader: Iterable, depth: int = 2, device=None,
                 mesh=None, spec=None):
        """``loader`` yields pytrees of numpy arrays. With ``mesh`` +
        ``spec`` (PartitionSpec for the batch leaves), batches land
        sharded; otherwise they go to ``device`` (default: first)."""
        self.loader = loader
        self.depth = max(1, int(depth))
        if mesh is not None:
            from jax.sharding import NamedSharding
            self._target = NamedSharding(mesh, spec)
        else:
            self._target = device or jax.devices()[0]

    def _put(self, batch):
        return jax.tree_util.tree_map(
            lambda a: jax.device_put(a, self._target), batch)

    def __iter__(self):
        q: "queue.Queue" = queue.Queue(maxsize=self.depth)
        err: list = []
        stop = threading.Event()

        def put(item) -> bool:
            # bounded put that gives up when the consumer walked away —
            # otherwise an early `break` in the train loop would pin the
            # producer thread (and depth device batches of HBM) forever
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        def producer():
            try:
                for batch in self.loader:
                    if not put(self._put(batch)):
                        return
            except BaseException as e:
                err.append(e)
            finally:
                put(_STOP)

        t = threading.Thread(target=producer, daemon=True)
        t.start()
        try:
            while True:
                item = q.get()
                if item is _STOP:
                    if err:
                        raise err[0]
                    return
                yield item
        finally:
            stop.set()
            while not q.empty():  # release buffered device arrays
                try:
                    q.get_nowait()
                except queue.Empty:
                    break
