"""The benchmark's own emulated S3: the yardstick every store read is held to.

The link arithmetic is a copy of the program's link model, so that no change
to the program's simulator can speed up the benchmark:

* every request pays `latency_s` as a plain sleep, so latency overlaps
  freely across threads (S3 is highly concurrent);
* payload bytes reserve one shared link for `bytes / bandwidth_Bps` seconds,
  starting no earlier than the previous reservation ends (the instance NIC is
  one serial resource).

Objects live in `backing`, an unlinked store over the same dict: set-up
writes there and pays no link time. Every read through `LinkedStore` pays.
"""

from __future__ import annotations

import threading
import time

from repro.store.base import ObjectMeta, ObjectStore, StoreError, adjacent_runs


class Link:
    """Per-request latency plus one shared bandwidth reservation queue, with
    the counters the metrics read."""

    def __init__(self, latency_s: float, bandwidth_Bps: float) -> None:
        if latency_s < 0 or bandwidth_Bps <= 0:
            raise ValueError(f"bad link: {latency_s} s, {bandwidth_Bps} B/s")
        self.latency_s = latency_s
        self.bandwidth_Bps = bandwidth_Bps
        self._lock = threading.Lock()
        self._free_at = 0.0
        self.requests = 0
        self.bytes_moved = 0

    def transfer(self, nbytes: int) -> None:
        """Block for the emulated time of one request carrying `nbytes`."""
        if self.latency_s > 0:
            time.sleep(self.latency_s)
        if nbytes > 0:
            with self._lock:
                start = max(time.perf_counter(), self._free_at)
                self._free_at = start + nbytes / self.bandwidth_Bps
                finish = self._free_at
            delay = finish - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
        with self._lock:
            self.requests += 1
            self.bytes_moved += nbytes

    def counters(self) -> dict:
        with self._lock:
            return {"requests": self.requests, "bytes": self.bytes_moved}

    def floor_s(self, nbytes: int) -> float:
        """Least time the link needs to deliver `nbytes`: one request's
        latency, then every byte at the link's bandwidth."""
        return self.latency_s + nbytes / self.bandwidth_Bps


class DictStore(ObjectStore):
    """Unlinked objects in a dict: where set-up writes."""

    def __init__(self) -> None:
        self.objects: dict[str, bytes] = {}
        self._lock = threading.Lock()

    def _data(self, key: str) -> bytes:
        with self._lock:
            try:
                return self.objects[key]
            except KeyError:
                raise StoreError(f"no such object: {key}") from None

    def list_objects(self, prefix: str = "") -> list[ObjectMeta]:
        with self._lock:
            return [ObjectMeta(k, len(v)) for k, v in sorted(self.objects.items())
                    if k.startswith(prefix)]

    def size(self, key: str) -> int:
        return len(self._data(key))

    def get_range(self, key: str, start: int, end: int) -> bytes:
        if start < 0 or end < start:
            raise StoreError(f"bad range [{start}, {end})")
        return self._data(key)[start:end]

    def get(self, key: str) -> bytes:
        return self._data(key)

    def put(self, key: str, data: bytes) -> None:
        with self._lock:
            self.objects[key] = bytes(data)

    def delete(self, key: str) -> None:
        with self._lock:
            self.objects.pop(key, None)


class LinkedStore(ObjectStore):
    """S3 request charging over `backing`: LIST and HEAD are one latency-only
    request each, a GET is one request with its payload, and a run of
    adjacent ranges is one request for the whole run."""

    def __init__(self, latency_s: float, bandwidth_Bps: float) -> None:
        self.link = Link(latency_s, bandwidth_Bps)
        self.backing = DictStore()

    def list_objects(self, prefix: str = "") -> list[ObjectMeta]:
        self.link.transfer(0)
        return self.backing.list_objects(prefix)

    def size(self, key: str) -> int:
        self.link.transfer(0)
        return self.backing.size(key)

    def get_range(self, key: str, start: int, end: int) -> bytes:
        data = self.backing.get_range(key, start, end)
        self.link.transfer(len(data))
        return data

    def get_ranges(self, key: str, spans: list[tuple[int, int]]) -> list[bytes]:
        out: list[bytes] = []
        for run in adjacent_runs(spans):
            start, end = run[0][0], run[-1][1]
            data = self.backing.get_range(key, start, end)
            self.link.transfer(len(data))
            out.extend(data[s - start:e - start] for s, e in run)
        return out

    def get(self, key: str) -> bytes:
        data = self.backing.get(key)
        self.link.transfer(len(data))
        return data

    def put(self, key: str, data: bytes) -> None:
        self.link.transfer(len(data))
        self.backing.put(key, data)

    def delete(self, key: str) -> None:
        self.link.transfer(0)
        self.backing.delete(key)


def store_at(link: dict) -> LinkedStore:
    """An empty store behind the link a workload file names (`latency_s`,
    `bandwidth_Bps`)."""
    return LinkedStore(float(link["latency_s"]), float(link["bandwidth_Bps"]))
