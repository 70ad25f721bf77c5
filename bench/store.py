"""The benchmark's object-store model: an S3-class store in memory.

A copy of the defaults of the repository's ``LatencyModel``: a PUT costs
15 ms + bytes / 300 MB/s, a GET 10 ms + bytes / 500 MB/s, LIST 12 ms, DELETE
8 ms, HEAD 6 ms, each with +-10% uniform jitter. The copy lives here so that
a change to the program cannot move the yardstick.

It adds what that model lacks: an aggregate bandwidth cap per direction over
concurrent requests. Each request's transfer also books its bytes on one
shared link; the request ends when both its own stream and its share of the
link are done. Without the cap, a change that only issued puts in parallel
would gain without limit. The cap's value is a configuration's ``assumed``
setting.

The jitter is drawn from the run's seed, so two runs of one seed draw the
same sequence of factors (which request gets which factor still depends on
thread timing).
"""
from __future__ import annotations

import random
import threading
import time
from dataclasses import dataclass
from typing import Mapping

from repro.core import MemoryObjectStore


@dataclass
class Link:
    """One direction of the host's link to the store: transfers queue on it
    first-come first-served at ``Bps`` bytes per second."""

    Bps: float

    def __post_init__(self):
        self._free_at = 0.0
        self._lock = threading.Lock()

    def book(self, nbytes: int, now: float) -> float:
        """Reserve the link for ``nbytes`` from ``now``; returns the time at
        which the last byte has crossed it."""
        with self._lock:
            start = max(now, self._free_at)
            self._free_at = start + nbytes / self.Bps
            return self._free_at


class StoreModel:
    """Delays of one S3-class store, with the aggregate cap (seconds)."""

    def __init__(self, params: Mapping[str, float], seed: int):
        self.p = dict(params)
        self._rng = random.Random(f"store/{seed}")
        self._rng_lock = threading.Lock()
        self.up = Link(self.p["aggregate_put_Bps"])
        self.down = Link(self.p["aggregate_get_Bps"])

    def jitter(self, t: float) -> float:
        f = self.p["jitter_frac"]
        with self._rng_lock:
            u = self._rng.uniform(-f, f)
        return t * (1.0 + u)

    def transfer(self, base: float, nbytes: int, stream_Bps: float,
                 link: Link) -> None:
        """Sleep for one request: base latency plus its bytes at the
        per-stream rate, no sooner than its bytes clear the shared link."""
        now = time.monotonic()
        own_end = now + self.jitter(base + nbytes / stream_Bps)
        link_end = link.book(nbytes, now) + base
        end = max(own_end, link_end)
        while True:
            left = end - time.monotonic()
            if left <= 0:
                return
            time.sleep(left)

    def pause(self, base: float) -> None:
        time.sleep(self.jitter(base))


class ModelledStore(MemoryObjectStore):
    """``MemoryObjectStore`` whose every request costs what ``StoreModel``
    says. Bytes are kept in the host's memory; nothing touches the disk."""

    def __init__(self, params: Mapping[str, float], seed: int):
        super().__init__()
        self.model = StoreModel(params, seed)

    def put(self, key, data):
        self._pre("put", key)
        self.model.transfer(self.model.p["put_base_s"], len(data),
                            self.model.p["put_Bps"], self.model.up)
        self._do_put(key, data)
        with self._stats_lock:
            self.stats.puts += 1
            self.stats.bytes_written += len(data)
        self._post("put", key)

    def put_if_absent(self, key, data):
        self._pre("cput", key)
        self.model.transfer(self.model.p["put_base_s"], len(data),
                            self.model.p["put_Bps"], self.model.up)
        ok = self._do_put_if_absent(key, data)
        with self._stats_lock:
            self.stats.conditional_puts += 1
            if ok:
                self.stats.bytes_written += len(data)
            else:
                self.stats.conditional_put_conflicts += 1
        self._post("cput", key)
        return ok

    def _get(self, nbytes: int) -> None:
        self.model.transfer(self.model.p["get_base_s"], nbytes,
                            self.model.p["get_Bps"], self.model.down)

    def get(self, key):
        self._pre("get", key)
        data = self._do_get(key)
        self._get(len(data))
        with self._stats_lock:
            self.stats.gets += 1
            self.stats.bytes_read += len(data)
        self._post("get", key)
        return data

    def get_range(self, key, start, length):
        self._pre("get_range", key)
        data = self._do_get_range(key, start, length)
        self._get(len(data))
        with self._stats_lock:
            self.stats.range_gets += 1
            self.stats.bytes_read += len(data)
        self._post("get_range", key)
        return data

    def get_ranges(self, key, ranges, gap_threshold=512 * 1024):
        """Vectored ranged GET: ranges whose gaps are at most
        ``gap_threshold`` share one modelled request, gap bytes included."""
        self._pre("get_ranges", key)
        out = [None] * len(ranges)
        order = sorted(range(len(ranges)), key=lambda i: ranges[i][0])
        groups = []   # [start, end, [index, ...]]
        for i in order:
            off, length = ranges[i]
            if groups and off - groups[-1][1] <= gap_threshold:
                groups[-1][1] = max(groups[-1][1], off + length)
                groups[-1][2].append(i)
            else:
                groups.append([off, off + length, [i]])
        fetched = 0
        for start, end, members in groups:
            view = memoryview(self._do_get_range(key, start, end - start))
            self._get(len(view))
            fetched += len(view)
            for i in members:
                off, length = ranges[i]
                out[i] = view[off - start:off - start + length]
        with self._stats_lock:
            self.stats.vectored_gets += 1
            self.stats.coalesced_requests += len(groups)
            self.stats.coalesced_ranges += len(ranges)
            self.stats.range_gets += len(groups)
            self.stats.bytes_read += fetched
        self._post("get_ranges", key)
        return out

    def head(self, key):
        self._pre("head", key)
        self.model.pause(self.model.p["head_base_s"])
        n = self._do_head(key)
        with self._stats_lock:
            self.stats.heads += 1
        self._post("head", key)
        return n

    def list(self, prefix):
        self._pre("list", prefix)
        keys = self._do_list(prefix)
        self.model.pause(self.model.p["list_base_s"] + 1e-6 * len(keys))
        with self._stats_lock:
            self.stats.lists += 1
        self._post("list", prefix)
        return keys

    def delete(self, key):
        self._pre("delete", key)
        self.model.pause(self.model.p["delete_base_s"])
        self._do_delete(key)
        with self._stats_lock:
            self.stats.deletes += 1
        self._post("delete", key)
