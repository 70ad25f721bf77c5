"""The benchmark's store model: per-request latency, and the aggregate
bandwidth cap that concurrent requests share."""
import sys
import threading
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[2]),
                str(Path(__file__).resolve().parents[2] / "src")]

from bench.store import ModelledStore  # noqa: E402

PARAMS = {"put_base_s": 0.005, "put_Bps": 100e6, "get_base_s": 0.004,
          "get_Bps": 100e6, "list_base_s": 0.001, "delete_base_s": 0.001,
          "head_base_s": 0.001, "jitter_frac": 0.0,
          "aggregate_put_Bps": 40e6, "aggregate_get_Bps": 40e6}


def test_one_put_costs_base_plus_bytes_over_stream_rate():
    s = ModelledStore(PARAMS, seed=1)
    t = time.monotonic()
    s.put("k", b"x" * 1_000_000)
    took = time.monotonic() - t
    assert 0.015 <= took < 0.015 + 0.05   # 5 ms + 1 MB / 100 MB/s
    assert s.get("k") == b"x" * 1_000_000


def test_concurrent_puts_stay_under_the_aggregate_cap():
    s = ModelledStore(PARAMS, seed=2)
    n, size = 8, 1_000_000
    threads = [threading.Thread(target=s.put, args=(f"k{i}", b"y" * size))
               for i in range(n)]
    t = time.monotonic()
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    took = time.monotonic() - t
    # each stream alone would take 15 ms; together 8 MB at 40 MB/s take 0.2 s
    assert took >= n * size / PARAMS["aggregate_put_Bps"]
    assert n * size / took <= PARAMS["aggregate_put_Bps"]
    assert s.stats.puts == n and s.stats.bytes_written == n * size


def test_jitter_follows_the_seed():
    p = dict(PARAMS, jitter_frac=0.1)
    a = [ModelledStore(p, seed=7).model.jitter(1.0) for _ in range(1)]
    b = [ModelledStore(p, seed=7).model.jitter(1.0) for _ in range(1)]
    assert a == b and 0.9 <= a[0] <= 1.1
