"""queue_wait_p50_ms.zipf: ``queue_wait_p50_ms``'s reading (the median
admission wait of the requests that reach the queue, the cache's misses) in
the cells that report ``latency_p75_ms``."""
from pathlib import Path

from bench.manifest import load_module

read = load_module(Path(__file__).with_name("queue_wait_p50_ms.py")).read
