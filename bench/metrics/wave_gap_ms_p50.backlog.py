"""wave_gap_ms_p50.backlog: ``wave_gap_ms_p50.open``'s reading (the median
host stretch between waves) in the backlog cells."""
from pathlib import Path

from bench.manifest import load_module

read = load_module(Path(__file__).with_name("wave_gap_ms_p50.open.py")).read
