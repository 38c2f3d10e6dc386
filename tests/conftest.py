import os
import sys

# src/ layout import path (so plain `pytest tests/` works too), and the repo
# root, where the benchmark's trace reduction lives (``perfbench``)
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(1, os.path.join(os.path.dirname(__file__), ".."))

# NOTE: no --xla_force_host_platform_device_count here — smoke tests must see
# the real single CPU device; only launch/dryrun.py forces 512.
