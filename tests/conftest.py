import os
import sys

# smoke tests and benches must see ONE device (the dry-run alone forces 512,
# in its own process) — per the brief, never set the device-count flag here.
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# Persistent XLA compilation cache: the suite is compile-dominated, and
# warm re-runs of the tier-1 lane skip most compile time.
from repro.compile_cache import configure_compile_cache  # noqa: E402

configure_compile_cache()
