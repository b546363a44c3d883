import os
import sys

# tests must see exactly ONE device (tests that need more start a
# subprocess with their own XLA flags)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
