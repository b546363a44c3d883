"""Batched serving with TXSQL-style dynamic group commit (§4.6.1).

    PYTHONPATH=src python examples/serve_batched.py --requests 16
"""
import os
import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.launch.serve import main
from repro.compile_cache import enable_compile_cache

if __name__ == "__main__":
    enable_compile_cache()
    main()
