"""repro: a lock-manager simulator for the TXSQL concurrency-control
protocols, compiled as JAX programs."""

__version__ = "0.1.0"
