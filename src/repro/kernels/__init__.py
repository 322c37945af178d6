"""The LSM's merges, sorts and searches: one XLA path per op (ops.py)."""
