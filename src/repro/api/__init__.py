"""Unified dictionary API: one jit-native facade over every backend.

The paper benchmarks the GPU LSM as a *dictionary* against a sorted array and
a cuckoo hash table (Table 1); this package is the corresponding library
surface. `Dictionary.create(backend=...)` yields a pytree-registered handle
whose methods (insert / delete / update / bulk_build / lookup / count /
range / cleanup / size) hide all jit / donation / batching plumbing:

    from repro.api import Dictionary

    d = Dictionary.create("lsm", capacity=1 << 20)
    d = d.insert(keys, values)            # any length — padded/split into b-batches
    found, vals = d.lookup(queries)
    counts, ok = d.count(k1, k2)          # QueryPlan auto-sized, override available

Backend capability matrix (paper Table 1 — dictionary ops x data structure):

    op          lsm   sorted_array   cuckoo
    insert      yes   yes            no (static: bulk_build only)
    delete      yes   yes            no
    lookup      yes   yes            yes
    count       yes   yes            no (unordered)
    range       yes   yes            no (unordered)
    cleanup     yes   yes            no
    bulk_build  yes   yes            yes

Unsupported ops raise `CapabilityError` naming the backend and the backends
that do support the op — never a silent wrong answer.
"""

from repro.api.backend import (  # noqa: F401
    Backend,
    BackendState,
    Capabilities,
    CapabilityError,
    KeyDomainError,
    OccupancyStats,
    available_backends,
    backend_class,
    register_backend,
)
from repro.api.plan import QueryPlan  # noqa: F401
from repro.api.dictionary import Dictionary  # noqa: F401

# Importing the module registers the built-in backends.
from repro.api import backends as _builtin_backends  # noqa: F401,E402
