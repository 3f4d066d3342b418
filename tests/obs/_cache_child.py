"""Child process for the persistent compile-cache warm-restart test.

Usage: ``JAX_COMPILATION_CACHE_DIR=<cache_dir> python _cache_child.py
<cache_dir>``.  Enables the cache, checks that it landed in ``cache_dir``,
runs one sweep group (the same family/shapes every
invocation), and prints one JSON line with the unified compile accounting:
trace-cache entries, persistent-cache hits, and the backend compile events
(trace entries minus hits — what ``record_compile`` attributes).
"""

import json
import os
import sys


def main() -> None:
    from repro.launch.cache import enable_compile_cache, persistent_cache_misses

    assert enable_compile_cache() == os.path.abspath(sys.argv[1])

    from repro.obs import counters
    from repro.obs.metrics import DEFAULT as registry
    from repro.sweeps import executor
    from repro.sweeps.registry import build_groups, expand

    scens = expand("hetero_kstar", ks=(50, 99), lams=(0.2,), rounds=32)
    (group,) = build_groups(scens, seeds=1)
    executor.run_group(group, round_chunk=16)

    try:  # absent on a warm restart: record_compile skips 0-event calls
        snap = registry.get("compile.sweeps_run_group.events")
    except KeyError:
        snap = None
    print(json.dumps({
        "trace_entries": counters.compile_events("sweeps.run_group"),
        "persistent_hits": counters.persistent_cache_hits(),
        "persistent_misses": persistent_cache_misses(),
        "backend_compiles": counters.backend_compile_events("sweeps.run_group"),
        "recorded_compile_metric": None if snap is None else snap["value"],
    }))


if __name__ == "__main__":
    main()
