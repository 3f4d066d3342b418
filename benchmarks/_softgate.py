"""Shared soft-perf-gate helpers for the BENCH_*.json-writing targets.

The repo's regression convention (established by ``sweep_smoke``, shared by
``bench_faults``): every perf-ish metric is checked against the COMMITTED
manifest — ``git show HEAD:BENCH_*.json``, so local refreshes can never
ratchet the reference down; the working-tree file is only the fallback when
git is unavailable — and a regression beyond tolerance prints a WARNING to
stderr and flags the manifest, but never fails the run.  Shared-CI wall
clocks are too noisy for hard gates; the hard gates are the in-run
correctness assertions.

Each check returns a STRUCTURED warning record (or ``None`` / an empty
list when the check passes) so benches can append it to their manifest's
``warnings`` list and ``benchmarks/run.py obs_report`` can surface every
soft regression across all committed manifests in one place.  A record is
a flat JSON-able dict: ``{"kind", "bench", "metric", "value", "baseline",
"message", ...}``; truthiness is preserved (record dict / non-empty list
iff the old booleans were True), so ``bool(...)`` recovers the legacy
manifest flags.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

# warn (never fail) when a throughput-style metric drops more than this
# fraction below the committed baseline
SLOWDOWN_WARN_FRACTION = 0.30


def cold_compile_metric(cold_s: float, cache_hits: int) -> dict:
    """The first-call seconds, keyed by whether that call really compiled.

    With the persistent compile cache on (``repro.launch.cache``), a rerun
    in the same checkout loads the family from disk instead of compiling
    it.  Such a time goes under ``cold_cached_s``, so the ``cold_s`` trend
    in ``BENCH_history.jsonl`` only ever compares real compiles.
    """
    key = "cold_s" if cache_hits == 0 else "cold_cached_s"
    return {key: cold_s, "cold_cache_hits": int(cache_hits)}


def committed_baseline_with_source(path: str) -> tuple[dict, str]:
    """The committed manifest at ``path`` plus WHERE it came from.

    Returns ``(doc, source)`` with ``source`` one of ``"git"`` (``git show
    HEAD:`` succeeded), ``"worktree"`` (no usable git checkout / the file
    is untracked at HEAD — the on-disk file stands in), or ``"missing"``
    (neither; ``doc`` is ``{}``).  Consumers that must degrade gracefully
    (``obs_report``) use the source to emit a structured ``baseline``
    warning record instead of silently diffing against the wrong
    reference."""
    git_root = _repo_root(os.path.dirname(os.path.abspath(path)))
    if git_root is not None:
        rel = os.path.relpath(os.path.abspath(path), git_root)
        try:
            blob = subprocess.run(
                ["git", "show", f"HEAD:{rel.replace(os.sep, '/')}"],
                capture_output=True, text=True, timeout=30, cwd=git_root,
            )
            if blob.returncode == 0:
                return json.loads(blob.stdout), "git"
        except (OSError, subprocess.SubprocessError, json.JSONDecodeError):
            pass
    try:
        with open(path) as f:
            return json.load(f), "worktree"
    except (OSError, json.JSONDecodeError):
        return {}, "missing"


def _repo_root(start: str) -> str | None:
    """The git worktree root containing ``start``, or None without git."""
    try:
        out = subprocess.run(
            ["git", "rev-parse", "--show-toplevel"],
            capture_output=True, text=True, timeout=30, cwd=start,
        )
        if out.returncode == 0 and out.stdout.strip():
            return out.stdout.strip()
    except (OSError, subprocess.SubprocessError):
        pass
    return None


def committed_baseline(path: str) -> dict:
    """The committed manifest at ``path`` (git HEAD), falling back to the
    on-disk file outside a usable git checkout."""
    return committed_baseline_with_source(path)[0]


def _emit(record: dict) -> dict:
    print(f"WARNING: {record['message']}", file=sys.stderr)
    return record


def warn_slowdown(
    bench: str,
    value: float,
    baseline_value: float | None,
    *,
    metric: str = "rows/sec",
    fraction: float = SLOWDOWN_WARN_FRACTION,
) -> dict | None:
    """Soft throughput check: a warning record (and a stderr WARNING) iff
    ``value`` fell more than ``fraction`` below the committed
    ``baseline_value``; ``None`` when the check passes."""
    if not baseline_value or value >= (1.0 - fraction) * baseline_value:
        return None
    return _emit({
        "kind": "slowdown",
        "bench": bench,
        "metric": metric,
        "value": float(value),
        "baseline": float(baseline_value),
        "drop_fraction": 1.0 - value / baseline_value,
        "message": (
            f"{bench} {metric} regressed "
            f"{1.0 - value / baseline_value:.0%} vs committed baseline "
            f"({value:.0f} vs {baseline_value:.0f}); soft check only"
        ),
    })


def warn_compiles(
    bench: str,
    family_compiles: dict[str, int],
    baseline_compiles: dict[str, int],
) -> list[dict]:
    """Soft compile-count check: one warning record (and one stderr WARNING)
    per family that compiled MORE computations than the committed baseline;
    an empty list when every family holds.  Counts are deterministic, but
    the convention stays soft — the hard gate is each bench's in-run
    one-compile assertion."""
    records = []
    for fam, count in family_compiles.items():
        committed = baseline_compiles.get(fam)
        if committed is not None and count > committed:
            records.append(_emit({
                "kind": "compiles",
                "bench": bench,
                "metric": f"family_compiles[{fam}]",
                "value": int(count),
                "baseline": int(committed),
                "message": (
                    f"{bench} family {fam!r} compiled {count} "
                    f"computations vs {committed} in the committed "
                    "baseline; soft check only"
                ),
            }))
    return records


def warn_speedup_bar(
    bench: str,
    speedup: float,
    bar: float,
    *,
    metric: str = "speedup",
) -> dict | None:
    """Soft absolute-bar check: a warning record (and a stderr WARNING) iff
    ``speedup`` is below the acceptance ``bar``; ``None`` otherwise.  Wall
    clock is never a hard gate (machine contention)."""
    if speedup >= bar:
        return None
    return _emit({
        "kind": "speedup_bar",
        "bench": bench,
        "metric": metric,
        "value": float(speedup),
        "baseline": float(bar),
        "message": (
            f"{bench} {metric} {speedup:.1f}x is below the {bar:.0f}x bar; "
            "soft check only (machine contention?)"
        ),
    })


def collect(*checks) -> list[dict]:
    """Flatten check results (records, ``None``s, lists of records) into the
    manifest ``warnings`` list."""
    out: list[dict] = []
    for c in checks:
        if not c:
            continue
        out.extend(c if isinstance(c, list) else [c])
    return out
