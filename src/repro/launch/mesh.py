"""Production mesh definitions and the multi-host grid entry point.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state.  Production target: TPU v5e, 16x16 = 256 chips per
pod; the multi-pod mesh adds a leading "pod" axis (2 pods = 512 chips).

Multi-host: :func:`init_distributed` joins a ``jax.distributed`` grid when
the ``REPRO_COORDINATOR`` / ``REPRO_NUM_PROCESSES`` / ``REPRO_PROCESS_ID``
environment (or explicit arguments) describe one, and is a strict no-op at
world size 1 — single-process runs never touch distributed state, so
world=1 behaviour (and bits) degenerate to the plain path.  :func:`world`
reports ``(process_index, process_count)`` either way.  The sweep executor
(:func:`repro.sweeps.executor.run_multihost`) shards scenario ROWS over
the grid, so each host only ever computes on its local devices —
:func:`make_sweep_mesh` therefore spans ``jax.local_devices()``, which is
identical to ``jax.devices()`` in a single-process run.
"""

from __future__ import annotations

import os

import jax
import numpy as np


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Small mesh over however many (host) devices exist — tests/examples."""
    n = int(np.prod(shape))
    if len(jax.devices()) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, found {len(jax.devices())} "
            "(set XLA_FLAGS=--xla_force_host_platform_device_count=N first)"
        )
    return _auto_mesh(shape, axes)


def _auto_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    # Auto axes: the models place data with with_sharding_constraint and let
    # GSPMD propagate the rest; jax.make_mesh defaults to Explicit axes
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_sweep_mesh(num_devices: int | None = None):
    """1-D ``("batch",)`` mesh for Monte-Carlo sweep sharding (repro.sweeps).

    Sweep rows are embarrassingly parallel, so the executor lays the flat
    (scenarios x seeds) batch over a single mesh axis spanning however many
    devices exist (or the first ``num_devices``).  Works the same on a real
    TPU slice and on forced host devices
    (``XLA_FLAGS=--xla_force_host_platform_device_count=N``).

    LOCAL devices only: under a ``jax.distributed`` grid each host shards
    its own scenario rows over its own devices (row sharding crosses hosts
    through the spool files, not through a global mesh), and in a
    single-process run ``local_devices() == devices()``.
    """
    avail = jax.local_devices()
    n = len(avail) if num_devices is None else num_devices
    if n < 1 or n > len(avail):
        raise RuntimeError(f"sweep mesh needs 1..{len(avail)} devices, asked for {n}")
    return jax.sharding.Mesh(np.asarray(avail[:n]), ("batch",))


def init_distributed(
    *,
    coordinator: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> tuple[int, int]:
    """Join a ``jax.distributed`` grid if one is configured; returns ``world()``.

    Configuration comes from the arguments or, when omitted, the
    environment: ``REPRO_COORDINATOR`` (``host:port``),
    ``REPRO_NUM_PROCESSES``, ``REPRO_PROCESS_ID``.  With no coordinator or
    ``num_processes <= 1`` this is a STRICT no-op returning ``(0, 1)`` —
    the world=1 degeneration the tests pin down.  Safe to call twice
    (already-initialised grids are detected, not re-joined).
    """
    coord = coordinator if coordinator is not None else os.environ.get(
        "REPRO_COORDINATOR")
    n = num_processes if num_processes is not None else int(
        os.environ.get("REPRO_NUM_PROCESSES", "1"))
    if not coord or n <= 1:
        return (0, 1)
    pid = process_id if process_id is not None else int(
        os.environ.get("REPRO_PROCESS_ID", "0"))
    # a module flag, NOT jax.process_count(): probing the backend would
    # initialise it single-process and poison distributed.initialize
    if not _DIST["joined"]:
        jax.distributed.initialize(
            coordinator_address=coord, num_processes=n, process_id=pid
        )
        _DIST["joined"] = True
    return world()


_DIST = {"joined": False}


def world() -> tuple[int, int]:
    """``(process_index, process_count)`` — ``(0, 1)`` outside any grid."""
    return (jax.process_index(), jax.process_count())


# Hardware constants for the roofline model (TPU v5e).
PEAK_FLOPS_BF16 = 197e12          # per chip
HBM_BW = 819e9                    # bytes/s per chip
ICI_BW = 50e9                     # bytes/s per link
