"""Packets-within-chunks: the erasure model below chunk granularity.

``coded_ops.chunk_on_time`` is all-or-nothing per worker: a worker whose
whole load misses the deadline contributes nothing.  Here each chunk's
result rows are split into ``packets`` equal blocks streamed out as they
finish, giving two refinements:

Partial-work conservation (the ``conserve=True`` rule)
------------------------------------------------------
Worker i evaluates its assigned prefix of chunks in order, emitting packet
q of its j-th chunk at time ``(j + (q+1)/packets) / speed``.  A packet is
on time iff that instant is within the worker's cutoff ``t_cut`` (the
deadline, shortened by crash/preemption injectors) AND the network kept it
(``FaultTrace.keep``).  A preempted worker's finished packets therefore
still count — exactly the partial results *Hierarchical Coded Elastic
Computing* (arXiv 2206.09399) conserves.

All-or-nothing reference (``conserve=False``)
---------------------------------------------
The classic rule at packet granularity: a worker's packets all arrive iff
its WHOLE load meets ``t_cut`` — the same comparison
``loads/speed <= t_cut + 1e-9`` as :func:`repro.core.coded_ops.chunk_on_time`.
Two containment properties anchor the tests and the benchmark:

  * AON ⊆ conserve, bitwise: the conserving numerator of worker i's last
    assigned packet is ``(loads-1) + packets/packets = loads`` — the SAME
    float32 expression the AON rule compares — and earlier packets have
    strictly smaller numerators, so every AON packet is a conserve packet
    on any trace, and a conserving decode can only recover MORE rounds.
  * At ``packets=1`` on the no-fault trace, the AON packet mask reshaped to
    chunks IS ``chunk_on_time`` bit-for-bit, and the per-packet decode
    below literally calls the same jitted ``_decode_on_time`` /
    ``_decode_on_time_modp`` computation — so the packet path degrades to
    the existing all-or-nothing path exactly (float AND GF(p)), not just
    approximately.

Per-packet decode
-----------------
LCC decode is row-wise: decoded chunk rows are fixed linear (or GF(p))
combinations of the SAME rows of the received evaluations.  Splitting each
chunk's ``rows`` into ``packets`` blocks therefore decouples the blocks:
packet q of every output chunk is decodable from any K* workers' chunk
evaluations whose packet q arrived — different packets may decode from
DIFFERENT K*-subsets.  :func:`coded_matmul_packets` (float) and
:func:`coded_matmul_exact_packets` (GF(p)) run the existing traced-pattern
device decode once per packet index (a static Python loop — ``packets`` is
a small static constant) and concatenate the row blocks.

Hierarchical two-layer option
-----------------------------
``layer1_recovery`` models a second, lower-rate code protecting the first
``p1`` packet indices of a smaller ``k1``-chunk summary (threshold
``K1 = (k1-1) deg_f + 1 < K*``): when the full decode is infeasible, the
round can still be served PARTIALLY from the layer-1 packets — the
degraded mode the executor accounts as ``partial``.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.core.coded_ops import (CodedDataset, CodedDatasetModp,
                                  _decode_on_time, _decode_on_time_modp,
                                  _products_modp)

from .channels import FaultTrace


def packet_on_time(
    states: jnp.ndarray,
    loads: jnp.ndarray,
    mu_g,
    mu_b,
    deadline,
    r: int,
    packets: int,
    trace: FaultTrace | None = None,
    conserve: bool = True,
) -> jnp.ndarray:
    """Per-packet on-time masks: (..., n) states/loads -> (..., n*r, packets).

    The packet generalisation of :func:`repro.core.coded_ops.chunk_on_time`
    (same speed model, same deadline tolerance — see the module docstring
    for the exact containment/degradation guarantees).  ``trace`` supplies
    per-round cutoffs and delivery masks from the fault channel; ``None``
    is the no-fault trace (``t_cut = deadline``, everything delivered).
    Leading axes broadcast: (M, n) states with (S, M, n) loads and an
    (M, n)-cutoff trace score every strategy against the SAME faults.
    """
    speeds = jnp.where(states == 1, mu_g, mu_b)                  # (..., n)
    if trace is not None:
        t_cut = jnp.minimum(trace.t_cut, jnp.asarray(deadline, jnp.float32))
        tc = t_cut[..., None, None]                              # (..., n, 1, 1)
    else:
        # scalar deadline kept raw so the AON comparison below is the exact
        # expression chunk_on_time evaluates (bit-identity anchor)
        t_cut = deadline
        tc = deadline
    if conserve:
        # packet q of assigned chunk j completes at (j + (q+1)/P) / speed
        frac = (jnp.arange(packets, dtype=jnp.float32) + 1.0) / packets
        num = jnp.arange(r, dtype=jnp.float32)[:, None] + frac[None, :]
        done = num / speeds[..., None, None] <= tc + 1e-9        # (..., n, r, P)
    else:
        whole = loads.astype(jnp.float32) / speeds <= t_cut + 1e-9
        done = jnp.broadcast_to(
            whole[..., None, None],
            whole.shape + (r, packets),
        )
    assigned = jnp.arange(r) < loads[..., None]                  # (..., n, r)
    ok = done & assigned[..., None]
    if trace is not None:
        ok = ok & trace.keep
    return ok.reshape(ok.shape[:-3] + (ok.shape[-3] * r, packets))


def packet_counts(packet_masks: jnp.ndarray) -> jnp.ndarray:
    """(..., nr, packets) masks -> (..., packets) received-evaluation counts.

    Count of distinct chunk evaluations whose packet q arrived — the
    quantity compared against K* for per-packet decodability.
    """
    return jnp.sum(packet_masks.astype(jnp.int32), axis=-2)


def layer1_recovery(counts: jnp.ndarray, k1_threshold, p1: int) -> jnp.ndarray:
    """(..., packets) counts -> (...,) layer-1 (partial) decodability.

    True iff every one of the first ``p1`` packet indices reached the
    layer-1 threshold ``K1`` — the smaller summary code decodes even when
    the full-rate layer cannot.
    """
    return jnp.all(counts[..., :p1] >= k1_threshold, axis=-1)


def _split_rows(results: jnp.ndarray, packets: int) -> int:
    rows = results.shape[1]
    if rows % packets != 0:
        raise ValueError(
            f"chunk rows ({rows}) must divide into packets ({packets})"
        )
    return rows // packets


def coded_matmul_packets(
    coded: CodedDataset, w: jnp.ndarray, packet_masks: jnp.ndarray
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-packet float decode of f(X_j) = X_j @ w.

    ``packet_masks`` is (nr, packets) from :func:`packet_on_time`.  Packet q
    of every output chunk decodes independently from the chunk evaluations
    whose packet q arrived.  Returns ``(decoded (k, rows[, d]), ok
    (packets,))``; packet q's rows are meaningful only where ``ok[q]``.
    At ``packets=1`` with a full mask this is the exact
    :func:`~repro.core.coded_ops.coded_matmul_device` computation.
    """
    packets = packet_masks.shape[-1]
    results = jnp.einsum("vrc,c...->vr...", coded.x_tilde, w)    # (nr, rows, ...)
    rp = _split_rows(results, packets)
    outs, oks = [], []
    for q in range(packets):
        out_q, ok_q = _decode_on_time(
            coded.spec, results[:, q * rp:(q + 1) * rp], packet_masks[:, q]
        )
        outs.append(out_q)
        oks.append(ok_q)
    return jnp.concatenate(outs, axis=1), jnp.stack(oks)


def coded_matmul_exact_packets(
    coded: CodedDatasetModp, w, packet_masks: jnp.ndarray
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-packet EXACT GF(p) decode — the finite-field twin of
    :func:`coded_matmul_packets`.

    Same packet decoupling, same per-packet ``ok`` flags; the workers'
    products are the round's jitted ``_products_modp`` and each packet block
    runs the existing jitted ``_decode_on_time_modp`` traced-pattern device
    decode, so at ``packets=1`` with a full mask the computation — and its
    bit-exactness against the numpy modp oracle — is exactly
    :func:`~repro.core.coded_ops.coded_matmul_exact`'s.
    """
    packets = packet_masks.shape[-1]
    w = jnp.asarray(w)
    results = _products_modp(coded.x_tilde, w)
    rp = _split_rows(results, packets)
    outs, oks = [], []
    for q in range(packets):
        out_q, ok_q = _decode_on_time_modp(
            coded.spec, results[:, q * rp:(q + 1) * rp], packet_masks[:, q]
        )
        outs.append(out_q)
        oks.append(ok_q)
    out = jnp.concatenate(outs, axis=1)
    return (out[..., 0] if w.ndim == 1 else out), jnp.stack(oks)
