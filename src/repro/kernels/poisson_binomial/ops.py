"""Dispatcher for the batched Poisson-binomial prefix-tail computation.

``success_tails`` is the single entry point the allocator uses:

  * ``impl="pallas"`` — the VMEM-tiled batch kernel (TPU; ``interpret=True``
    on CPU for testing).  Below ``WIDE_N`` workers, static (tuple / numpy)
    thresholds are baked into the unrolled kernel as trace-time constants
    and traced threshold ARRAYS ride a VMEM tile through its
    shape-polymorphic twin; from ``WIDE_N`` on, both take the wide body
    (worker loop rolled, count axis capped and tiled), whose compile does
    not grow with n.
  * ``impl="ref"``    — the seed ``lax.scan`` DP, batched over leading axes.
    This is the XLA path used on CPU/GPU and the oracle the kernels are
    tested against.  Thresholds may be static or traced ((..., n)-broadcast).
  * ``impl=None``     — pallas on TPU, ref elsewhere (overridable via
    ``REPRO_KERNEL_IMPL`` / ``REPRO_KERNEL_INTERPRET`` — see
    :mod:`repro.kernels.dispatch`).

Any leading batch shape is accepted; rows are flattened to (B, n) for the
kernels and reshaped back.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.dispatch import default_interpret, resolve_impl

from .kernel import success_tails_pallas, success_tails_pallas_w, success_tails_wide
from .ref import success_tails_ref

# Widths from here on take the wide body.  The unrolled bodies compile in
# time that grows faster than n (40 s, then a VMEM refusal, at n = 128 for
# a v5e) and keep all n + 1 counts live for every worker.
WIDE_N = 128


def success_tails(
    probs: jnp.ndarray,
    w,
    *,
    impl: str | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """(..., n) descending-sorted probabilities -> (..., n) prefix tails.

    ``w``: (n,) static thresholds (tuple/list/numpy) shared across rows, or
    a traced int32 array broadcastable to ``probs`` for per-row thresholds
    (heterogeneous K*/ell, mask-padded pools).
    """
    impl = resolve_impl(impl, allowed=("pallas", "ref"))
    if impl == "ref":
        return success_tails_ref(probs, jnp.asarray(w, jnp.int32))
    interpret = default_interpret(interpret)
    batch_shape = probs.shape[:-1]
    n = probs.shape[-1]
    flat = probs.reshape((-1, n)) if batch_shape else probs.reshape((1, n))
    if n >= WIDE_N or isinstance(w, jax.Array):
        w_flat = jnp.broadcast_to(
            jnp.asarray(w, jnp.int32), probs.shape
        ).reshape(flat.shape)
        kernel = success_tails_wide if n >= WIDE_N else success_tails_pallas_w
        out = kernel(flat, w_flat, interpret=interpret)
    else:
        w_static = tuple(int(v) for v in np.asarray(w).reshape(-1))
        out = success_tails_pallas(flat, w_static, interpret=interpret)
    return out.reshape(batch_shape + (n,))


__all__ = [
    "WIDE_N", "success_tails", "success_tails_pallas", "success_tails_pallas_w",
    "success_tails_ref", "success_tails_wide",
]
