from .ops import (  # noqa: F401
    WIDE_N,
    success_tails,
    success_tails_pallas,
    success_tails_pallas_w,
    success_tails_ref,
    success_tails_wide,
)
