"""Device milliseconds under the ``repro.decode`` scope per coded round:
gathering the K* received results and the exact decode GEMM."""


def read(view):
    ns = view.scope_ns("repro.decode")
    if ns <= 0:
        return None
    return ns / (view.n_calls * view.info["rounds_per_call"]) * 1e-6
