from repro.kernels.ops import (decode_attention, flash_attention, fused_mlp,
                               moe_gmm, paged_decode_attention,
                               resolve_backend)

__all__ = ["decode_attention", "flash_attention", "fused_mlp", "moe_gmm",
           "paged_decode_attention", "resolve_backend", "analyzable_kernels"]


def analyzable_kernels() -> dict:
    """name -> zero-arg builder returning ``(fn, args, kwargs)`` for one
    representative call of each Pallas kernel — the enumeration the static
    kernel verifier (``repro.analysis.pallas_lint``) walks. A new kernel
    is added here once and inherits the in-bounds / MXU-alignment /
    scalar-prefetch gates for free."""
    # importlib: the function re-exports above shadow the submodule names
    import importlib
    _da = importlib.import_module("repro.kernels.decode_attention")
    _fa = importlib.import_module("repro.kernels.flash_attention")
    _fm = importlib.import_module("repro.kernels.fused_mlp")
    _mg = importlib.import_module("repro.kernels.moe_gmm")
    _pd = importlib.import_module("repro.kernels.paged_decode_attention")
    return {
        "flash_attention": _fa.analysis_example,
        "fused_mlp": _fm.analysis_example,
        "moe_gmm": _mg.analysis_example,
        "decode_attention": _da.analysis_example,
        "paged_decode_attention": _pd.analysis_example,
    }
