"""TPU kernels (Pallas) for the payload's hot ops.

The reference has no compute kernels of any kind (SURVEY.md §2); these exist
to make the *payload* slot genuinely TPU-native: where XLA's automatic
fusion isn't enough (attention's [T, T] score materialization), a Pallas
kernel takes over.
"""

import jax


def pallas_interpret() -> bool:
    """Whether a Pallas kernel traced now runs in the interpreter.

    The one place that decision is made for the model's call sites
    (fused RMSNorm, flash attention, fused cross-entropy, paged decode
    attention): on a TPU backend it is never the interpreter — a kernel
    on the chip is a compiled ``tpu_custom_call`` or a compile error —
    and off the TPU it always is, which is what lets the CPU tests run
    the same kernels. ``chip_smoke.py`` checks the lowered programs for
    the custom call, so a regression here fails on the chip instead of
    quietly measuring the interpreter.
    """
    return jax.default_backend() != "tpu"


from kvedge_tpu.ops.attention import flash_attention  # noqa: E402
from kvedge_tpu.ops.xent import fused_xent  # noqa: E402

__all__ = ["flash_attention", "fused_xent", "pallas_interpret"]
