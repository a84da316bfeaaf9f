"""TPU-native compute ops: Pallas kernels and pipeline schedules.

No reference counterpart (``gshuichi/chainermn`` has no custom device
kernels beyond CuPy JIT pack/cast strings, SURVEY.md S2.9) — this package
holds the ops where hand-written kernels beat XLA's default lowering, plus
TPU-idiomatic extensions (microbatched pipeline schedule).
"""

from chainermn_tpu.ops.flash_attention import (
    flash_attention,
    kernels_interpreted,
    set_kernels_interpreted,
)
from chainermn_tpu.ops.pipeline import (
    init_pipeline_lm,
    jit_pp_lm_train_step,
    make_pipeline_lm,
    pipeline_apply,
    pp_lm_opt_init,
)

__all__ = [
    "flash_attention",
    "kernels_interpreted",
    "set_kernels_interpreted",
    "pipeline_apply",
    "make_pipeline_lm",
    "init_pipeline_lm",
    "pp_lm_opt_init",
    "jit_pp_lm_train_step",
]
