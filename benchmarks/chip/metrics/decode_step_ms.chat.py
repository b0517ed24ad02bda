"""Model step: device time per call of the decode program (trace)."""

from chipbench.readers import module_ms_per_call


def read(ctx):
    return module_ms_per_call(ctx.trace, "decode_step")
