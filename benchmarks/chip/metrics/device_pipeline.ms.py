"""Device milliseconds per call of the one-jit device program
(pipeline.build_device_fn), from the trace: the programs that ran inside
the engine's `device_pipeline` span."""


def read(ctx):
    return ctx.module_ms("device_pipeline")
