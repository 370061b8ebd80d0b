"""Step: the model FLOPs one training step requires (``work.step_work``),
over the step's host-clock time and the chip's peak.  The peak is the
bf16 one, the only FLOP peak published for a TPU v5e, though the step
runs in float32."""


def read(ctx):
    work, step_s = ctx.get("work"), ctx.get("step_s")
    if not work or not step_s:
        return None
    return 100.0 * work["flops"] / (step_s * ctx["peaks"]["flops_bf16"])
