"""Model FLOPs of one train step over the device time of one execution of
the jitted step (median over the traced window), over the chip's bf16 peak.
The program trains in float32 at default precision, one bf16 pass per
matmul on the TPU, so the bf16 peak is its ceiling."""
import statistics


def read(ctx):
    r = ctx.get("trace")
    if r is None:
        return None
    runs = [t for name, ts in r.modules.items() if "train_step" in name
            for t in ts]
    if not runs:
        return None
    return 100.0 * ctx["flops_per_step"] / statistics.median(runs) \
        / ctx["peaks"]["bf16_flops_per_s"]
