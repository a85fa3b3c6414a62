"""Wall time the training loop stands still per save (the whole
``train._checkpoint`` call: pull to the host, pack, every host's vote and
the resolution), median over the saves inside the window."""
import statistics


def read(ctx):
    saves = ctx.get("window_saves") or []
    if not saves:
        return None
    return 1e3 * statistics.median(s["stall_s"] for s in saves)
