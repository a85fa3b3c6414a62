"""The host's part of each training step, during which the device waits:
the program's own ``step`` span less its ``loss_sync`` child (the wait for
the step's loss), median over the steps that ended without raising."""
import statistics


def read(ctx):
    try:
        from repro import obs
    except ImportError:  # a program without its own spans
        return None
    synced = {x.parent: x.ms for x in obs.records("loss_sync")}
    got = [s.ms - synced[s.id] for s in obs.records("step")
           if s.ok and s.id in synced]
    return statistics.median(got) if got else None
