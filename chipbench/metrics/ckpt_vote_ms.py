"""The program's own ``CheckpointOutcome.vote_ms`` (upload and LogOnce of
every host), median over the saves inside the window."""
import statistics


def read(ctx):
    saves = [s for s in ctx.get("window_saves") or [] if s["outcome"]]
    if not saves:
        return None
    return statistics.median(s["outcome"].vote_ms for s in saves)
