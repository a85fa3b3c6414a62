"""The program's own ``pack`` spans of each save that ended without
raising, summed over its hosts (each host's share laid out in the ``CKS1``
format, one CRC32 a leaf), median over those saves."""
import statistics


def read(ctx):
    try:
        from repro import obs
    except ImportError:  # a program without its own spans
        return None
    got = [sum(p.ms for p in obs.children(c, "pack"))
           for c in obs.records("checkpoint") if c.ok]
    return statistics.median(got) if got else None
