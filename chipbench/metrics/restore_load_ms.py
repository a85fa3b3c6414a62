"""The program's own ``load`` span of each restore that ended without
raising: reading every host's payload and unpacking it on the host, mean
over the resumes."""
import statistics


def read(ctx):
    try:
        from repro import obs
    except ImportError:  # a program without its own spans
        return None
    got = [x.ms for r in obs.records("restore") if r.ok
           for x in obs.children(r, "load")]
    return statistics.mean(got) if got else None
