"""Host time of a resume's ``latest_committed`` (which terminates the
in-flight epoch) and ``restore_params`` (read, unpack, put on the device),
mean over the run's resumes."""


def read(ctx):
    latest, restores = ctx.get("latest") or [], ctx.get("restores") or []
    if not latest or len(latest) != len(restores):
        return None
    return 1e3 * sum(a["secs"] + b["secs"]
                     for a, b in zip(latest, restores)) / len(latest)
