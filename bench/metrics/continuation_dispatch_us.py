"""Mean queue-to-callback latency of the continuation engine over the
window of a traced run (``continuation.dispatch_latency_s``: its count and
sum are exact)."""


def read(run, metric):
    d = (run.obs or {}).get("dispatch")
    if not d or not d["count"]:
        return None
    return d["sum"] / d["count"] * 1e6
