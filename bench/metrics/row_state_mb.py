"""Megabytes of state one row of the decode cache holds, KV caches and
recurrent (SSM) state together: the program's ``serving.row_state_bytes``
gauges (label ``kind``), which the adapter sets when it allocates its cache
and at every handoff into a row."""

GAUGE = "serving.row_state_bytes{"


def read(run, metric):
    from repro.obs.registry import REGISTRY
    values = [v["value"] for name, v in REGISTRY.as_dict().items()
              if name.startswith(GAUGE)]
    return sum(values) / 1e6 if values and sum(values) > 0 else None
