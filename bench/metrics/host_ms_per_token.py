"""Host milliseconds that runtime tasks ran per token emitted, over the
window of a traced run: the sum of the ``repro.obs`` ``task``/``run``
spans over the tokens emitted."""


def read(run, metric):
    if not run.obs or run.obs["task_run_s"] is None:
        return None
    n = len(run.emitted(*run.window))
    return run.obs["task_run_s"] / n * 1e3 if n else None
