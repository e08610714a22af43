"""Host time in ``TokenPipeline.batch_for_step`` (a ``fix.local`` shard
recipe) per step of the window."""


def read(run):
    return 1000.0 * run.spans["batch_for_step_s"] / run.steps if run.steps else None
