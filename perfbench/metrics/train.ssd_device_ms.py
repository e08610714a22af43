"""Device time of the chunked SSD per ``train_step`` run: the innermost
operations under ``jax.named_scope("mamba2.ssd")`` (the fused kernels and
the XLA operations traced inside the scope), from the traced window's
device trace.  Work the compiler fuses into an operation of a neighbouring
scope is counted there, not here."""
from harness.program import scope_ms_per_run


def read(run):
    return scope_ms_per_run(run.program, "mamba2.ssd", "train_step")
