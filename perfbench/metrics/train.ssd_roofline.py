"""The chunked SSD's share of its roofline: the least time the chip needs
for the SSD's forward and backward in one step (the family's
``ssd_train_cost``: operations at the bf16 peak, or the bytes of ``x``,
``dt``, ``B``, ``C``, ``y`` and their gradients at HBM's peak, whichever
takes longer), over the device time under the ``mamba2.ssd`` scope per
``train_step`` run.  At mamba2-780m's batch 4 x 2048 the bytes bound it:
1.29e10 bytes need 15.8 ms at 819 GB/s, 2.83e12 operations 14.3 ms at
197 TFLOP/s."""
from harness import counts as C
from harness.peaks import least_seconds
from harness.program import scope_ms_per_run


def read(run):
    ms = scope_ms_per_run(run.program, "mamba2.ssd", "train_step")
    if not ms:
        return None
    tr = run.traffic
    cost = C.family(run.config).ssd_train_cost(run.config, int(tr["batch"]), int(tr["seq"]))
    return 100.0 * least_seconds(*cost, run.peaks) / (ms / 1000.0)
