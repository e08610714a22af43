"""``decode_jit``'s share of its roofline: for every call in the traced part,
the least time its bytes (weights once, keys and values of live positions)
or operations need at the chip's peaks, over the program's device time.
Nothing where the trace and the host disagree on the number of calls."""
from harness import counts as C
from harness.peaks import least_seconds
from harness.readers import decode_invocations


def read(run):
    d = run.device
    if not d or not d["program_s"].get("decode_jit"):
        return None
    calls = decode_invocations(run)
    if abs(len(calls) - d["program_calls"].get("decode_jit", 0)) > 2 or not calls:
        return None
    least = sum(least_seconds(*C.decode_call(run.config, pos), run.peaks) for pos in calls)
    return 100.0 * least / d["program_s"]["decode_jit"]
