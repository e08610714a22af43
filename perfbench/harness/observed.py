"""What a run observed, handed to every metric reader."""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional


@dataclass
class Served:
    """One request as the client saw it (seconds since the window opened;
    negative times fall in set-up)."""
    rid: int
    due: float
    prompt_len: int
    max_new: int
    submitted: Optional[float] = None
    token_times: list = field(default_factory=list)


@dataclass
class Call:
    """One call into the model fns, timed on the host."""
    kind: str               # prefill | replay | decode
    start: float
    end: float
    tokens: int             # prompt tokens covered, or rows decoded
    positions: list         # decode: each row's position; else [first position]


@dataclass
class Observed:
    cell: str
    config: dict
    traffic: dict
    peaks: dict
    window_s: float = 0.0
    setup_s: float = 0.0
    served: list = field(default_factory=list)      # [Served] due in the window
    carried: list = field(default_factory=list)     # [Served] admitted in set-up
    calls: list = field(default_factory=list)       # [Call] (traced runs)
    counters: dict = field(default_factory=dict)    # program counters, window deltas
    spans: dict = field(default_factory=dict)       # name -> [host seconds]
    steps: int = 0                                  # training steps in the window
    tokens_per_step: int = 0
    device: Optional[dict] = None                   # harness.trace.reduce_trace
    program: Optional[dict] = None                  # harness.program.reduce_program
    trace_window: Optional[tuple] = None            # (start, end) s, traced part
    profiler_s: float = 0.0                         # starting and stopping the profiler

    @property
    def busy_window_s(self) -> float:
        """The window less the time spent starting and stopping the
        profiler: the time base of rates read in a traced run."""
        return self.window_s - self.profiler_s

    def in_trace(self, call: Call) -> bool:
        if self.trace_window is None:
            return False
        lo, hi = self.trace_window
        return lo <= call.start and call.end <= hi
