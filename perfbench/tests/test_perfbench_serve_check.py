"""The serving check on the tiny model: served tokens that are the model
fns' own greedy stream pass; a token altered where it is produced fails."""
import time

import numpy as np
import pytest

import tiny
from harness import serve as S
from harness.clock import CompileClock
from harness.observed import Observed
from harness.spec import load_cell


@pytest.fixture(scope="module")
def streams(tmp_path_factory):
    from repro.launch.serve import greedy_stream

    cell = load_cell("qwen3_4b.chat_unique", tiny.build(tmp_path_factory.mktemp("b")))
    obs = Observed(cell=cell.name, config=cell.config, traffic=cell.traffic, peaks={})
    run = S.Run(cell, 9, 1.0, False, None, obs, time.perf_counter(), None,
                lambda *a: None)
    run.setup()
    out = []
    for a in run.arrivals[:3]:
        toks, logits = greedy_stream(run.fns._prefill, run.fns._decode, a.prompt,
                                     a.max_new, 16)
        gen = np.concatenate([toks[len(a.prompt):], [int(np.argmax(logits[-1]))]])
        out.append((a.prompt, gen.astype(np.int32)))
    run.release()
    weights = S.SeededWeights(9, run.abstract, cell.config["init"],
                              cell.config["num_hidden_layers"])
    return cell, weights, out


def _gap(cell, weights, sample, control=False):
    seqs, want, targets = S.served_inputs(sample)
    gaps, ctrl = cell.reference().served_gaps(cell.config, weights, seqs, want, targets,
                                              control=control)
    return max(float(g.max()) for g in gaps), ctrl


def test_greedy_stream_passes_and_the_float8_control_fails(streams):
    cell, weights, sample = streams
    gap, ctrl = _gap(cell, weights, sample, control=True)
    assert gap <= cell.limits["logit_gap"]
    assert max(float(g.max()) for g in ctrl) > cell.limits["logit_gap"]


def test_an_altered_token_fails(streams):
    cell, weights, sample = streams
    prompt, gen = sample[0]
    bad = gen.copy()
    bad[len(bad) // 2] = (bad[len(bad) // 2] + 1) % int(cell.config["vocab_size"])
    gap, _ = _gap(cell, weights, [(prompt, bad)] + sample[1:])
    assert gap > cell.limits["logit_gap"]
