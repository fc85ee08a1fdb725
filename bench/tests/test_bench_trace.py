"""The reduction from a trace to numbers, on a small trace kept as a
fixture (one device, a 1 ms window, times in ns), against hand-worked
values."""
import json
import types
from pathlib import Path

import pytest

from harness import serve, trace

FIXTURE = Path(__file__).parent / "fixtures" / "trace_small.json"


@pytest.fixture
def tr():
    return trace.Trace.from_json(json.loads(FIXTURE.read_text()))


def test_busy_is_the_union_of_op_intervals_inside_the_window(tr):
    # [1.0, 1.3] (two overlapping ops and one that starts before the
    # window), [1.305, 1.5], [1.7, 1.8], [1.95, 2.0] ms: 0.645 ms
    assert trace.busy_s(tr) == pytest.approx(645e-6)
    assert tr.window_s == pytest.approx(1e-3)
    assert trace.idle_share(tr) == pytest.approx(0.355)


def test_breakdown_ranks_ops_and_labels_gaps_by_host_activity(tr):
    b = trace.breakdown(tr)
    ops = dict(b["device_ops"])
    # by program and op kind; the while loop that contains ops is not
    # ranked; fusions 1 and 2 (200 us each), 3 (20), 4 and 0 (50 each
    # after clipping to the window)
    assert b["device_ops"][0] == ["jit_serve_step:fusion",
                                  pytest.approx(520e-6)]
    assert ops == pytest.approx({
        "jit_serve_step:fusion": 520e-6,
        "jit_admit:flash_attention": 100e-6,
        "jit_serve_step:all-reduce": 100e-6,
        "jit_admit:moe_expert_ffn": 95e-6})
    gaps = dict(b["idle_gaps"])
    # [1.5, 1.7] under the decode step's span; [1.8, 1.95] mostly under
    # the idle span (130 us) rather than the admit's (20 us); the 5 us
    # gap between two programs' ops is not put down to the host
    assert gaps == pytest.approx({"bench.decode_step": 200e-6,
                                  "bench.idle": 150e-6,
                                  "device: between ops": 5e-6})
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_program_runs_are_those_wholly_inside_the_window(tr):
    # one started before the window, one was still running at its close
    runs = trace.program_runs(tr, "jit_serve_step")
    assert [s for _, s, _ in runs] == [1000000, 1700000]


def test_serve_runs_pair_with_the_host_dispatches_they_ran_for(tr):
    # host clock: the traced span opened at 10.0 s.  The device ran
    # decode, admit, decode wholly inside the window (and a decode cut
    # short by its close); the host dispatched decode, decode, admit,
    # decode, decode: the runs are the second to the fourth, the first
    # decode having run before the window
    rec = types.SimpleNamespace(
        trace=tr, trace_host=(10.0, 11.0),
        serve=types.SimpleNamespace(dispatch=[
            (9.9990, "decode", [4]), (9.9995, "decode", [5]),
            (10.0003, "admit", 7), (10.0004, "decode", [6, 8]),
            (10.0009, "decode", [7, 9])]))
    runs = serve.traced_runs(rec)
    assert [(k, w) for k, w, _, _ in runs] == [
        ("decode", [5]), ("admit", 7), ("decode", [6, 8])]
    # a kernel's time is found by its name, inside the runs of a program
    assert serve.kernel_in_runs(rec, "flash_attention", "admit") == \
        pytest.approx(100e-6)
    assert serve.kernel_in_runs(rec, "moe_expert_ffn", "admit") == \
        pytest.approx(95e-6)
    assert serve.kernel_in_runs(rec, "moe_expert_ffn", "decode") == 0.0
