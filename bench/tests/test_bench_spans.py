"""The program's ``serve.`` spans: taken from a real profiler trace of a
small serve loop on the CPU, and from a fixture (one device, a 2 ms
window, times in ns) through the five readers that reduce them, against
hand-worked values."""
import json
import types
from pathlib import Path

import numpy as np
import pytest

from harness import common, spans, trace

FIXTURE = Path(__file__).parent / "fixtures" / "serve_spans_small.json"
READERS = ("admission_wait_p95_ms", "admit_host_ms_p50", "admit_idle_ms_mean",
           "decode_host_gap_ms_p50", "decode_kv_useful_pct")


def fixture_rec(span_items=None):
    d = json.loads(FIXTURE.read_text())
    rec = types.SimpleNamespace(trace=trace.Trace.from_json(d),
                                trace_dir=None)
    rec._spans = spans.from_json(d["spans"] if span_items is None
                                 else span_items)
    return rec


def test_serve_spans_come_back_from_a_real_profiler_trace(tmp_path):
    import jax

    from repro.configs.registry import reduced_config
    from repro.models import transformer as tf
    from repro.runtime.admission import AdmissionQueue
    from repro.runtime.serve_loop import ContinuousServeLoop, Request

    cfg = reduced_config("llama3.2-1b").with_(n_layers=1, vocab=64)
    params = jax.jit(lambda k: tf.init_params(k, cfg))(
        jax.random.PRNGKey(0))
    loop = ContinuousServeLoop(cfg, params, slots=4, max_len=32)
    queue = AdmissionQueue()
    for rid, n in ((0, 5), (1, 11)):
        queue.push(Request(rid=rid, prompt=np.arange(n, dtype=np.int32),
                           max_new_tokens=4))
    # warm the programs, then trace two admits and three decode steps
    loop.admit(Request(rid=-1, prompt=np.arange(5, dtype=np.int32),
                       max_new_tokens=1))
    loop.admit(Request(rid=-2, prompt=np.arange(11, dtype=np.int32),
                       max_new_tokens=1))
    loop.decode_step()
    jax.block_until_ready(loop.serve_state()["cur"])
    want = []                       # the harness's context of each step
    with jax.profiler.trace(str(tmp_path)):
        while len(queue):
            loop.admit(queue.pop())
        for _ in range(3):
            want.append(sum(int(loop._plen[i] + loop._t[i] + 1)
                            for i in range(loop.slots)
                            if loop._reqs[i] is not None))
            loop.decode_step()
        jax.block_until_ready(loop.serve_state()["cur"])
    got = spans.load(str(tmp_path))
    names = [n for n, *_ in got]
    assert names.count("serve.admit") == 2
    for child in ("serve.admit.prepare", "serve.admit.dispatch"):
        assert names.count(child) == 2
    assert names.count("serve.decode_step") == 3
    for child in ("serve.decode.sync", "serve.decode.dispatch",
                  "serve.decode.select"):
        assert names.count(child) == 3
    admits = [a for n, _, _, a in got if n == "serve.admit"]
    assert [(a["rid"], a["plen"], a["bucket"], a["slot"]) for a in admits] \
        == [(0, 5, 8, 0), (1, 11, 16, 1)]
    assert all(a["queued_us"] > 0 for a in admits)
    steps = [a for n, _, _, a in got if n == "serve.decode_step"]
    assert [a["lanes"] for a in steps] == [2, 2, 2]
    assert [a["ctx_tokens"] for a in steps] == want == [18, 20, 22]
    assert all(a["kv_positions"] == 4 * 32 for a in steps)
    # each child lies inside its parent
    rec = types.SimpleNamespace(_spans=got)
    parents = [s for s in got if s[0] == "serve.decode_step"]
    for name in ("serve.decode.sync", "serve.decode.dispatch"):
        for p, c in zip(parents, spans.children(rec, parents, name)):
            assert c is not None and p[1] <= c[1]
            assert spans.end(c) <= spans.end(p)


@pytest.mark.parametrize("name,want", [
    # queued 5000, 12000 and 3000 us in the window (one admit without a
    # queue wait, one before the window): p95 = 5000 + 0.9 * 7000 us
    ("admission_wait_p95_ms", 11.3),
    # admits of 200, 100, 20 and 40 us in the window
    ("admit_host_ms_p50", 0.07),
    # idle under them: 80 + 20, 100, 20 and 40 us over four admits
    ("admit_idle_ms_mean", 0.065),
    # sync end to dispatch end: 60, 40 and 20 us
    ("decode_host_gap_ms_p50", 0.04),
    # context 30 + 50 + 80 over 3 x 400 positions
    ("decode_kv_useful_pct", 100 * 160 / 1200),
])
def test_span_readers_on_the_fixture(name, want):
    got = common.Manifest().reader(name).read(fixture_rec())
    assert got == pytest.approx(want)


@pytest.mark.parametrize("name", READERS)
def test_span_readers_read_nothing_without_program_spans(name):
    # a trace of a program that writes no serve span (the parent of
    # these readers) reads None, and so does an untraced run
    assert common.Manifest().reader(name).read(fixture_rec([])) is None
    untraced = types.SimpleNamespace(trace=None, trace_dir=None)
    assert common.Manifest().reader(name).read(untraced) is None
