"""The program's own spans and counters, as the benchmark sees them: CPU
only.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q

On two traces recorded on a TPU v5e (a super-tick chunk; the end of a
serving run's call with its requests) they check that the program's
``repro.*`` spans leave the older readers as they read, that the spans
name the host's work under every idle gap at a chunk boundary, that the
serving spans and their device programs keep their order across the
trace's two clocks, and that the readers of the serving counters and of
the ``obs.row_gather`` scope read numbers where the program has them and
nothing where it has not.
"""

from __future__ import annotations

import bisect
import gzip
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench import readers, spec, trace_reduce, workcount  # noqa: E402

SUPERTICK_CUT = "tpu_v5e_supertick.xplane.pb.gz"
SERVE_CUT = "tpu_v5e_serve_cut.xplane.pb.gz"

# The 13 readers older than the program's spans, on each recorded trace
# with the context of ``_ctx``.
BEFORE = {
    SUPERTICK_CUT: {
        "device_idle_pct": 96.43314301130282, "supertick_ms": 4.538801937500001,
        "supertick_mfu": 0.2082168285967303, "span_ms.wake_sample": 0.8865421874999998,
        "span_ms.gather_mix": 1.6574066250000006, "span_ms.row_update": 0.3046344374999999,
        "span_ms.scatter": 0.05744631250000001, "gather_mix_roofline": 0.09429680920224162,
        "row_update_roofline": 2.557159280453246, "publish_ms": 60.0, "predict_device_ms": None,
        "predict_host_ms": None, "gen_late_p99_ms": 3.96,
    },
    SERVE_CUT: {
        "device_idle_pct": 12.717233137792139, "supertick_ms": 2.6132531875000002,
        "supertick_mfu": 0.36163925852092543, "span_ms.wake_sample": 0.4433483749999999,
        "span_ms.gather_mix": 0.8966161875, "span_ms.row_update": 0.17133143749999993,
        "span_ms.scatter": 0.032315562500000006, "gather_mix_roofline": 0.17430887203132975,
        "row_update_roofline": 4.546735790965271, "publish_ms": 60.0,
        "predict_device_ms": 0.0422652, "predict_host_ms": 2.943386666666667,
        "gen_late_p99_ms": 3.96,
    },
}
NEW_READERS = ("publish_sync_ms", "predict_route_ms", "predict_fetch_ms", "span_ms.row_gather")

# A v5e trace puts host and device events on one clock only to within
# about a millisecond: serving programs start up to 1.05 ms before the
# span that dispatched them began.
SKEW = 2_000_000  # ns
FETCH_AFTER = 1_000_000  # ns: a request's fetch opens this soon after its score span


def _events(name, tmp_path):
    path = tmp_path / name.replace(".gz", "")
    with gzip.open(DATA / name, "rb") as f:
        path.write_bytes(f.read())
    return trace_reduce.events(str(path))


def _ctx(reduced, slots=16):
    """A reader's context around a reduced trace: fixed work, requests
    and serving counters, ``slots`` super-ticks of 1,000 updates."""
    work = workcount.rate_weighted_mean(
        workcount.per_update(np.array([60.0, 90.0]), np.array([12.0, 20.0]), 20), np.ones(2)
    )
    c0 = {"serve_requests": 10, "serve_snapshots_published": 4, "serve_publish_s_total": 0.2,
          "serve_publish_sync_s_total": 0.19, "serve_route_s_total": 0.01,
          "serve_fetch_s_total": 0.3}
    c1 = {"serve_requests": 30, "serve_snapshots_published": 9, "serve_publish_s_total": 0.5,
          "serve_publish_sync_s_total": 0.47, "serve_route_s_total": 0.03,
          "serve_fetch_s_total": 1.1}
    return {
        "device_kind": "TPU v5 lite", "window": {}, "work": work,
        "client": {"latency_s": np.linspace(0.01, 0.09, 50), "late_s": np.linspace(0.0, 0.004, 50)},
        "serve_counters": (c0, c1), "trace": reduced, "traced": {"applied": 1000 * slots, "slots": slots},
    }


def _host(evs, name):
    return sorted((e["t"], e["t"] + e["u"]) for e in evs if e["k"] == "host" and e["n"] == name)


def _pairs(evs):
    """Each ``repro.serve.score`` span with its ``_score_rows`` program,
    by dispatch order: the device runs its programs in the order they were
    dispatched, so the k-th span to end goes with the k-th program to
    start, counted from the first program whose request the trace holds.
    That first program is the one that fits best: a program fits when it
    starts after its span began and ends before the request's
    ``repro.serve.fetch`` (the next fetch to open) ended, to within
    ``SKEW``. ``[((span), (program), (fetch)), ...]`` in ns."""
    runs = sorted((e["t"], e["t"] + e["u"]) for e in evs
                  if e["k"] == "module" and readers.SCORE_PROGRAM in e["n"])
    spans = sorted(_host(evs, "repro.serve.score"), key=lambda s: s[1])
    fetches = _host(evs, "repro.serve.fetch")
    starts = [a for a, _ in fetches]

    def fetch(span):
        i = bisect.bisect_left(starts, span[1])
        assert i < len(fetches) and fetches[i][0] - span[1] <= FETCH_AFTER, span
        return fetches[i]

    own = [fetch(s) for s in spans]

    def misfits(early):
        return sum(run[0] < span[0] - SKEW or run[1] > f[1] + SKEW
                   for span, f, run in zip(spans, own, runs[early:]))

    early = min(range(len(runs) + 1), key=lambda e: (misfits(e), e))
    assert misfits(early) == 0
    return [(span, run, f) for span, f, run in zip(spans, own, runs[early:])]


@pytest.mark.parametrize("fixture", [SUPERTICK_CUT, SERVE_CUT])
def test_existing_readers_read_as_before(fixture, tmp_path):
    """The program's ``repro.`` spans move none of the 13 older readers:
    each reads its recorded value on the trace with those spans and on
    the trace with them taken out."""
    evs = _events(fixture, tmp_path)
    red = trace_reduce.reduce(evs)
    bare = trace_reduce.reduce([e for e in evs if not e["n"].startswith("repro.")])
    for name, before in BEFORE[fixture].items():
        read = spec.reader(name)
        assert read(_ctx(red)) == before, name
        assert read(_ctx(bare)) == before, name


def test_recorded_serve_trace_names_the_host_under_every_chunk_gap(tmp_path):
    """A cut of a traced run of the serve cell on a TPU v5e (the chunk
    programs' and serving programs' device events, their HLO, and the
    host's spans, 48 ms around the end of one ``run()`` call): it holds
    the run driver's and the serving tier's spans, and a ``repro.`` span
    lies over every idle gap between the two chunks."""
    evs = _events(SERVE_CUT, tmp_path)
    assert len(_host(evs, "repro.run.result")) == 1
    assert len(_host(evs, "repro.serve.score")) >= 2
    for name in ("repro.run", "repro.run.advance", "repro.serve.publish.sync"):
        assert _host(evs, name), name
    dev = trace_reduce.reduce(evs)["devices"][0]
    chunks = sorted((a, b) for name, a, b in dev["modules"] if "_chunk_impl" in name)
    assert len(chunks) >= 2
    end, start = chunks[0][1], chunks[1][0]
    busy = [(max(a, end), min(b, start)) for a, b in dev["cover"].iv if a < start and b > end]
    edges = [end, *[x for iv in busy for x in iv], start]
    gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    program = [(e["t"], e["t"] + e["u"]) for e in evs
               if e["k"] == "host" and e["n"].startswith("repro.")]
    assert gaps
    for a, b in gaps:
        assert any(min(b, y) > max(a, x) for x, y in program), (a, b)


def test_score_spans_and_their_programs_are_causal_across_clocks(tmp_path):
    """On the recorded trace, each request's serving program starts after
    its ``repro.serve.score`` span began and ends before its
    ``repro.serve.fetch`` ended: to within the clocks' skew for an idle
    device, which ran the program at once; strictly for a program queued
    behind a chunk, which starts after the span ended."""
    pairs = _pairs(_events(SERVE_CUT, tmp_path))
    assert len(pairs) >= 2
    for span, run, fetch in pairs:
        assert span[0] - SKEW <= run[0] and run[1] <= fetch[1] + SKEW
    queued = [(span, run, fetch) for span, run, fetch in pairs if run[0] - span[1] > SKEW]
    assert queued  # one request waited behind the chunk
    for span, run, fetch in queued:
        assert span[1] < run[0] < run[1] < fetch[1]


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_readers_return_numbers(name, tmp_path):
    red = trace_reduce.reduce(_events(SERVE_CUT, tmp_path))
    value = spec.reader(name)(_ctx(red))
    assert value is not None and np.isfinite(value) and value >= 0.0


def test_new_counter_readers_read_the_window():
    ctx = _ctx(None)
    assert spec.reader("publish_sync_ms")(ctx) == pytest.approx(1e3 * 0.28 / 5)
    assert spec.reader("predict_route_ms")(ctx) == pytest.approx(1e3 * 0.02 / 20)
    assert spec.reader("predict_fetch_ms")(ctx) == pytest.approx(1e3 * 0.8 / 20)


def test_new_readers_read_nothing_from_a_program_without_them():
    """A program with no such counters or scope: each new reader returns
    None and does not raise."""
    c = {"serve_requests": 5, "serve_snapshots_published": 2, "serve_publish_s_total": 0.1}
    ctx = _ctx({"devices": {0: {"scope_s": {"row_update": 0.01}, "modules": [], "cover": None,
                                "module_s": {}, "busy_s": 0.0}},
                "host_spans": [("bench.predict", "t", 0, 5)], "window_s": 1.0, "busy_s": 0.0})
    ctx["serve_counters"] = (c, dict(c, serve_requests=9, serve_snapshots_published=4))
    for name in NEW_READERS:
        assert spec.reader(name)(ctx) is None, name
