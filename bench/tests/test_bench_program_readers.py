"""The readers of the program's own spans and counters (``repro.obs``):
the numbers they read from a filled ring and a window's counts, and
nothing from an empty ring, a program without spans, or spans that cannot
be the window's."""
from __future__ import annotations

import pathlib
import sys
import time

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402

harness.use_src()

from repro import obs  # noqa: E402

import program_obs  # noqa: E402

NAMES = ("dispatch_ms.fleet", "host_wait_ms.fleet", "setup_compile_s.fleet",
         "setup_init_s.fleet")
READERS = {n: harness.load_module(BENCH / "metrics" / f"{n}.py") for n in NAMES}
MS = 1_000_000  # ns


def _fill(monkeypatch, spans, compiles):
    monkeypatch.setattr(obs, "spans", lambda name: list(spans.get(name, [])))
    monkeypatch.setattr(obs, "compile_events", lambda: list(compiles))


def _ctx(latency_s):
    return {"counts": {"slots": len(latency_s), "latency_s": list(latency_s),
                       "slices": 1}}


@pytest.fixture
def filled(monkeypatch):
    """Set-up: from_jobs at 0-100 ms, init at 100-300 ms; warm-up calls at
    1000 and 1010 ms; the window's 4 calls at 2000 + 10 k ms, each 0.5 ms
    of dispatch in a 2 ms call. Compile phases: a trace of 0.4 s holding a
    nested one of 0.1 s, a lowering of 0.2 s and a compile of 0.3 s, all
    before the window; a cache load inside the compile; one compile after
    the window's start, which set-up does not count."""
    window = [(2000 * MS + 10 * k * MS, MS // 2) for k in range(4)]
    spans = {"fleet.from_jobs": [(0, 100 * MS)],
             "fleet.init": [(100 * MS, 200 * MS)],
             "fleet.run": [(1000 * MS, 3 * MS), (1010 * MS, MS)] + window}
    compiles = [(600 * MS, obs.TRACE, 0.1),  # nested in the next
                (700 * MS, obs.TRACE, 0.4),
                (900 * MS, obs.LOWER, 0.2),
                (1190 * MS, obs.CACHE_LOAD, 0.05),
                (1200 * MS, obs.COMPILE, 0.3),
                (2500 * MS, obs.COMPILE, 0.7)]
    _fill(monkeypatch, spans, compiles)
    return _ctx([0.002] * 4)


def test_program_readers(filled):
    assert READERS["dispatch_ms.fleet"].read(filled) == pytest.approx(0.5)
    assert READERS["host_wait_ms.fleet"].read(filled) == pytest.approx(1.5)
    # trace 0.3-0.7 s, lowering 0.7-0.9 s, compile 0.9-1.2 s: 0.9 s in all
    assert READERS["setup_compile_s.fleet"].read(filled) == pytest.approx(0.9)
    assert READERS["setup_init_s.fleet"].read(filled) == pytest.approx(0.3)


def test_dispatch_and_wait_make_up_the_host_time():
    """With the program's real spans: dispatch + wait is the mean latency."""
    obs.reset()
    lat = []
    for _ in range(5):
        t = time.perf_counter()
        with obs.span("fleet.run"):
            time.sleep(0.001)
        time.sleep(0.002)
        lat.append(time.perf_counter() - t)
    c = _ctx(lat)
    d = READERS["dispatch_ms.fleet"].read(c)
    w = READERS["host_wait_ms.fleet"].read(c)
    assert d >= 1.0 and w >= 2.0
    assert d + w == pytest.approx(1e3 * sum(lat) / len(lat))
    obs.reset()


@pytest.mark.parametrize("name", NAMES)
def test_empty_ring_reads_nothing(name):
    obs.reset()
    assert READERS[name].read(_ctx([0.002] * 4)) is None


@pytest.mark.parametrize("name", NAMES)
def test_too_few_spans_read_nothing(name, filled):
    assert READERS[name].read(_ctx([0.002] * 7)) is None


@pytest.mark.parametrize("name", NAMES)
def test_spans_longer_than_their_calls_read_nothing(name, filled):
    """Spans that outlast the window's calls are not the window's."""
    assert READERS[name].read(_ctx([0.0001] * 4)) is None


@pytest.mark.parametrize("name", NAMES)
def test_program_without_spans_reads_nothing(name, filled, monkeypatch):
    """An older program (no ``repro.obs``) gives nothing and raises
    nothing."""
    monkeypatch.setattr(program_obs, "program_obs", lambda: None)
    assert READERS[name].read(filled) is None


def test_program_obs_import_fails_cleanly(monkeypatch):
    import repro
    monkeypatch.delattr(repro, "obs")
    monkeypatch.setitem(sys.modules, "repro.obs", None)  # import raises
    assert program_obs.program_obs() is None


def test_union_counts_overlaps_once():
    assert program_obs.union_s([]) == 0.0
    assert program_obs.union_s([(0, 2e9), (1e9, 3e9), (5e9, 6e9),
                                (5.5e9, 5.6e9)]) == pytest.approx(4.0)
