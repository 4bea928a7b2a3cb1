"""The program's spans and compile counter (``repro.obs``), and the scopes
of the five scheduler stages in the slot program."""
import json
import pathlib
import re
import time

import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.core import DS, CocktailConfig, FleetEngine, SliceJob

PAPER = json.loads((pathlib.Path(__file__).resolve().parents[1]
                    / "bench" / "configs" / "cocktail-paper.json").read_text())
STAGES = ("network", "collection", "allocation", "pairing", "update")


def _paper_engine(seed=1):
    """One slice of the paper's Sec. IV-C simulation (20 CUs x 5 ECs)."""
    c, sl = PAPER, PAPER["slice"]
    cfg = CocktailConfig(n_cu=c["n_cu"], n_ec=c["n_ec"],
                         pair_iters=c["pair_iters"],
                         f_base=tuple(c["f_base"]), seed=seed, **sl)
    return FleetEngine.from_jobs([SliceJob(cfg, spec=DS)])


@pytest.fixture(scope="module")
def paper():
    eng = _paper_engine()
    return eng, eng.init()


def test_span_records_name_and_duration():
    obs.reset()
    t0 = time.perf_counter_ns()
    with obs.span("test.block"):
        time.sleep(0.002)
    t1 = time.perf_counter_ns()
    (start, dur), = obs.spans("test.block")
    assert t0 <= start and start + dur <= t1
    assert dur >= 2_000_000
    assert obs.spans("test.other") == []


def test_span_records_when_the_block_raises():
    obs.reset()
    with pytest.raises(ValueError):
        with obs.span("test.raises"):
            raise ValueError("boom")
    assert len(obs.spans("test.raises")) == 1


def test_ring_is_bounded():
    obs.reset()
    for _ in range(obs.RING + 10):
        with obs.span("test.ring"):
            pass
    got = obs.spans("test.ring")
    assert len(got) == obs.RING
    assert got == sorted(got)  # oldest first, the newest kept


def test_run_adds_one_span_and_lower_none(paper):
    eng, state = paper
    obs.reset()
    jax.block_until_ready(eng.run(1, state))
    assert len(obs.spans("fleet.run")) == 1
    eng.lower(1, state)
    assert len(obs.spans("fleet.run")) == 1
    jax.block_until_ready(eng.run(1, state))
    assert len(obs.spans("fleet.run")) == 2


def test_from_jobs_and_init_spans():
    obs.reset()
    _paper_engine(seed=2).init()
    assert len(obs.spans("fleet.from_jobs")) == 1
    assert len(obs.spans("fleet.init")) == 1


def test_compile_listener_counts_a_fresh_compile_once():
    f = jax.jit(lambda x: jnp.sin(x) * 3.0 + 1.0)
    x = jnp.arange(7.0)
    obs.reset()
    f(x).block_until_ready()
    events = {ev for _, ev, _ in obs.compile_events()}
    assert {obs.TRACE, obs.LOWER, obs.COMPILE} <= events
    assert all(s >= 0 for _, _, s in obs.compile_events())
    obs.reset()
    f(x).block_until_ready()
    assert obs.compile_events() == []


def test_no_compile_in_the_slot_window():
    """The benchmark's window: after set-up and 3 warm slots, one
    ``run(1, state)`` per slot compiles nothing."""
    eng = _paper_engine(seed=3)
    state = eng.init()
    for _ in range(3):
        state, rec = eng.run(1, state)
        jax.block_until_ready((state, rec))
    obs.reset()
    for _ in range(20):
        state, rec = eng.run(1, state)
        jax.block_until_ready((state, rec))
    assert obs.compile_events() == []
    assert len(obs.spans("fleet.run")) == 20


def test_slot_program_scopes_the_five_stages(paper):
    eng, state = paper
    text = eng.lower(1, state).as_text(debug_info=True)
    names = set(re.findall(r'loc\("([^"]*)"', text))
    for stage in STAGES:
        rx = re.compile(rf"(^|/|\(){stage}(\)|/)")
        assert any(rx.search(n) for n in names), stage


def test_decision_assembly_runs_at_highest_precision(paper):
    """The einsums that assemble x and y (``_compose_from_match``, in the
    pairing stage) ask for float32 whatever JAX's default matmul precision:
    at the default the TPU rounds their operands to bfloat16."""
    eng, state = paper
    text = eng.lower(1, state).as_text(debug_info=True)
    locs = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    dots = re.findall(r"stablehlo\.dot_general .*loc\((#loc\d+)\)$", text, re.M)
    lines = [l for l in text.splitlines() if "stablehlo.dot_general" in l]
    assembly = [l for l, loc in zip(lines, dots)
                if re.search(r"(^|/|\()pairing(\)|/)", locs.get(loc, ""))]
    assert len(lines) == len(dots)
    assert len(assembly) == 6  # 2 two-operand einsums + 2 three-operand (2 dots each)
    for line in assembly:
        assert "precision = [HIGHEST, HIGHEST]" in line, line


def test_gauge_keeps_the_last_value_and_reset_clears_it():
    obs.reset()
    obs.gauge("test.g", 3)
    obs.gauge("test.g", 5)
    assert obs.gauges() == {"test.g": 5}
    obs.gauges()["test.g"] = 7  # a copy
    assert obs.gauges() == {"test.g": 5}
    obs.reset()
    assert obs.gauges() == {}


def test_from_jobs_gauges_a_uniform_fleet_as_all_real():
    """Eight slices of the paper's 20 x 5: nothing is padding."""
    obs.reset()
    c, sl = PAPER, PAPER["slice"]
    FleetEngine.from_jobs([SliceJob(CocktailConfig(
        n_cu=c["n_cu"], n_ec=c["n_ec"], pair_iters=c["pair_iters"],
        f_base=tuple(c["f_base"]), seed=s, **sl), spec=DS) for s in range(8)])
    assert obs.gauges() == {"fleet.links_real": 800, "fleet.links_compiled": 800,
                            "fleet.pairs_real": 80, "fleet.pairs_compiled": 80}
    obs.reset()
