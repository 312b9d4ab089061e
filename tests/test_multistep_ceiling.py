"""The fused decode block's ceiling (ISSUE 42): the smallest minted K whose
dispatch outlasts the tick loop's own work per tick by the margin, worked
out from two running means the loop keeps whether it is watched or not.
The controller alone (no device), then the engine around it: pressure,
the DYN_MULTISTEP pins, the warm-up that still runs every minted K, and
tokens identical whatever the ceiling."""

import asyncio
import inspect
import re

import pytest

from dynamo_tpu.engine import multistep
from dynamo_tpu.engine.engine import JaxEngine
from dynamo_tpu.engine.multistep import FusedStepCeiling

from tests.test_jax_engine import collect, make_engine, req
from tests.test_multistep_decode import ms_env  # noqa: F401  (fixture)

MS = 1e-3

# (service ms a decode step, the loop's ms a tick) of the five cells, ledger
# 41 (`step.decode_step_mean_ms`, `tick.host_work_ms_per_dispatch`), and a
# model whose step is 2 ms
CELLS = [
    ("docqa-open", 12.3, 7.6, 2),
    ("longdoc-open", 15.1, 8.1, 2),
    ("mixedlen-open", 18.2, 11.1, 2),
    ("chat-open", 16.9, 12.4, 2),
    ("batch-closed", 17.7, 16.3, 2),
    ("a-2-ms-step", 2.0, 7.0, 8),
    ("a-long-step", 40.0, 8.0, 1),
    ("host-bound", 2.0, 30.0, 8),  # no minted block hides it: the widest
]


def _ceiling(step_ms, loop_ms, widest=8):
    c = FusedStepCeiling(widest)
    c.observe_step(step_ms * MS)
    c.observe_loop(loop_ms * MS)
    return c


@pytest.mark.parametrize("name,step_ms,loop_ms,want", CELLS,
                         ids=[c[0] for c in CELLS])
def test_ceiling_of_a_cell(name, step_ms, loop_ms, want):
    c = _ceiling(step_ms, loop_ms)
    assert c.value() == want
    # the smallest such block: the one below it does not outlast the work
    if 1 < want < 8:
        assert (want // 2) * step_ms < multistep.MARGIN * loop_ms <= want * step_ms


@pytest.mark.parametrize("fed", ["nothing", "step", "loop"])
def test_without_both_readings_it_is_the_widest_block(fed):
    c = FusedStepCeiling(8)
    if fed == "step":
        c.observe_step(0.012)
    elif fed == "loop":
        c.observe_loop(0.007)
    assert c.value() == 8


@pytest.mark.parametrize("widest,want", [(1, 1), (2, 2), (4, 4), (6, 6), (8, 8)])
def test_never_wider_than_an_executable_exists_for(widest, want):
    assert _ceiling(1.0, 50.0, widest).value() == want


def test_the_margin_is_one_constant_of_the_code():
    assert multistep.MARGIN == 2.0
    src = inspect.getsource(multistep)
    assert "environ" not in src and "getenv" not in src and "profil" not in src


def test_means_are_slow_and_a_stall_is_no_reading():
    c = _ceiling(12.0, 7.0)
    for _ in range(8):  # a few long ticks do not flip the width
        c.observe_loop(0.013)
    assert c.value() == 2 and c.loop_s < 0.008
    c.observe_loop(2.0)  # a stall of the machine: counts as 4 x the mean
    assert c.loop_s < 0.0085
    c.observe_step(5.0)  # a compile behind a dispatch
    assert c.step_s < 0.013
    for _ in range(400):  # a lasting change does move it
        c.observe_loop(0.013)
    assert c.value() == 4
    c.observe_loop(0.0)
    c.observe_step(-1.0)  # a bundled commit's empty share
    assert c.value() == 4


# -- the engine around it -----------------------------------------------------


def _plan(engine, pressure=False):
    return engine._multistep_plan_k(["chunk"] if pressure else [], 0)


def _pin(engine, step_ms, loop_ms):
    """Pin the two means, as a process that has served for a while."""
    engine._ms_ceiling.step_s = step_ms * MS
    engine._ms_ceiling.loop_s = loop_ms * MS


def test_first_ramp_runs_every_minted_k_then_the_ceiling_binds():
    """Means with readings do not hold the first ramp back: a warm-up still
    compiles K = 1, 2, 4, 8.  Once the widest block has run, the ramp stops
    at the ceiling, and pressure still collapses it to 1."""

    async def body():
        engine = make_engine()
        try:
            _pin(engine, 12.3, 7.6)
            assert [_plan(engine) for _ in range(5)] == [1, 2, 4, 8, 8]
            engine._ms_topped = True  # a dispatch of 8 steps went out
            assert _plan(engine) == 2 and engine._ms_ramp == 2
            assert _plan(engine, pressure=True) == 1 and engine._ms_ramp == 1
            assert [_plan(engine) for _ in range(4)] == [1, 2, 2, 2]
            # the look-ahead the scheduler is told follows the ramp
            assert engine._ms_ramp == 2
            _pin(engine, 2.0, 7.0)  # a fast step: back to the widest
            assert [_plan(engine) for _ in range(3)] == [2, 4, 8]
            reg = engine.obs.registry
            assert reg.sample("dynamo_engine_multistep_ceiling") == 8.0
            assert reg.sample(
                "dynamo_engine_multistep_reading_seconds",
                {"of": "decode_step"}) == pytest.approx(0.002)
        finally:
            await engine.stop()

    asyncio.run(body())


def test_fixed_mode_still_pins(ms_env):  # noqa: F811
    async def body():
        ms_env("4")
        engine = make_engine()
        try:
            _pin(engine, 12.3, 7.6)
            engine._ms_topped = True
            assert [_plan(engine) for _ in range(3)] == [4, 4, 4]
            assert _plan(engine, pressure=True) == 1
        finally:
            await engine.stop()

    asyncio.run(body())


def test_across_processes_the_ceiling_stays_the_widest():
    """Commits are lockstep there: K has to be one number everywhere, and a
    mean of local clocks is not."""

    async def body():
        engine = make_engine()
        try:
            _pin(engine, 12.3, 7.6)
            engine._ms_topped = True
            engine._ms_local = False
            assert [_plan(engine) for _ in range(5)] == [1, 2, 4, 8, 8]
        finally:
            await engine.stop()

    asyncio.run(body())


def test_plan_k_reads_nothing_that_exists_only_while_watched():
    src = inspect.getsource(JaxEngine._multistep_plan_k) + inspect.getsource(
        JaxEngine._read_tick_clock)
    for name in (r"self\.profiler", r"\bprof\.", r"self\._tick\b",
                 r"\btracing\.", r"\.enabled\b", r"annotating"):
        assert not re.search(name, src), name


@pytest.mark.parametrize("loop_ms,want_k", [(0.001, 1), (1e6, 8)],
                         ids=["ceiling-1", "ceiling-8"])
def test_tokens_are_the_same_at_any_ceiling(loop_ms, want_k, ms_env):  # noqa: F811
    """A served engine whose means are pinned to either end of the rule:
    the readings are taken (both means move off their pins), the ceiling
    binds after the first ramp, and every token is the one K = 1 gives."""
    prompts = [[1, 2, 3, 4, 5], [9, 8, 7], [5, 5, 5, 5, 5, 5, 5]]

    async def served(pin):
        engine = make_engine()
        try:
            if pin:
                # blends would drift the pins toward the CPU's own
                # readings: hold them
                engine._ms_ceiling.observe_step = lambda s: seen.append(("step", s))
                engine._ms_ceiling.observe_loop = lambda s: seen.append(("loop", s))
                _pin(engine, 1.0, loop_ms)
            out = await asyncio.gather(
                *[collect(engine, req(p, max_tokens=40)) for p in prompts])
            return out, engine.obs.registry.sample("dynamo_engine_multistep_ceiling")
        finally:
            await engine.stop()

    async def body():
        ms_env(None)
        pinned, ceiling = await served(True)
        ms_env("0")
        off, _ = await served(False)
        assert pinned == off
        assert ceiling == want_k
        kinds = {k for k, _ in seen}
        assert kinds == {"step", "loop"}
        assert all(0.0 < s < 30.0 for _, s in seen)

    seen = []
    asyncio.run(body())


def test_a_served_engine_takes_its_readings_after_the_first_ramp():
    """No reading before the widest block has run (the ticks that compile
    the fused executables); both means afterwards."""

    async def body():
        engine = make_engine()
        try:
            await collect(engine, req([1, 2, 3], max_tokens=6))
            c = engine._ms_ceiling
            assert not engine._ms_topped and c.step_s is None and c.loop_s is None
            await collect(engine, req([3, 2, 1], max_tokens=56))
            assert engine._ms_topped
            assert c.step_s is not None and c.loop_s is not None
            assert 0.0 < c.step_s < 10.0 and 0.0 < c.loop_s < 10.0
        finally:
            await engine.stop()

    asyncio.run(body())


@pytest.mark.parametrize("pages,loop_ms,ceiling", [(17, 1.9, 4), (18, 0.9, 2), (17, 0.1, 1)],
                         ids=["17-pages-k4", "18-pages-k2", "17-pages-k1"])
def test_a_lane_walked_up_to_its_limit_is_revived(pages, loop_ms, ceiling, ms_env):  # noqa: F811
    """A pool too small for two growing lanes: one is preempted, and its
    pages raise the other's limit while a short fused block is still in
    flight that carries that lane to the limit it was issued under.  The
    lane pauses there on the device; the growth refresh must leave the
    host's copy of the limit alone so that the revival sees the pause (at
    the first two sizes a lane never ran again before it did).
    Tokens are those of the engine with multistep off."""

    async def served(pin):
        engine = make_engine(max_batch_size=2, num_pages=pages,
                             async_dispatch=False, decode_block_size=4)
        try:
            if pin:
                engine._ms_ceiling.observe_step = lambda s: None
                engine._ms_ceiling.observe_loop = lambda s: None
                _pin(engine, 1.0, loop_ms)
            out = await asyncio.wait_for(asyncio.gather(*[
                collect(engine, req([1, 2, 3 + i], max_tokens=40))
                for i in range(2)]), 60)
            return out, engine.obs.registry.sample("dynamo_engine_multistep_ceiling")
        finally:
            await engine.stop()

    async def body():
        ms_env(None)
        pinned, read = await served(True)
        ms_env("0")
        off, _ = await served(False)
        assert [len(toks) for toks, _ in pinned] == [40, 40]
        assert pinned == off
        assert read == ceiling

    asyncio.run(body())
