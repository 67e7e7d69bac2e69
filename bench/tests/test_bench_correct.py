"""``correct`` holds for a sound run and fails for the control and faults.

Runs a whole cell at a tiny size on the CPU, skipping only the harness's
look for a chip. The control is the program's own bfloat16 path
(``io_dtype="bfloat16"``) in place of the float32 one the configuration
states. The faults are planted in the step the window drives, by wrapping
the lane step that ``make_step`` jits: a step that returns its state
unchanged, half of each batch left out, and an answer altered where it is
produced. (One chip: there is no exchange between chips to leave out.)
"""
import dataclasses

import _small
import jax.numpy as jnp
import pytest

from repro.core import pipeline
from repro.stream import elastic


def _state_unchanged(out, frames, ids, state):
    return dataclasses.replace(out, state=state)


def _half_batch(out, frames, ids, state):
    b = out.frames.shape[1]
    return dataclasses.replace(
        out, frames=out.frames.at[:, b // 2:].set(0.0))


def _answer_altered(out, frames, ids, state):
    j = out.frames
    return dataclasses.replace(
        out, frames=j.at[..., 0, 0, 0].set(jnp.clip(j[..., 0, 0, 0] + 0.25,
                                                     0.0, 1.0)))


FAULTS = {"state_unchanged": _state_unchanged, "half_batch": _half_batch,
          "answer_altered": _answer_altered}
CELL_FAULTS = [(c, f) for c in ("dcp-1080p-backlog", "cap-1080p-nav-b1")
               for f in FAULTS
               if not (f == "half_batch" and c == "cap-1080p-nav-b1")]


@pytest.fixture
def fresh_steps(monkeypatch):
    """A step cache of this test's own, so no planted fault outlives it."""
    monkeypatch.setattr(elastic, "_STEP_CACHE", elastic._LRUStepCache(8))


@pytest.mark.parametrize("cell", ["dcp-1080p-backlog", "cap-1080p-nav-b1"])
def test_sound_run_is_correct(cell, fresh_steps):
    r = _small.run_small(cell)
    assert r["correct"], r["checks"]


@pytest.mark.parametrize("cell", ["dcp-1080p-backlog", "cap-1080p-nav-b1"])
def test_bfloat16_control_is_not_correct(cell, fresh_steps):
    r = _small.run_small(cell, dehaze={"io_dtype": "bfloat16"})
    gap = r["checks"]["J_max_abs_diff"]
    assert not r["correct"] and gap["value"] > gap["limit"], gap


@pytest.mark.parametrize("cell,fault", CELL_FAULTS)
def test_planted_fault_is_not_correct(cell, fault, fresh_steps, monkeypatch):
    make_lane_step = pipeline._make_lane_step

    def faulty(cfg, **kw):
        step = make_lane_step(cfg, **kw)
        return lambda f, i, s: FAULTS[fault](step(f, i, s), f, i, s)

    monkeypatch.setattr(pipeline, "_make_lane_step", faulty)
    r = _small.run_small(cell)
    assert not r["correct"], r["checks"]
