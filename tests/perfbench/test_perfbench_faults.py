"""The comparison that decides ``correct`` fails a broken timed path, and
its lower-precision control, at toy sizes on the CPU.

Each test drives a whole run of the toy offline cell with the look for a
chip stubbed out and one fault planted underneath the engine.  The harness
and the engine read a clock that moves a fixed step at each reading, so a
window holds the same ticks, and the comparison the same tokens, however
busy the machine that runs the tests is."""
import time

import numpy as np
import pytest

from benchroot import LIMIT_DECODE, LIMIT_MHA, make_root, stub_chip

SEED = 2**33 + 11


class StepClock:
    """``time`` with a ``monotonic`` that advances ``step`` seconds at each
    reading."""

    def __init__(self, step: float):
        self.now, self.step = 0.0, step

    def monotonic(self) -> float:
        self.now += self.step
        return self.now

    def __getattr__(self, name):
        return getattr(time, name)


@pytest.fixture
def root(tmp_path, monkeypatch):
    stub_chip(monkeypatch, LIMIT_DECODE)
    from pb import harness
    from repro.serving import engine
    clock = StepClock(0.001)
    monkeypatch.setattr(harness, "time", clock)
    monkeypatch.setattr(engine, "time", clock)
    return make_root(tmp_path)


def _run(root, control=False, workload="tiny.decode"):
    from pb import harness
    line = harness.run_cell(workload, SEED, 1.0, False, root=root,
                            control=control)
    line.pop("_run")
    return line


def test_sound_run_is_correct(root):
    line = _run(root)
    assert line["correct"] is True, line["checks"]


def test_sound_mha_run_is_correct(root, monkeypatch):
    """The same backlog on a model whose KV heads are its heads (group 1),
    as deepseek-7b's are."""
    stub_chip(monkeypatch, LIMIT_MHA)
    line = _run(root, workload="tiny-mha.decode")
    assert line["correct"] is True, line["checks"]


def test_altered_token_is_caught(root, monkeypatch):
    """On every tenth decode tick each lane's token is replaced, where the
    engine samples it, by another token of the vocabulary."""
    from repro.serving import sampling
    real = sampling.sample_batch
    calls = {"n": 0}

    def altered(logits, params, counters):
        toks = real(logits, params, counters)
        calls["n"] += 1
        if calls["n"] % 10 == 0:
            v = np.asarray(logits).shape[-1]
            toks = [(t + 1 + v // 2) % v for t in toks]
        return toks
    monkeypatch.setattr(sampling, "sample_batch", altered)
    line = _run(root)
    assert line["correct"] is False
    gap = line["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]


def test_unwritten_kv_is_caught(root, monkeypatch):
    """The decode step returns the pool it was given: the keys and values
    of decoded tokens are never written."""
    from repro.models.model import Model
    real = Model.paged_decode_step

    def stale(self, params, caches, *args, **kw):
        logits, _ = real(self, params, caches, *args, **kw)
        return logits, caches
    monkeypatch.setattr(Model, "paged_decode_step", stale)
    line = _run(root)
    assert line["correct"] is False
    gap = line["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]


def test_lower_precision_control_fails(root):
    """The plain reference computed in int8, and in float8, in the
    program's place comes out not correct by the same judgement that
    passes the program's own run."""
    line = _run(root, control=True)
    assert line["correct"] is True, line["checks"]
    assert set(line["control"]) == {"int8", "fp8"}
    for control in line["control"].values():
        assert control["correct"] is False
        gap = control["checks"]["logit_gap"]
        assert gap["value"] > gap["limit"] == line["checks"]["logit_gap"][
            "limit"]
