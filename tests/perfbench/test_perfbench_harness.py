"""The harness on the CPU at toy sizes: the offline cell's ticks follow the
seed and not the clock, and a run's result line has the contract's keys."""
import dataclasses
import json

import pytest

from benchroot import LIMIT_DECODE, LIMIT_DOCQA, make_root, stub_chip

RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device",
               "checks"]
SEED = 2**33 + 7          # wider than 32 bits, as --seed may be


@pytest.fixture
def root(tmp_path, monkeypatch):
    stub_chip(monkeypatch, LIMIT_DECODE)
    return make_root(tmp_path)


def _run(root, workload, seed=SEED, seconds=1.0):
    from pb import harness
    line = harness.run_cell(workload, seed, seconds, False, root=root)
    return line, line.pop("_run")


def test_offline_ticks_follow_the_seed(root):
    a, run_a = _run(root, "tiny.decode")
    b, run_b = _run(root, "tiny.decode", seconds=0.5)
    # the window opens at the same tick index in both runs
    assert run_a.ticks[0].index == run_b.ticks[0].index == 2
    # and every request was admitted at the same tick, as far as the
    # shorter run went
    last = run_b.ticks[-1].index
    adm_a = {r: t for r, t in run_a.admitted.items() if t <= last}
    adm_b = {r: t for r, t in run_b.admitted.items() if t <= last}
    assert adm_a == adm_b and len(adm_a) > 8
    # the ticks that both runs made did the same work
    ticks_a = {t.index: (t.decode_lens, t.chunks) for t in run_a.ticks}
    for t in run_b.ticks:
        assert ticks_a[t.index] == (t.decode_lens, t.chunks)
    # and the same prompts were served the same tokens
    out_a = {s.req.rid: s.req.out_tokens for s in run_a.sent if s.req.done}
    out_b = {s.req.rid: s.req.out_tokens for s in run_b.sent if s.req.done}
    for rid in set(out_a) & set(out_b):
        assert out_a[rid] == out_b[rid]


def test_other_seed_same_amount_of_work(root):
    from pb import traffic
    mix = json.loads((root / "perfbench/traffic/decode-tiny.json")
                     .read_text())
    sizes = {}
    for seed in (SEED, SEED + 1):
        gen = traffic.Backlog(mix, seed, 8192, first=4)
        reqs = [gen.request(i) for i in range(4 + 2 * mix["block"])]
        # the first cohort and each block are one fixed set of shapes
        shapes = [(len(p), n) for p, n in reqs]
        sizes[seed] = (sorted(shapes[:4]), sorted(shapes[4:4 + mix["block"]]),
                       sorted(shapes[4 + mix["block"]:]))
        assert sizes[seed][1] == sizes[seed][2]
    assert sizes[SEED] == sizes[SEED + 1]
    p0, _ = traffic.Backlog(mix, SEED, 8192, first=4).request(5)
    p1, _ = traffic.Backlog(mix, SEED + 1, 8192, first=4).request(5)
    assert p0 != p1


def test_result_line_has_the_contract_keys(root):
    line, run = _run(root, "tiny.decode")
    assert list(line) == RESULT_KEYS          # `checks` comes last
    assert line["correct"] is True
    assert set(line["metrics"]) == {"output_tok_s", "setup_s"}
    assert all(set(m) == {"value", "unit"} for m in line["metrics"].values())
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert all(set(c) == {"value", "limit"} for c in line["checks"].values())
    assert line["attempted"] > 0 and line["failed"] == 0
    # the rate divides by the time the window's ticks took, not by the
    # nominal seconds
    span = run.ticks[-1].t_end - run.ticks[0].t_start
    tokens = sum(t.tokens for t in run.ticks)
    assert line["metrics"]["output_tok_s"]["value"] == tokens / span
    json.dumps(line)


def test_sessions_open_loop(root, monkeypatch):
    stub_chip(monkeypatch, LIMIT_DOCQA)
    line, run = _run(root, "tiny.docqa", seconds=2.0)
    assert line["correct"] is True, line["checks"]
    assert set(line["metrics"]) == {"ttft_p50_ms", "setup_s"}
    due = run.due_in_window()
    assert line["attempted"] == len(due) > 0
    assert all(s.req.submit_t == s.due for s in run.sent)
    # follow-up questions reuse the document's pages
    assert any(s.req.cached_tokens > 0 for s in run.sent if s.turn > 0)


def test_no_chip_no_result(tmp_path, capsys):
    from pb import harness
    make_root(tmp_path)
    with pytest.raises(harness.NoChip):
        harness.run_cell("tiny.decode", SEED, 1.0, False, root=tmp_path)


@dataclasses.dataclass(frozen=True)
class FixedEpsArch:
    """A program's architecture config whose norms take ``rms_norm``'s
    default eps: the fields that ``arch_config`` sets, and no eps."""
    n_layers: int = 0
    d_model: int = 0
    n_heads: int = 0
    n_kv_heads: int = 0
    d_head: int = 0
    d_ff: int = 0
    vocab_size: int = 0
    rope_theta: float = 0.0
    param_dtype: str = ""
    compute_dtype: str = ""
    cache_dtype: str = ""


@dataclasses.dataclass(frozen=True)
class EpsArch(FixedEpsArch):
    """One whose norms take the eps its ``norm_eps`` field states."""
    norm_eps: float = 1e-5


def _conf(path):
    import benchroot
    return json.loads((benchroot.REPO / path).read_text())


def test_eps_the_program_cannot_take_is_refused(monkeypatch):
    """A configuration is served with the ``rms_norm_eps`` it states, or
    not at all."""
    import repro.configs
    from pb import harness
    conf = _conf("tests/perfbench/data/tiny.json")
    assert harness.arch_config(conf).n_layers == conf["num_hidden_layers"]
    monkeypatch.setattr(repro.configs, "get_arch",
                        lambda name: FixedEpsArch())
    assert harness.arch_config(conf).n_layers == conf["num_hidden_layers"]
    with pytest.raises(ValueError, match="eps"):
        harness.arch_config(dict(conf, rms_norm_eps=1e-6))


@pytest.mark.parametrize("path", ["tests/perfbench/data/tiny-mha.json",
                                  "perfbench/configs/yi-6b.json",
                                  "perfbench/configs/deepseek-7b.json"])
def test_eps_the_file_states_reaches_the_program(monkeypatch, path):
    """Where the program's config carries ``norm_eps``, the file's eps is
    served in it: 1e-6 as the file states it, not refused."""
    import repro.configs
    from pb import harness
    monkeypatch.setattr(repro.configs, "get_arch", lambda name: EpsArch())
    conf = _conf(path)
    arch = harness.arch_config(conf)
    assert arch.norm_eps == conf["rms_norm_eps"]
    assert arch.n_kv_heads == conf["num_key_value_heads"]
    assert harness.arch_config(dict(conf, rms_norm_eps=1e-6)).norm_eps == 1e-6


def test_configuration_without_eps_is_refused():
    from pb import harness
    conf = _conf("tests/perfbench/data/tiny.json")
    del conf["rms_norm_eps"]
    with pytest.raises(ValueError, match="rms_norm_eps"):
        harness.arch_config(conf)
