"""A benchmark tree at toy sizes for the tests of the harness.

It holds ``BENCHMARK.json`` with the real metrics and three cells of two
toy configurations (``tiny.decode``, a backlog, and ``tiny.docqa``,
sessions, with the open-loop metric ``ttft_p50_ms``, of a GQA model;
``tiny-mha.decode``, the backlog on a model whose KV heads are its heads),
read by the same harness code as the real cells.  ``stub_chip`` replaces
the harness's look for a chip with the CPU, its reading of the device
memory with a fixed number, and turns off the persistent compile cache.
"""
import json
import shutil
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
for p in (REPO / "src", REPO / "perfbench"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from pb.harness import HEADROOM_BYTES  # noqa: E402

CELLS = {"yi-6b.decode": ["tiny.decode", "tiny-mha.decode"]}


def make_root(root: Path) -> Path:
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    spec["configs"] = [{"name": name, "source": "test",
                        "file": f"perfbench/configs/{name}.json",
                        "reduced": [], "why": "test"}
                       for name in ("tiny", "tiny-mha")]
    spec["workloads"] = [
        {"name": "tiny.decode", "config": "tiny", "traffic": "decode-tiny",
         "chips": 1, "why": "test"},
        {"name": "tiny.docqa", "config": "tiny", "traffic": "docqa-tiny",
         "chips": 1, "why": "test"},
        {"name": "tiny-mha.decode", "config": "tiny-mha",
         "traffic": "decode-tiny", "chips": 1, "why": "test"}]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = sorted({t for w in m["workloads"]
                                     for t in CELLS[w]})
    # the open-loop cell's end-to-end metric, read by its own reader; no
    # chip cell reports it yet
    spec["end_to_end"].append(
        {"name": "ttft_p50_ms", "unit": "ms", "better": "lower",
         "bound": 0.25, "source": "host_clock", "workloads": ["tiny.docqa"]})
    (root / "perfbench" / "configs").mkdir(parents=True)
    (root / "perfbench" / "traffic").mkdir(parents=True)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    for name in ("tiny", "tiny-mha"):
        shutil.copy(DATA / f"{name}.json",
                    root / "perfbench" / "configs" / f"{name}.json")
    for name in ("decode-tiny", "docqa-tiny"):
        shutil.copy(DATA / f"{name}.json",
                    root / "perfbench" / "traffic" / f"{name}.json")
    return root


def stub_chip(monkeypatch, limit: int) -> None:
    import jax

    from pb import harness
    monkeypatch.setattr(harness, "find_devices",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(harness, "bytes_limit", lambda devs: limit)
    monkeypatch.setattr(harness, "enable_cache", lambda: None)


#: device bytes for the toy cells: the headroom, weights (4,785,408
#: bytes) and two copies of 25 pages of 8,192 bytes, so four lanes of the
#: backlog
LIMIT_DECODE = HEADROOM_BYTES + 4_785_408 + 2 * 8_192 * 25
#: the same four lanes of the backlog on the MHA model: weights of
#: 4,850,944 bytes and pages of 16,384
LIMIT_MHA = HEADROOM_BYTES + 4_850_944 + 2 * 16_384 * 25
#: room for every lane of the sessions at full length, without preemption
LIMIT_DOCQA = HEADROOM_BYTES + 4_785_408 + 2 * 8_192 * 64
