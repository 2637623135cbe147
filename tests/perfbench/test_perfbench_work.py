"""Work counts behind the rooflines and MFU, and the table of peaks."""
import json

import pytest

import benchroot  # noqa: F401  (puts the benchmark on the path)
from pb import work

YI = work.Shapes(layers=32, d_model=4096, heads=32, kv_heads=4,
                 head_dim=128, d_ff=11008, vocab=64000)
DEEPSEEK = work.Shapes(layers=15, d_model=4096, heads=32, kv_heads=32,
                       head_dim=128, d_ff=11008, vocab=102400)


def test_shapes_from_the_config_files():
    conf = json.loads((benchroot.REPO / "perfbench/configs/yi-6b.json")
                      .read_text())
    assert work.Shapes.from_config(conf) == YI
    conf = json.loads((benchroot.REPO / "perfbench/configs/deepseek-7b.json")
                      .read_text())
    assert work.Shapes.from_config(conf) == DEEPSEEK


@pytest.mark.parametrize("block_k,num_splits,table_width", [
    (16, 1, 80), (16, 2, 80), (8, 1, 80), (8, 2, 256), (16, 4, 512)])
def test_decode_count_ignores_tiles_splits_and_table(block_k, num_splits,
                                                      table_width):
    """The kernel's tile, split degree and page-table width do not enter:
    the same lanes give the same count as the sum over their contexts."""
    lens = [1, 17, 300, 1280]
    flops, bytes_ = work.decode_attn(YI, lens)
    assert flops == 4 * 32 * 128 * sum(lens) * 32
    assert bytes_ == (2 * 4 * 128 * 2 * sum(lens)
                      + 2 * 32 * 128 * 2 * len(lens)) * 32


def test_counts_grow_with_real_context():
    f1, b1 = work.decode_attn(DEEPSEEK, [100, 100])
    f2, b2 = work.decode_attn(DEEPSEEK, [200, 100])
    assert f2 > f1 and b2 > b1
    # eight times the key-value heads read eight times the cache bytes
    cache_yi = work.decode_attn(YI, [1000])[1] / YI.layers
    cache_ds = work.decode_attn(DEEPSEEK, [1000])[1] / DEEPSEEK.layers
    assert cache_ds - 2 * 32 * 128 * 2 == 8 * (cache_yi - 2 * 32 * 128 * 2)


def test_prefill_count_is_causal():
    """Token i of a chunk at ``start`` attends to ``start + i + 1`` keys."""
    start, end = 300, 556
    keys = sum(start + i + 1 for i in range(end - start))
    flops, bytes_ = work.prefill_attn(YI, [(start, end)])
    assert flops == pytest.approx(4 * 32 * 128 * keys * 32)
    assert bytes_ == (2 * 4 * end * 128 * 2 + 2 * 32 * 256 * 128 * 2) * 32
    # split into two chunks, the attention work is the same
    two = work.prefill_attn(YI, [(300, 428), (428, 556)])[0]
    assert two == pytest.approx(flops)


def test_model_flops_per_token():
    # 2 FLOPs per matmul weight; the head only where logits are needed
    per_layer = 4096 * 4096 * 2 + 4096 * 512 * 2 + 3 * 4096 * 11008
    assert YI.layer_matmul_params == per_layer
    f = work.token_flops(YI, 1, logits=True)
    assert f == 2 * per_layer * 32 + 4 * 32 * 128 * 32 + 2 * 4096 * 64000
    chunk = work.prefill_flops(YI, 0, 256, final=False)
    assert chunk == pytest.approx(sum(work.token_flops(YI, i + 1, False)
                                      for i in range(256)))
    assert work.prefill_flops(YI, 0, 256, final=True) - chunk \
        == 2 * 4096 * 64000
    assert work.decode_flops(YI, [5, 9]) == work.token_flops(YI, 5, True) \
        + work.token_flops(YI, 9, True)


def test_roofline_takes_the_binding_bound():
    peak = work.peaks("TPU v5 lite")
    assert work.roofline_s(197e12, 0.0, peak) == pytest.approx(1.0)
    assert work.roofline_s(0.0, 819e9, peak) == pytest.approx(1.0)
    f, b = work.decode_attn(YI, [1024] * 19)
    # decode attention is bound by bandwidth
    assert work.roofline_s(f, b, peak) == pytest.approx(b / 819e9)


def test_missing_device_kind_raises():
    with pytest.raises(work.PeakMissing):
        work.peaks("TPU v9 imaginary")
    with pytest.raises(work.PeakMissing):
        work.peaks("cpu")
    assert "source" in work.peaks("TPU v5 lite")
