"""FLOPs and bytes from shapes, at the two configurations' widths,
against numbers written out by hand."""
import pytest

from benchmarks.families import dense_gqa_costs as costs
from benchmarks.harness import peaks
from benchmarks.harness.spec import Spec

SPEC = Spec()
INTERN = SPEC.config("internlm2-1.8b")
MISTRAL = SPEC.config("mistral-7b-v0.3")
FAMILY = SPEC.family("dense-gqa")


def test_layer_matmul_params_by_hand():
    # internlm2: wq 2048x2048, wk/wv 2048x1024 each, wo 2048x2048,
    # w1/w3/w2 3 x 2048x8192
    assert costs.layer_matmul_params(INTERN) == \
        4194304 + 2 * 2097152 + 4194304 + 3 * 16777216 == 62914560
    # mistral: wq/wo 4096x4096, wk/wv 4096x1024, mlp 3 x 4096x14336
    assert costs.layer_matmul_params(MISTRAL) == \
        2 * 16777216 + 2 * 4194304 + 3 * 58720256 == 218103808


def test_train_step_flops_by_hand():
    # 2 layers + head 2048x92544 = 125829120 + 189530112 = 315359232
    assert costs.matmul_params(INTERN, 2) == 315359232
    # 1 x 1024 row feeds 1023 positions: 6 * 315359232 * 1023
    dense = 6 * 315359232 * 1023
    # causal attention forward, one layer: 2 matmuls * 2 FLOPs * 16 heads
    # * 1023^2 * 128 / 2 = 4286582784; three passes, two layers
    attn = 3 * 2 * 4286582784
    assert costs.train_step_flops(INTERN, 2, 1, 1024) == \
        pytest.approx(dense + attn)
    assert dense + attn == 1935674966016 + 25719496704
    # at 197 TFLOP/s that is 9.96 ms: a 39 ms step is at about 25%
    least = (dense + attn) / peaks.peaks_of("TPU v5 lite")["flops_per_s"]
    assert least == pytest.approx(9.956e-3, rel=1e-3)


def test_decode_tick_bytes_by_hand():
    # mistral, 26 layers, 16 slots, 4000 live positions, bf16
    n = 26 * 218103808 + 4096 * 32768           # matmul weights
    n += (2 * 26 + 1) * 4096 + 16 * 4096        # norms, embedding rows
    kv = 2 * 26 * 8 * 128 * 2                   # bytes per position
    assert costs.kv_bytes_per_position(MISTRAL, 26) == kv == 106496
    assert costs.decode_tick_bytes(MISTRAL, 26, 16, 4000) == \
        n * 2 + kv * (4000 + 16)
    # internlm2, 24 layers: 98304 bytes of KV a position
    assert costs.kv_bytes_per_position(INTERN, 24) == 98304


def test_family_cost_table_gives_the_same_numbers():
    """What ``roofline_pct`` reads through the family's ``COSTS`` is the
    hand-computed value above; a decode tick with nothing live in the
    traced part has no cost to read."""
    assert FAMILY.COSTS["train_step"](INTERN, {}) == {
        "flops": costs.train_step_flops(INTERN, 2, 1, 1024)}
    assert FAMILY.COSTS["decode_tick"](MISTRAL, {"live_positions": 4000}) \
        == {"bytes": costs.decode_tick_bytes(MISTRAL, 26, 16, 4000)}
    assert FAMILY.COSTS["decode_tick"](MISTRAL,
                                       {"live_positions": None}) is None


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        peaks.peaks_of("cpu")
