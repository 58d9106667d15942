"""The counts of operations and bytes, pinned to hand counts at tiny
shapes."""
import pytest

from chipbench import flops

TINY = {"n_layers": 2, "d_model": 8, "n_heads": 2, "n_kv_heads": 1,
        "d_head": 4, "d_ff": 16, "vocab": 10, "dtype": "bfloat16"}


def test_matmul_params_per_layer():
    # q,k,v 8*(2+2)*4 + o 2*4*8 + MLP 3*8*16
    assert flops.matmul_params_per_layer(TINY) == 128 + 64 + 384


def test_decode_step_flops():
    # rows at contexts 3 and 5: dense 2*2*576, attention 4*(3+5)*2*4 per
    # layer, two layers, unembedding 2*2*8*10
    assert flops.step_flops(TINY, [3, 5]) == 2 * (2304 + 256) + 320


def test_prefill_chunk_counts_valid_tokens_causally():
    # positions 4, 5, 6 attend to 5, 6, 7 keys; one row unembedded
    assert flops.step_flops(TINY, [], (4, 3)) == 2 * (3456 + 4 * 18 * 8) + 160


def test_prefill_chunk_within_window():
    m = dict(TINY, window=5)
    assert flops.step_flops(m, [], (4, 3)) == 2 * (3456 + 4 * 15 * 8) + 160


@pytest.mark.parametrize("d_head,want_flops,want_bytes", [
    # live contexts 20 and 16 (a third row is inactive), pages of 16:
    # FLOPs 4*(20+16)*4*dh; K and V of 3 pages 2*3*16*2*dh*2 bytes; q and
    # out 2*2*4*dh*2 bytes.  120 is counted as 120: the kernel path's
    # padding to 128 is its own cost, not required work
    (120, 576 * 120, (384 + 32) * 120),
    (128, 576 * 128, (384 + 32) * 128),
])
def test_paged_decode_cost(d_head, want_flops, want_bytes):
    m = dict(TINY, n_heads=4, n_kv_heads=2, d_head=d_head)
    f, b = flops.paged_decode_cost(m, [20, 0, 16], page=16)
    assert (f, b) == (want_flops, want_bytes)


def test_least_seconds_takes_the_binding_roof():
    peak = {"flops": 100.0, "hbm_bytes": 10.0}
    assert flops.least_seconds(1000.0, 50.0, peak) == 10.0
    assert flops.least_seconds(100.0, 50.0, peak) == 5.0


def test_peaks_table():
    assert flops.peaks("TPU v5 lite")["flops"] == 197e12
    with pytest.raises(KeyError):
        flops.peaks("TPU v9 imaginary")
