"""The benchmark's operation and byte counts against hand counts."""

import pytest

from bench import costs

ARCH = dict(n_layers=1, d_model=4, n_heads=2, n_kv_heads=2, head_dim=2, d_ff=8, vocab=10,
            mlp="swiglu")
PEAK = {"int8_ops_per_s": 100.0, "hbm_bytes_per_s": 10.0}


def test_attention_counts():
    assert costs.attention_ops(1, 2, 3, 5, 4) == 4 * 2 * 3 * 5 * 4
    assert costs.attention_bytes(1, 2, 3, 5, 4) == 2 * (2 * 3 * 4 + 2 * 5 * 4)


def test_least_time_takes_the_binding_bound():
    assert costs.least_time(1000, 5, PEAK) == 10.0   # operations bind
    assert costs.least_time(10, 500, PEAK) == 50.0   # bytes bind


def test_decoder_counts():
    # qkv 4x(2+2+2)x2 = 48, wo 4x4 = 16, swiglu 3x4x8 = 96
    assert costs.decoder_linear_macs(ARCH) == 160
    assert costs.decoder_weight_bytes(ARCH) == 160 + 10 * 4
    assert costs.kv_row_bytes(ARCH) == 2 * 1 * 2 * 2
    ops, nbytes = costs.decode_step(ARCH, lanes=2, key_rows=5)
    assert ops == 2 * (2 * 160 + 2 * 4 * 10) + 4 * 1 * 2 * 2 * 5
    assert nbytes == 200 + 8 * (5 + 2)


def test_chunk_counts_new_rows_only_when_asked():
    # rows 0 and 1 attend 1 and 2 keys; LM head on the last token
    assert costs.chunk_ops(ARCH, 0, 2) == 2 * 2 * 160 + 2 * 4 * 10 + 4 * 1 * 2 * 2 * 3
    assert costs.chunk_ops(ARCH, 0, 2, first_new=1) == 2 * 160 + 2 * 4 * 10 + 4 * 1 * 2 * 2 * 2
    assert costs.chunk_ops(ARCH, 0, 2, first_new=2) == 0.0


@pytest.mark.parametrize("arch,seq,want", [
    (dict(ARCH, mlp="gelu"), 3, 2 * 3 * (48 + 16 + 64) + 4 * 2 * 3 * 3 * 2),
    # mobilebert as executed: per token 128x768 + 256x128 + 2x128x512 MACs,
    # attention 4 heads x 128^2 x 64 x 4, over 24 layers
    (dict(n_layers=24, d_model=128, n_heads=4, n_kv_heads=4, head_dim=64, d_ff=512),
     128, 24 * (2 * 128 * 262144 + 4 * 4 * 128 * 128 * 64)),
    # the Whisper-Tiny encoder: per token 384x1152 + 384x384 + 2x384x1536 MACs,
    # attention 6 heads x 512^2 x 64 x 4, over 4 layers (8.86 GOp a sequence)
    (dict(n_layers=4, d_model=384, n_heads=6, n_kv_heads=6, head_dim=64, d_ff=1536),
     512, 4 * (2 * 512 * 1769472 + 4 * 6 * 512 * 512 * 64)),
])
def test_encoder_sequence_ops(arch, seq, want):
    assert costs.encoder_seq_ops(arch, seq) == want
