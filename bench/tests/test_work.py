"""Operation and byte counts behind ``mfu`` and the roofline shares, on
hand-counted shapes."""
import pytest

from harness import work

ONE = {"hidden_size": 8, "intermediate_size": 16, "num_attention_heads": 2,
       "num_key_value_heads": 1, "head_dim": 4, "num_hidden_layers": 1,
       "vocab_size": 10}


def test_layer_params_by_hand():
    # q 8x2x4 + k 8x1x4 + v 8x1x4 + o 2x4x8 = 64+32+32+64; MLP 3 x 8x16
    assert work.layer_params(ONE) == 192 + 384


def test_forward_flops_one_token_one_layer():
    # one query over 3 keys: 2*576 matmul + 4*2*4*3 attention + 2*8*10 head
    assert work.forward_flops(ONE, 1, 3, 1) == 1152 + 96 + 160


def test_spec_step_counts_live_context_only():
    # K=1 at pos 5: target feeds 2 tokens attending 6 and 7 keys, head on
    # both; the draft proposes 1 (6 keys, head) and re-feeds 1 (7 keys)
    f = work.spec_step_flops(ONE, ONE, 1, 5)
    tgt = 2 * 2 * 576 + 4 * 8 * (6 + 7) + 2 * 2 * 80
    drf = (2 * 576 + 4 * 8 * 6 + 2 * 80) + (2 * 576 + 4 * 8 * 7)
    assert f == tgt + drf


def test_paged_attention_bytes_follow_context_not_pages():
    # bf16 keys+values of 1 kv-head x 4 wide per token: 16 bytes a token
    assert work.kv_bytes_per_token(ONE, 2) == 16
    f, b = work.paged_attention_call(ONE, 1, 37, 2)
    assert f == 4 * 2 * 4 * 37
    assert b == 16 * 37 + 2 * 1 * 2 * 4 * 2
    # a slot of 37 live keys costs the same however many pages its table
    # or the pool holds: nothing in the count depends on them
    f2, b2 = work.paged_attention_call(ONE, 1, 38, 2)
    assert (f2 - f, b2 - b) == (4 * 2 * 4, 16)


def test_prefill_counts_valid_tokens():
    f = work.prefill_flops(ONE, ONE, 0, 2)
    per = 2 * 2 * 576 + 4 * 8 * (1 + 2)
    assert f == per + 2 * 2 * 80 + per


def test_verify_tail_and_roofline():
    f, b = work.verify_tail(100, 4, 0)
    assert b == 9 * 100 * 4 and f == 500
    pk = {"flops": 1e3, "hbm_bytes_per_s": 1e3}
    share, bound = work.roofline_share([(10, 100), (10, 100)], 0.4, pk)
    assert bound == "bytes" and share == pytest.approx(50.0)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")
    assert work.peaks("TPU v5 lite")["flops"] == 197e12
