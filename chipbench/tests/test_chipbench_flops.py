"""FLOP counts of the policy networks against a hand count."""
import flops
import peaks
import pytest


def test_actor_and_critic_by_hand():
    # T=2 steps, F=4 features, G=3 actions, hidden 4:
    # actor LSTM step 2*(4+4)*16 = 256, head 2*(4*2 + 2*3) = 28
    assert flops.actor_forward_flops(2, 4, 3, 4) == 2 * (256 + 28)
    # critic input F+G = 7: LSTM step 2*(7+4)*16 = 352, head 2*(4*2+2*1)
    assert flops.critic_forward_flops(2, 4, 3, 4) == 2 * (352 + 20)


def test_serve_flops_at_the_cells_widths():
    cfg = {"tables": {"num_sas": 6}, "max_rq": 96, "hidden": 64}
    # 97 slots; F = 16, G = 7: 2*80*256 + 2*(64*32 + 32*7) per slot
    assert flops.serve_flops_per_stream_tick(cfg) == 97 * (40960 + 4544)


def test_unknown_device_has_no_peak():
    assert peaks.peak("TPU v5 lite")["bf16_flop_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks.peak("cpu")
