"""The operation and byte counts against a hand count at both widths."""

import pytest

from bench import flops


def hand_update(k, m, h=64, b=16):
    # matmul shapes per layer: actor (k,h) (h,h) (h,m); critic (k+m,h) (h,h)
    # (h,1); 2*b*i*o operations each
    A = 2 * b * (k * h + h * h + h * m)
    C = 2 * b * ((k + m) * h + h * h + h * 1)
    critic_step = A + C + C + C + 2 * b * (h * h + h * 1)
    actor_step = A + C + C + A + 2 * b * (h * h + h * m)
    params = (k * h + h + h * h + h + h * m + m) + \
        ((k + m) * h + h + h * h + h + h + 1)
    return critic_step + actor_step + 17 * params, params


@pytest.mark.parametrize("m", [2, 8])
def test_update_flops_by_hand(m):
    want, _ = hand_update(12, m)
    assert flops.update_flops(12, m, (64, 64), 16) == want


def test_magpie8_numbers():
    # 8 knobs: 1,855,449 operations an update; 5,512 + 5,569 parameters
    assert flops.update_flops(12, 8, (64, 64), 16) == 1855449
    _, params = hand_update(12, 8)
    assert params == 5512 + 5569
    bytes_ = 2 * 4 * params * 4 + 96 * 16 * (12 + 8 + 1 + 12) * 4
    assert flops.learner_call_bytes(12, 8, (64, 64), 16, 96) == bytes_
    assert flops.session_step_flops(12, 8, (64, 64), 16, 96) == \
        2 * (12 * 64 + 64 * 64 + 64 * 8) + 96 * 1855449
