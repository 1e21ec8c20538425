"""chip_smoke.py --chips 4, rehearsed at tiny widths on four virtual CPU
devices: how the script is steered from outside, the one-chip rehearsal and
the one-process-per-chip rules are tests/test_zz_chip_smoke.py's (this is
its second long test, in a file of its own so that the suite's last two
workers share them).
"""

import chip_smoke
from test_zz_chip_smoke import (NOT_A_TPU, SERVE, TRAIN,  # noqa: F401
                                _only_not_a_tpu, fake_chips)


def test_four_chip_phases_rehearsed_on_cpu(fake_chips):
    """--chips 4: the fsdp=2 x tp=2 train run against its one-device twin,
    and tp=4 serving against tp=1, on four virtual devices."""
    fake_chips(4)
    trn = chip_smoke.run_train(TRAIN, seed=5, chips=4,
                               mesh={"fsdp": 2, "tp": 2})
    # the CPU backend keeps no allocator statistics
    no_stats = ["train: a device reports no memory_stats()"]
    _only_not_a_tpu(chip_smoke.check_train(trn, TRAIN, chips=4),
                    NOT_A_TPU["train"], also=no_stats)
    assert trn["device_count"] == 4
    assert trn["runs"]["mesh"]["mesh"] == {"fsdp": 2, "tp": 2}
    assert len(trn["runs"]["mesh"]["param_bytes_per_device"]) == 4

    srv = chip_smoke.run_serve_tp(SERVE, seed=5, tp=4)
    bad = chip_smoke.check_serve_tp(srv, SERVE, tp=4)
    assert srv["sharded"]["device_count"] == 4
    assert len(srv["sharded"]["devices"]) == 4
    assert srv["prompts_with_identical_tokens"] == 4
    assert all(c["max_gap"] <= chip_smoke.LOGIT_TOL
               for c in srv["plain_check"]["tp4"])
    _only_not_a_tpu(bad, [f"serve_tp{n}: {what}" for n in (4, 1) for what in (
        "replica ran on platform 'cpu'", "paged attention impl 'reference'")],
        also=[f"serve_tp4: device {i} reports no memory_stats()"
              for i in range(4)])
