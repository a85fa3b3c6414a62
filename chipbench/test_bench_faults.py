"""``correct`` comes out false when the timed path is broken underneath:
each fault a one-chip training cell can have, planted in the program, with
the rest of a run driven at the smoke size.  And the control, the reference
computed in bfloat16 in the program's place, fails the limits."""
import jax.numpy as jnp
import pytest

from chipbench import compare, faults, smoke
from chipbench.kinds import train as K
from chipbench.testing import cpu_run  # noqa: F401  (fixture)


# The numbers each fault must push over their limits.
CAUGHT_BY = {
    "unchanged_state": {"grad_norm_gap", "update_norm_gap"},
    "half_batch": {"loss_gap", "grad_norm_gap", "update_norm_gap"},
    "altered_loss": {"loss_gap"},
    "altered_checkpoint": {"restore_bits_mismatched"},
}


@pytest.mark.parametrize("fault,cell", [
    ("unchanged_state", "train.steady"),
    ("half_batch", "train.steady"),
    ("altered_loss", "train.steady"),
    ("unchanged_state", "train.ckpt"),
    ("half_batch", "train.ckpt"),
    ("altered_loss", "train.ckpt"),
    ("altered_checkpoint", "train.ckpt"),
])
def test_fault_makes_the_run_incorrect(cpu_run, fault, cell):
    with faults.FAULTS[fault]():
        got = cpu_run(cell)
    checks = got["checks"]
    over = {k for k, c in checks.items() if not c["value"] <= c["limit"]}
    assert got["result"]["correct"] is False, checks
    assert CAUGHT_BY[fault] <= over, checks


def test_control_in_bfloat16_is_not_correct():
    cfg = smoke.config("minicpm-2b-train.vocab-half")
    traffic = smoke.traffic("steady")
    seed = 2 ** 31 + 11
    ref = K.reference_numbers(cfg, traffic, seed)
    ctl = K.reference_numbers(cfg, traffic, seed, jnp.bfloat16)
    checks = K.step_checks(cfg, ctl, ref)
    assert not compare.passed(checks), checks
