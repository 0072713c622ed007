"""A training run at a test size is correct, and comes out not correct with
each fault a training cell can have planted underneath its timed path; the
control (the fp8 reference in the program's place) and the faults planted
in the reference read over the limits."""
import pytest

from bench_faults import child


@pytest.fixture(scope="module")
def found(tmp_path_factory):
    return child(tmp_path_factory.mktemp("train"), "train")


def test_sound_run_is_correct(found):
    assert found["sound"] is True


@pytest.mark.parametrize("fault", ["unchanged_state", "half_batch",
                                   "no_exchange"])
def test_fault_in_program_is_not_correct(found, fault):
    assert found[fault] is False


@pytest.mark.parametrize("reading", ["control_fp8", "fault_half_batch",
                                     "fault_no_exchange"])
def test_control_and_reference_faults_fail_a_limit(found, reading):
    nums, lim = found["readings"][reading], found["limits"]
    assert any(nums[k] > lim[k] for k in lim), nums


def test_program_readings_within_limits(found):
    nums, lim = found["readings"]["program"], found["limits"]
    assert all(nums[k] <= lim[k] for k in lim), nums
