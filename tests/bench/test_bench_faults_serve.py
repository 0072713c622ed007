"""A serving run at a test size is correct, and comes out not correct with
a served token altered where it is produced, or with decode steps that
leave the cache unwritten; the fp8 control's first tokens and altered
tokens read over the limit."""
import pytest

from bench_faults import child


@pytest.fixture(scope="module")
def found(tmp_path_factory):
    return child(tmp_path_factory.mktemp("serve"), "serve")


def test_sound_run_is_correct(found):
    assert found["sound"] is True


@pytest.mark.parametrize("fault", ["token_altered", "cache_unwritten"])
def test_fault_in_program_is_not_correct(found, fault):
    assert found[fault] is False


@pytest.mark.parametrize("reading", ["control_fp8", "fault_token_altered"])
def test_control_and_altered_tokens_fail_the_limit(found, reading):
    assert found["readings"][reading] > found["limits"]["served_gap"]


def test_program_reading_within_limit(found):
    assert found["readings"]["program"] <= found["limits"]["served_gap"]
