"""conftest.py's `limited`: a test, or a fixture of a wider scope, that
overruns fails by name."""
import signal
import time

import pytest
from conftest import TEST_LIMIT_S, limited


@pytest.mark.parametrize("time_limit", [0.2], indirect=True)
def test_overrunning_test_fails_with_its_node_id(time_limit, request):
    assert time_limit == 0.2
    with pytest.raises(pytest.fail.Exception,
                       match=r"test_time_limit\.py::test_overrunning.*0\.2 s"):
        time.sleep(5.0)
    assert request.node.nodeid.endswith("[0.2]")


@pytest.fixture(scope="module")
def left_while_a_module_fixture_is_set_up():
    return signal.getitimer(signal.ITIMER_REAL)[0]


def test_a_module_fixture_is_set_up_under_the_limit(
        left_while_a_module_fixture_is_set_up):
    """`time_limit` is armed after the module-scoped fixtures are set up:
    `pytest_fixture_setup` arms the same timer around each of them."""
    assert 0 < left_while_a_module_fixture_is_set_up <= TEST_LIMIT_S


def test_an_inner_limit_fails_by_its_own_name_and_gives_the_outer_back():
    before = signal.getitimer(signal.ITIMER_REAL)[0]
    with pytest.raises(pytest.fail.Exception,
                       match=r"fixture `trained` of tests/x\.py ran over "
                             r"its 0\.2 s limit"):
        with limited("fixture `trained` of tests/x.py", 0.2):
            time.sleep(5.0)
    after = signal.getitimer(signal.ITIMER_REAL)[0]
    assert before - 1.0 < after <= before
