"""The chip benchmark's reader of the program's stage spans
(``benchmarks/chip/chipbench/program_spans.py``) through its own self-test,
one case a check, so that tier-1 holds what the benchmark reads."""

from __future__ import annotations

import importlib.util
import os

import pytest

SELFTEST = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "benchmarks", "chip", "selftest", "program_spans.py",
)
CHECKS = ("hand_split", "hand_identity", "innermost", "nothing_to_read", "readers_on_a_run",
          "load_a_recorded_session", "recorded")


@pytest.fixture(scope="module")
def selftest():
    spec = importlib.util.spec_from_file_location("chipbench_selftest_program_spans", SELFTEST)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_every_check_is_a_case(selftest):
    assert tuple(t.__name__.removeprefix("test_") for t in selftest.TESTS) == CHECKS


@pytest.mark.parametrize("check", CHECKS)
def test_program_spans_selftest(selftest, check):
    getattr(selftest, "test_" + check)()
