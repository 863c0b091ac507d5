"""The order of tier-1's queue (ISSUE 50): under ``--dist loadfile`` a file
is one worker's, so the files that are long because they hold a few long
cases start first (``tests/conftest.py`` ``_LONGEST_FIRST``), and the one
file that describes a TPU topology stands at their head. Host-only: no JAX
work."""

import pathlib
import re

import conftest

TESTS = pathlib.Path(__file__).resolve().parent


def test_the_queue_is_the_tuple_then_every_other_file_in_its_place():
    """The list the hook builds from a hand-made collection: the tuple's
    files first, in the tuple's order and each with its cases in theirs,
    whatever place they were collected in; every other file where it was."""
    first, second, third = conftest._LONGEST_FIRST[:3]
    collected = ["test_a.py", "test_a.py", third, "test_b.py", second, second,
                 "test_c.py", first, "test_a.py", third]
    cases = [f"{name}::{i}" for i, name in enumerate(collected)]
    order = conftest.longest_first(collected)
    assert sorted(order) == list(range(len(collected)))
    assert [cases[i] for i in order] == [
        f"{first}::7", f"{second}::4", f"{second}::5", f"{third}::2",
        f"{third}::9", "test_a.py::0", "test_a.py::1", "test_b.py::3",
        "test_c.py::6", "test_a.py::8"]
    # a run that collected none of them keeps its order
    assert conftest.longest_first(["test_b.py", "test_a.py"]) == [0, 1]


def test_every_name_of_the_tuple_is_a_file_of_tests_once():
    names = conftest._LONGEST_FIRST
    assert len(set(names)) == len(names)
    missing = [n for n in names if not (TESTS / n).is_file()]
    assert not missing, missing
    # the hook reorders the loadfile queue itself: the option that would ask
    # xdist for it is no option of a pytest without xdist
    addopts = (TESTS.parent / "pyproject.toml").read_text()
    assert "loadscope-reorder" not in addopts


def test_one_file_describes_the_topology_and_it_starts_first():
    """``on-chip-measurement`` guide, section 2: one file describes the
    topology (a second would skip in silence on another worker, where the
    first holds libtpu's lock). That file is the head of the queue."""
    callers = sorted(
        p.name for p in TESTS.rglob("*.py")
        if re.search(r"\bget_topology_desc\(", p.read_text()))
    assert callers == ["test_zero_layout.py"] == [conftest._LONGEST_FIRST[0]]
