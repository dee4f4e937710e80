"""Flags plane (set_flags/get_flags/env override, cache invalidation)
and the FLAGS_check_nan_inf executor scan.

Capability parity: platform/flags.cc + pybind/global_value_getter_setter.cc
-> paddle.set_flags/get_flags (fluid/framework.py:5576,5599); NaN/Inf scan
framework/details/nan_inf_utils_detail.cc hooked at operator.cc:1056.
"""

import os
import re

import numpy as np
import pytest

from paddle_tpu import flags
from paddle_tpu.framework import Executor, Program, Scope

FLAGS_PY = os.path.abspath(flags.__file__)


def _defined_flags():
    """The names ``flags.py`` defines, read from its source: the same
    list in every worker, whatever a test has defined since."""
    with open(FLAGS_PY) as f:
        return re.findall(r'^define_flag\("(\w+)"', f.read(), re.M)


@pytest.fixture(scope="module")
def program_sources():
    """Every module of the program but ``flags.py``, as text."""
    out = []
    for root, _dirs, files in os.walk(os.path.dirname(FLAGS_PY)):
        for name in files:
            path = os.path.join(root, name)
            if name.endswith(".py") and path != FLAGS_PY:
                with open(path) as f:
                    out.append(f.read())
    return out


@pytest.mark.parametrize("name", _defined_flags())
def test_a_flag_does_not_outlive_its_reader(name, program_sources):
    """A defined flag is read somewhere in the program: its quoted name
    or ``FLAGS_<name>`` stands in a module other than ``flags.py``."""
    needles = (f'"{name}"', f"'{name}'", f"FLAGS_{name}")
    assert any(n in src for src in program_sources for n in needles), \
        f"flag {name!r} is defined in flags.py and read nowhere"


def test_flags_get_set_and_unknown():
    assert flags.get_flags(["use_pallas_attention"])[
        "use_pallas_attention"] in (True, False)
    old = flags.get_flag("pallas_min_seq")
    try:
        flags.set_flags({"pallas_min_seq": 2048})
        assert flags.get_flag("pallas_min_seq") == 2048
    finally:
        flags.set_flags({"pallas_min_seq": old})
    with pytest.raises(ValueError):
        flags.get_flags("no_such_flag")
    with pytest.raises(ValueError):
        flags.set_flags({"no_such_flag": 1})


def test_verifier_flags_registered():
    got = flags.get_flags(["check_program", "check_ir_passes"])
    assert set(got) == {"check_program", "check_ir_passes"}
    # default off in production; conftest turns check_program on for the
    # suite via the FLAGS_ env override, so only assert the type here
    assert all(isinstance(v, bool) for v in got.values())


def test_unknown_flag_suggests_closest_name():
    with pytest.raises(ValueError) as ei:
        flags.set_flags({"check_programs": True})
    msg = str(ei.value)
    assert "check_programs" in msg
    assert "did you mean 'check_program'?" in msg
    with pytest.raises(ValueError) as ei:
        flags.get_flags(["check_nan_if"])
    assert "did you mean 'check_nan_inf'?" in str(ei.value)


def test_flags_env_override(monkeypatch):
    flags.define_flag("test_only_env_flag", 7, "test")
    monkeypatch.setenv("FLAGS_test_only_env_flag", "13")
    assert flags.get_flag("test_only_env_flag") == 13


def test_set_flags_bumps_version():
    v0 = flags.version()
    old = flags.get_flag("use_pallas_layer_norm")
    flags.set_flags({"use_pallas_layer_norm": old})
    assert flags.version() > v0


def _nan_program():
    prog = Program()
    blk = prog.global_block()
    blk.create_var("x", is_data=True)
    blk.create_var("y")
    blk.append_op("log", {"X": "x"}, {"Out": "y"}, {})
    blk.create_var("loss")
    blk.append_op("reduce_sum", {"X": "y"}, {"Out": "loss"},
                  {"reduce_all": True})
    return prog


def test_check_nan_inf_catches_and_names_op():
    prog = _nan_program()
    exe = Executor()
    old = flags.get_flag("check_nan_inf")
    try:
        flags.set_flags({"check_nan_inf": True})
        with pytest.raises(Exception) as ei:
            exe.run(prog, feed={"x": np.array([-1.0, 2.0], np.float32)},
                    fetch_list=["loss"], scope=Scope())
        assert "log" in str(ei.value) and "NaN" in str(ei.value)
        # clean inputs pass
        (out,) = exe.run(prog, feed={"x": np.array([1.0, 2.0], np.float32)},
                         fetch_list=["loss"], scope=Scope())
        assert np.isfinite(out)
    finally:
        flags.set_flags({"check_nan_inf": old})


def test_flag_change_invalidates_executor_cache():
    """Same program/scope/feed, flag flipped between runs -> retrace (the
    NaN scan appears without structural program changes)."""
    prog = _nan_program()
    exe = Executor()
    feed = {"x": np.array([-1.0], np.float32)}
    old = flags.get_flag("check_nan_inf")
    try:
        flags.set_flags({"check_nan_inf": False})
        (out,) = exe.run(prog, feed=feed, fetch_list=["loss"],
                         scope=Scope())
        assert np.isnan(out)  # no scan: NaN flows out
        flags.set_flags({"check_nan_inf": True})
        with pytest.raises(Exception):
            exe.run(prog, feed=feed, fetch_list=["loss"], scope=Scope())
    finally:
        flags.set_flags({"check_nan_inf": old})
