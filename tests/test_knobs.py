"""The NOMAD_TPU_* knob registry (`nomad_tpu/knobs.py`).

Every env knob the runtime consults is declared once in `knobs.KNOBS`
and read through the typed accessors; the `knob-registry` static
checker enforces the other side (no raw `os.environ` reads of
`NOMAD_TPU_*` outside the registry).  These tests pin the accessor
semantics the call sites rely on — in particular that every registered
knob parses its own default.
"""
import os

import pytest

from nomad_tpu import knobs

_GETTER = {"str": knobs.get_str, "int": knobs.get_int,
           "float": knobs.get_float, "bool": knobs.get_bool}


@pytest.mark.parametrize("name", sorted(knobs.KNOBS))
def test_every_registered_knob_parses_its_own_default(name):
    knob = knobs.KNOBS[name]
    assert knob.type in _GETTER, f"{name}: unknown type {knob.type!r}"
    assert knob.doc.strip(), f"{name}: empty doc"
    # unset environment (env={}) must resolve the registry default
    # without raising; an empty default means "auto" (None/""/False)
    value = _GETTER[knob.type](name, env={})
    if knob.default == "":
        assert value in (None, "", False)
    elif knob.type == "int":
        assert value == int(knob.default)
    elif knob.type == "float":
        assert value == float(knob.default)
    elif knob.type == "bool":
        assert isinstance(value, bool)
    else:
        assert value == knob.default


def test_env_value_beats_registry_and_call_site_default():
    env = {"NOMAD_TPU_PLAN_BATCH": "7"}
    assert knobs.get_int("NOMAD_TPU_PLAN_BATCH", env=env) == 7
    assert knobs.get_int("NOMAD_TPU_PLAN_BATCH", default=99,
                         env=env) == 7


def test_call_site_default_beats_registry_default():
    assert knobs.get_int("NOMAD_TPU_WAVE", default=6, env={}) == 6
    assert knobs.get_float("NOMAD_TPU_HEARTBEAT_BATCH_MS",
                           default=25.0, env={}) == 25.0


def test_empty_string_counts_as_unset():
    env = {"NOMAD_TPU_WAVE_SHARDS": ""}
    assert knobs.get_int("NOMAD_TPU_WAVE_SHARDS", env=env) is None
    assert knobs.get_bool("NOMAD_TPU_JAX_CACHE",
                          env={"NOMAD_TPU_JAX_CACHE": ""}) is True


@pytest.mark.parametrize("raw,want", [
    ("0", False), ("false", False), ("No", False), ("OFF", False),
    ("1", True), ("true", True), ("yes", True), ("2", True),
])
def test_bool_parse_table(raw, want):
    assert knobs.get_bool("NOMAD_TPU_TRACE",
                          env={"NOMAD_TPU_TRACE": raw}) is want


def test_unregistered_knob_is_a_hard_error():
    with pytest.raises(KeyError):
        knobs.get_str("NOMAD_TPU_NO_SUCH_KNOB")
    with pytest.raises(KeyError):
        with knobs.override("NOMAD_TPU_NO_SUCH_KNOB", "1"):
            pass


@pytest.mark.parametrize("name", [
    "NOMAD_TPU_ENGINE", "NOMAD_TPU_SHARD", "NOMAD_TPU_FUSE",
    "NOMAD_TPU_DONATE", "NOMAD_TPU_OVERLAP"])
def test_removed_path_switch_is_refused(name):
    """The five on/off switches whose off side was deleted (PR 32) must
    not come back as silent no-ops: reading one is the hard error of any
    unregistered knob."""
    assert name not in knobs.KNOBS
    with pytest.raises(KeyError):
        knobs.get_bool(name)


def test_override_scopes_and_restores():
    assert "NOMAD_TPU_PLAN_BATCH" not in os.environ
    with knobs.override("NOMAD_TPU_PLAN_BATCH", 5):
        assert knobs.get_int("NOMAD_TPU_PLAN_BATCH") == 5
        with knobs.override("NOMAD_TPU_PLAN_BATCH", None):
            assert knobs.get_int("NOMAD_TPU_PLAN_BATCH") == 64
        assert os.environ["NOMAD_TPU_PLAN_BATCH"] == "5"
    assert "NOMAD_TPU_PLAN_BATCH" not in os.environ


def test_markdown_table_covers_every_knob():
    table = knobs.markdown_table()
    for name in knobs.KNOBS:
        assert f"`{name}`" in table
