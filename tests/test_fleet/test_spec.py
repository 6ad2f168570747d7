"""Fleet spec parsing/expansion units (``sheeprl_tpu/fleet/spec.py``)."""

from __future__ import annotations

import pytest

from sheeprl_tpu.fleet.spec import expand_members, load_spec, read_marker, write_marker

pytestmark = pytest.mark.fleet


def _write(tmp_path, text: str) -> str:
    path = tmp_path / "spec.yaml"
    path.write_text(text)
    return str(path)


def test_sweep_expansion_cartesian_with_safe_names(tmp_path):
    spec = load_spec(
        _write(
            tmp_path,
            """
name: demo
base: [exp=ppo]
sweep:
  seed: [42, 43]
  env.id: [CartPole-v1]
""",
        )
    )
    names = [m["name"] for m in spec["members"]]
    assert names == ["seed-42_envid-CartPole-v1", "seed-43_envid-CartPole-v1"]
    assert spec["members"][0]["overrides"] == ["seed=42", "env.id=CartPole-v1"]
    assert spec["base"] == ["exp=ppo"]


def test_explicit_members_append_after_sweep(tmp_path):
    spec = load_spec(
        _write(
            tmp_path,
            """
sweep: {seed: [1]}
members:
  - name: control
    overrides: [seed=9, algo.total_steps=64]
""",
        )
    )
    assert [m["name"] for m in spec["members"]] == ["seed-1", "control"]


@pytest.mark.parametrize(
    "body, match",
    [
        ("base: [exp=ppo]", "no members"),
        ("members: [{name: a}, {name: a}]", "duplicate"),
        ("members: [{name: 'gang'}]", "filesystem-safe"),
        ("members: [{name: 'a/b'}]", "filesystem-safe"),
        ("sweep: {seed: [1]}\ncompare: {fail_on: bogus}", "fail_on"),
    ],
)
def test_invalid_specs_rejected(tmp_path, body, match):
    with pytest.raises(ValueError, match=match):
        load_spec(_write(tmp_path, body))


def test_missing_spec_file(tmp_path):
    with pytest.raises(FileNotFoundError):
        load_spec(str(tmp_path / "nope.yaml"))


def test_defaults_and_env_normalization(tmp_path):
    spec = load_spec(
        _write(
            tmp_path,
            """
sweep: {seed: [1]}
env: {JAX_PLATFORMS: cpu, XLA_FLAGS: null}
""",
        )
    )
    assert spec["max_parallel"] == 1 and spec["stagger_first"] and spec["compile_cache"]
    assert spec["rank_by"] == "sps" and spec["compare"]["baseline"] == "first"
    assert spec["env"] == {"JAX_PLATFORMS": "cpu", "XLA_FLAGS": None}


def test_marker_round_trip(tmp_path):
    spec = load_spec(_write(tmp_path, "name: demo\nsweep: {seed: [1, 2]}"))
    write_marker(str(tmp_path), spec)
    marker = read_marker(str(tmp_path))
    assert marker["name"] == "demo"
    assert marker["members"] == {"seed-1": "members/seed-1", "seed-2": "members/seed-2"}
    assert read_marker(str(tmp_path / "nope")) is None


def test_expand_members_rejects_bare_strings():
    with pytest.raises(ValueError, match="mapping"):
        expand_members({"members": ["just-a-name"]})
