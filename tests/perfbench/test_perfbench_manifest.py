"""BENCHMARK.json and the files it names: a later PR adds cells as files and
entries, so every name has to lead to its file."""

import json
import os
import re

import pytest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@pytest.fixture(scope="module")
def manifest():
    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        return root, json.load(fh)


def test_names_lead_to_files(manifest):
    root, m = manifest
    assert list(m) == ["command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"]
    configs = {c["name"]: c for c in m["configs"]}
    for c in m["configs"]:
        assert NAME.match(c["name"]) and c["file"].startswith(tuple(p + "/" for p in m["paths"]))
        with open(os.path.join(root, c["file"])) as fh:
            data = json.load(fh)
        assert data["name"] == c["name"] and set(c["reduced"]) == set(data["reduced"])
        assert os.path.exists(os.path.join(root, data["reference"]))
        assert set(data["limits"]) == {
            "act_view_gap", "act_state_gap", "act_sample_mismatch", *(f"{g}_{n}_gap" for g in ("wm", "actor", "critic") for n in ("loss1", "loss", "grad", "grad_mid", "update", "update_mid"))}
    for w in m["workloads"]:
        assert NAME.match(w["name"]) and w["config"] in configs and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        assert os.path.exists(os.path.join(root, "perfbench", "traffic", w["traffic"] + ".json"))
    assert len({(w["config"], w["traffic"]) for w in m["workloads"]}) == len(m["workloads"])
    e2e = {e["name"] for e in m["end_to_end"]}
    assert "setup_s" in e2e and all(0 < e["bound"] <= 0.1 for e in m["end_to_end"])
    for p in m["per_layer"]:
        assert p["moves"] in e2e and "bound" not in p
        assert os.path.exists(os.path.join(root, "perfbench", "metrics", p["name"] + ".py"))
    assert any("mfu" in p["name"].split("_") for p in m["per_layer"])


@pytest.mark.parametrize("workload", ["dv3_XL_crafter.train_4env", "dv3_L_doapp128.train", "dv3_XL_crafter.train"])
def test_the_configuration_files_say_what_is_run(manifest, workload, monkeypatch):
    """The `model` block of a configuration's file equals what the program composes
    from the cell's overrides: published widths, batch, sequence and horizon."""
    root, _ = manifest
    from perfbench.harness import bench
    from sheeprl_tpu.config import compose

    monkeypatch.setenv("SHEEPRL_SEARCH_PATH", os.path.join(root, "perfbench", "sheeprl_configs"))
    data = bench.load_cell(workload, root)
    cfg = compose([f"exp={data['config']['exp']}", *data["config"]["overrides"], *data["traffic"]["overrides"]])
    assert bench.spec_from_cfg(cfg) == data["config"]["model"]
    assert cfg.env.sync_env is True and cfg.metric.log_level == 0 and cfg.checkpoint.every == 0
    published = {"dv3_XL_crafter": (1024, 5, 4096, 96, 16, 64), "dv3_L_doapp128": (768, 4, 2048, 64, 8, 128)}
    model = data["config"]["model"]
    assert (model["dense_units"], model["mlp_layers"], model["recurrent_state_size"],
            model["cnn_channels_multiplier"], model["batch_size"], model["screen_size"]) == published[data["cell"]["config"]]


@pytest.mark.parametrize("workload", ["dv3_XL_crafter.train_4env", "dv3_L_doapp128.train"])
def test_the_benchmarks_weights_have_the_programs_layout(manifest, workload, monkeypatch):
    """The reference makes its weights in the program's checkpoint layout: same tree,
    same shapes, and leaf by leaf the spread the program's own init gives (compared at
    the cell's layout and small widths; `jax.eval_shape` keeps it to shapes)."""
    import jax
    import numpy as np

    root, _ = manifest
    from perfbench.harness import bench
    from perfbench.reference import dreamer_v3 as ref
    from sheeprl_tpu.algos.dreamer_v3.agent import build_agent
    from sheeprl_tpu.analysis.programs import tiny_fabric
    from sheeprl_tpu.config import compose
    from sheeprl_tpu.utils.env import make_env

    monkeypatch.setenv("SHEEPRL_SEARCH_PATH", os.path.join(root, "perfbench", "sheeprl_configs"))
    data = bench.load_cell(workload, root)
    small = ["algo.dense_units=24", "algo.world_model.encoder.cnn_channels_multiplier=4",
             "algo.world_model.recurrent_model.recurrent_state_size=40",
             "algo.world_model.transition_model.hidden_size=24",
             "algo.world_model.representation_model.hidden_size=24", "fabric.accelerator=cpu"]
    cfg = compose([f"exp={data['config']['exp']}", *data["config"]["overrides"], *data["traffic"]["overrides"], *small])
    model = bench.spec_from_cfg(cfg)
    env = make_env(cfg, 0, 0)()
    _, theirs = build_agent(tiny_fabric(), (model["actions"],), False, cfg, env.observation_space,
                            jax.random.PRNGKey(0))
    mine = jax.jit(lambda seed: ref.init_params(model, seed))(np.int32(0))
    assert jax.tree_util.tree_structure(theirs) == jax.tree_util.tree_structure(mine)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(theirs)[0], jax.tree_util.tree_leaves(mine)):
        name = jax.tree_util.keystr(path)
        assert a.shape == b.shape and a.dtype == b.dtype, name
        if a.size >= 2000:  # same distribution: compare the spread where there is a sample
            assert np.std(np.asarray(b)) == pytest.approx(np.std(np.asarray(a)), rel=0.1, abs=1e-9), name
        else:
            assert bool(np.any(np.asarray(a))) == bool(np.any(np.asarray(b))) or a.size < 64, name
