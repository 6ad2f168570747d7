"""Fabric runtime contracts (reference tests/test_utils/test_fabric.py: the
single-device derivation; plus this build's mesh/sharding/checkpoint-backend
surface)."""

from __future__ import annotations

import jax
import numpy as np
import pytest

from sheeprl_tpu.parallel.fabric import Fabric, get_single_device_fabric


def test_single_device_fabric_shares_runtime_settings():
    f = Fabric(
        devices=2,
        accelerator="cpu",
        precision="bf16-mixed",
        checkpoint_backend="sharded",
        checkpoint_async=True,
    )
    single = get_single_device_fabric(f)
    assert single.requested_devices == 1
    assert single.strategy == "single_device"
    assert single.accelerator == f.accelerator
    assert single.precision == f.precision
    assert single.checkpoint_backend == "sharded"
    assert single.checkpoint_async is True
    assert single._callbacks == []


def test_accelerator_tpu_without_a_tpu_raises_instead_of_serving_the_cpu():
    with pytest.raises(RuntimeError, match=r"no 'tpu' backend.*CpuDevice"):
        Fabric(devices=1, accelerator="tpu")._setup()


def test_peak_flops_knows_the_cpu_has_none_and_refuses_an_unknown_tpu():
    from types import SimpleNamespace

    from sheeprl_tpu.utils.mfu import peak_flops

    assert peak_flops(jax.local_devices()[0]) is None
    assert peak_flops(SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")) == 197e12
    with pytest.raises(ValueError, match="TPU v9"):
        peak_flops(SimpleNamespace(platform="tpu", device_kind="TPU v9"))


def test_mesh_and_world_size():
    f = Fabric(devices=4, accelerator="cpu")
    f._setup()
    assert f.world_size == 4
    assert f.mesh.axis_names == ("data",)
    assert len(f.devices) == 4


def test_devices_auto_takes_all():
    f = Fabric(devices=-1, accelerator="cpu")
    f._setup()
    assert f.world_size == len(jax.devices("cpu"))


def test_too_many_devices_is_actionable():
    f = Fabric(devices=512, accelerator="cpu")
    with pytest.raises(RuntimeError, match="xla_force_host_platform_device_count"):
        f._setup()


def test_precision_policy():
    assert Fabric(precision="32-true").compute_dtype == np.float32
    f16 = Fabric(precision="bf16-mixed")
    assert str(f16.compute_dtype) == "<class 'jax.numpy.bfloat16'>" or "bfloat16" in str(f16.compute_dtype)
    assert f16.param_dtype == np.float32  # mixed keeps f32 master weights


def test_shard_and_allgather_roundtrip():
    import jax.numpy as jnp

    f = Fabric(devices=2, accelerator="cpu")
    f._setup()
    x = jnp.arange(8.0).reshape(4, 2)
    sharded = f.shard_pytree({"x": x})
    assert sharded["x"].sharding.spec == jax.sharding.PartitionSpec("data")
    gathered = f.all_gather(sharded)
    np.testing.assert_array_equal(np.asarray(gathered["x"]), np.asarray(x))


def test_local_mesh_restricts_to_this_process():
    # single process: local == global, but the path must run
    f = Fabric(devices=2, accelerator="cpu", local_mesh=True)
    f._setup()
    assert all(d.process_index == jax.process_index() for d in f.devices)


def test_act_placement_identity_on_cpu_fabric():
    """On a CPU fabric ActPlacement is the identity (no transfers, no copies);
    the select function still shapes the view."""
    import jax
    import numpy as np

    from sheeprl_tpu.parallel.fabric import Fabric
    from sheeprl_tpu.utils.utils import ActPlacement

    fabric = Fabric(devices=1, accelerator="cpu")
    fabric._setup()
    act = ActPlacement(fabric, lambda p: {"actor": p["actor"]})
    assert act.on_cpu is False
    params = {"actor": jax.numpy.ones(3), "critic": jax.numpy.zeros(3)}
    view = act.view(params)
    assert set(view) == {"actor"}
    assert view["actor"] is params["actor"]  # identity, not a copy
    key = jax.random.PRNGKey(0)
    assert act.place(key) is key
    np.testing.assert_array_equal(np.asarray(view["actor"]), np.ones(3))
