"""Sharding rules (single-process checks) + multi-device pjit smoke via a
subprocess with 8 forced host devices (XLA device count must stay 1 in the
main test process)."""
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs import ARCHS
from repro.distributed import sharding as shd
from repro import models


def _fake_mesh(shape, axes):
    """Mesh over a single device repeated is illegal; build an abstract-ish
    mesh via np object array of the one device — only mesh.shape is used by
    the rules."""
    n = int(np.prod(shape))
    dev = jax.devices()[0]
    arr = np.array([dev] * n).reshape(shape)
    return Mesh(arr, axes)


MESH = _fake_mesh((4, 2), ("data", "model"))


def test_param_rules_dense():
    shd.set_model_config(ARCHS["qwen3-1.7b"])
    abs_p = models.abstract_params(ARCHS["qwen3-1.7b"])
    import jax.tree_util as jtu
    flat = jtu.tree_flatten_with_path(abs_p)[0]
    specs = {shd._path_str(p): shd.param_spec(MESH, p, l) for p, l in flat}
    assert specs["embed"] == P("model", None)
    wq = [v for k, v in specs.items() if k.endswith("attn/wq")][0]
    assert wq == P(None, None, "model")          # stacked leading unit axis
    wo = [v for k, v in specs.items() if k.endswith("attn/wo")][0]
    assert wo == P(None, "model", None)
    wd = [v for k, v in specs.items() if k.endswith("mlp/w_down")][0]
    assert wd == P(None, "model", None)


def test_gqa_kv_replication_rule():
    """qwen2 has 2 kv heads: on tp=16 the kv projections replicate."""
    mesh16 = _fake_mesh((2, 16), ("data", "model"))
    shd.set_model_config(ARCHS["qwen2-0.5b"])
    abs_p = models.abstract_params(ARCHS["qwen2-0.5b"])
    import jax.tree_util as jtu
    flat = jtu.tree_flatten_with_path(abs_p)[0]
    wk = [(p, l) for p, l in flat if shd._path_str(p).endswith("attn/wk")][0]
    assert shd.param_spec(mesh16, *wk) == P()
    # but q still shards
    wq = [(p, l) for p, l in flat if shd._path_str(p).endswith("attn/wq")][0]
    assert "model" in str(shd.param_spec(mesh16, *wq))
    shd.set_model_config(None)


def test_moe_expert_rules():
    shd.set_model_config(ARCHS["grok-1-314b"])
    abs_p = models.abstract_params(ARCHS["grok-1-314b"])
    import jax.tree_util as jtu
    flat = jtu.tree_flatten_with_path(abs_p)[0]
    wup = [(p, l) for p, l in flat
           if shd._path_str(p).endswith("moe/w_up")][0]
    spec = shd.param_spec(MESH, *wup)
    # grok: 8 experts don't divide nothing here (8%4==0 -> EP over data)
    assert spec[1] == "data" or spec[2] == "data" or "data" in str(spec)
    shd.set_model_config(None)


def test_zero_spec_adds_data_axis():
    shd.set_model_config(None)
    leaf = jax.ShapeDtypeStruct((1024, 512), jax.numpy.float32)
    path = (jax.tree_util.DictKey("m"), jax.tree_util.DictKey("final_norm"),
            jax.tree_util.DictKey("scale"))
    spec = shd.zero_spec(MESH, path, leaf)
    assert "data" in str(spec)


def test_batch_spec():
    assert shd.batch_spec(MESH, 8) == P(("data",))
    assert shd.batch_spec(MESH, 3) == P()


MULTIDEV_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys; sys.path.insert(0, "src")
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import ARCHS, smoke_config
    from repro import models
    from repro.distributed import sharding as shd
    from repro.training import AdamW, constant_schedule, init_state, make_train_step
    from repro.launch.mesh import make_host_mesh

    cfg = smoke_config(ARCHS["qwen3-1.7b"])
    mesh = make_host_mesh(data=4, model=2)
    shd.set_model_config(cfg)
    key = jax.random.PRNGKey(0)
    opt = AdamW(lr=constant_schedule(1e-3))
    with jax.sharding.set_mesh(mesh):
        state = init_state(cfg, opt, key)
        abs_p = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state.params)
        p_shard = shd.param_shardings(mesh, abs_p)
        state = state._replace(params=jax.device_put(state.params, p_shard))
        step = jax.jit(make_train_step(cfg, opt, microbatches=2))
        toks = jax.random.randint(key, (8, 16), 0, cfg.vocab_size)
        batch = {"tokens": toks, "targets": jnp.roll(toks, -1, 1)}
        state, m1 = step(state, batch)
        state, m2 = step(state, batch)
    assert np.isfinite(float(m2["loss"]))
    assert float(m2["loss"]) < float(m1["loss"]) + 0.5
    # single-device reference for numerical agreement
    print("MULTIDEV_OK", float(m1["loss"]))
""")


@pytest.mark.slow
def test_pjit_train_step_8_devices(tmp_path):
    """End-to-end pjit train step on a 4x2 host-device mesh (subprocess so
    the main process keeps 1 device)."""
    script = tmp_path / "multidev.py"
    script.write_text(MULTIDEV_SCRIPT)
    res = subprocess.run([sys.executable, str(script)], cwd=".",
                         capture_output=True, text=True, timeout=600)
    assert "MULTIDEV_OK" in res.stdout, res.stdout + res.stderr
