"""Checkpoint/restart, straggler detection, elastic re-mesh, data resume."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.checkpointer import AsyncCheckpointer, Checkpointer
from repro.configs.base import ShapeConfig
from repro.configs.registry import get_reduced
from repro.data.pipeline import SyntheticLM
from repro.launch.mesh import auto_mesh
from repro.models import transformer as T
from repro.optim.adamw import AdamWConfig, init_opt_state
from repro.runtime.fault import (PreemptionError, StragglerDetector,
                                 Supervisor)

KEY = jax.random.PRNGKey(0)


def test_checkpoint_roundtrip(tmp_path):
    cfg = get_reduced("qwen2-1.5b", num_layers=2)
    params = T.init_params(cfg, KEY)
    opt = init_opt_state(params, AdamWConfig())
    ck = Checkpointer(str(tmp_path))
    ck.save(7, {"params": params, "opt": opt}, extra={"data": {"step": 7}})
    trees, extra = ck.restore(7, {"params": params, "opt": opt})
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(a, b),
                 params, trees["params"])
    assert extra["data"]["step"] == 7
    assert trees["opt"].step == opt.step


def test_checkpoint_gc_keeps_latest(tmp_path):
    ck = Checkpointer(str(tmp_path), keep=2)
    x = {"w": jnp.ones((3,))}
    for s in (1, 2, 3, 4):
        ck.save(s, {"t": x})
    assert ck.all_steps() == [3, 4]


def test_async_checkpointer(tmp_path):
    ck = AsyncCheckpointer(str(tmp_path))
    x = {"w": jnp.arange(5.0)}
    ck.save_async(3, {"t": x})
    ck.wait()
    trees, _ = ck.restore(3, {"t": x})
    np.testing.assert_array_equal(trees["t"]["w"], x["w"])


def test_supervisor_recovers_from_injected_failure(tmp_path):
    """Training survives a mid-run preemption and reaches total_steps."""
    ck = Checkpointer(str(tmp_path))

    def step_fn(step, st):
        st = dict(st)
        st["trees"] = {"v": {"x": st["trees"]["v"]["x"] + 1.0}}
        return st

    def restore_fn(last):
        trees, extra = ck.restore(last, {"v": {"x": jnp.zeros(())}})
        return {"step": last, "trees": trees, "extra": extra}

    failed = {"done": False}

    def fail_hook(step):
        if step == 7 and not failed["done"]:
            failed["done"] = True
            raise PreemptionError("node lost")

    sup = Supervisor(checkpointer=ck, save_every=5)
    final = sup.run(total_steps=12, state={"step": 0,
                                           "trees": {"v": {"x": jnp.zeros(())}},
                                           "extra": {}},
                    step_fn=step_fn, restore_fn=restore_fn,
                    fail_hook=fail_hook)
    assert sup.restarts == 1
    assert float(final["trees"]["v"]["x"]) == 12.0   # no lost or doubled steps


def test_straggler_detector_flags_slow_steps():
    d = StragglerDetector(threshold=2.0, patience=2)
    verdicts = [d.observe(i, 0.1) for i in range(5)]
    assert set(verdicts[1:]) == {"ok"}
    assert d.observe(5, 0.5) == "straggler"
    assert d.observe(6, 0.5) == "reslot"
    assert d.observe(7, 0.1) == "ok"


def test_data_pipeline_resumable():
    cfg = get_reduced("qwen2-1.5b")
    sh = ShapeConfig("t", 16, 4, "train")
    d1 = SyntheticLM(cfg, sh)
    d1.next_batch(); d1.next_batch()
    st = d1.state()
    b1 = d1.next_batch()
    d2 = SyntheticLM(cfg, sh)
    d2.restore(st)
    b2 = d2.next_batch()
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])


def test_elastic_restore_different_topology(tmp_path):
    """Checkpoint saved ignorant of topology restores onto any mesh."""
    cfg = get_reduced("qwen2-1.5b", num_layers=2)
    params = T.init_params(cfg, KEY)
    ck = Checkpointer(str(tmp_path))
    ck.save(1, {"params": params})
    from repro.runtime.sharding import make_ctx, param_shardings
    mesh = auto_mesh((1, 1), ("data", "model"))
    ctx = make_ctx(mesh)
    sh = param_shardings(ctx, params, cfg)
    trees, _ = ck.restore(1, {"params": params}, shardings={"params": sh})
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(a, b),
                 params, trees["params"])
