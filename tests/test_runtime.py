"""Runtime: checkpoint roundtrip + atomicity, crash-resume, elastic
resharding, straggler watchdog, gradient compression convergence."""
import os
import shutil
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.runtime.checkpoint import CheckpointManager
from repro.runtime.failures import (StepWatchdog, flag_stragglers,
                                    run_with_restarts)


def _state(v=0.0):
    return {"w": jnp.full((8, 4), v, jnp.float32),
            "step": jnp.asarray(3, jnp.int32),
            "nested": {"b": jnp.arange(5, dtype=jnp.float32) + v}}


def test_checkpoint_roundtrip(tmp_path):
    mgr = CheckpointManager(tmp_path)
    s = _state(1.5)
    mgr.save(7, s)
    out = mgr.restore(jax.eval_shape(lambda: s))
    assert float(out["w"][0, 0]) == 1.5 and int(out["step"]) == 3
    assert mgr.latest_step() == 7


def test_checkpoint_async_and_gc(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=2)
    for k in range(5):
        mgr.save_async(k, _state(float(k)))
    mgr.wait()
    mgr.save(99, _state(9.0))
    steps = mgr.all_steps()
    assert 99 in steps and len(steps) <= 2


def test_checkpoint_atomicity(tmp_path):
    """A dir without _COMMITTED must be ignored (crash during save)."""
    mgr = CheckpointManager(tmp_path)
    mgr.save(1, _state(1.0))
    broken = tmp_path / "step_000000099"
    broken.mkdir()
    (broken / "manifest.json").write_text("{}")
    assert mgr.latest_step() == 1


def test_checkpoint_corruption_detected(tmp_path):
    mgr = CheckpointManager(tmp_path)
    s = _state(2.0)
    mgr.save(1, s)
    leaf = next((tmp_path / "step_000000001").glob("leaf_0.npy"))
    arr = np.load(leaf)
    arr.flat[0] += 1
    np.save(leaf, arr)
    with pytest.raises(IOError):
        mgr.restore(jax.eval_shape(lambda: s))


def test_elastic_restore_reshards(tmp_path):
    """Save under mesh (4,2), restore under (2,4) — axis-name rules only."""
    devs = jax.devices()[:8]
    mesh_a = Mesh(np.array(devs).reshape(4, 2), ("data", "model"))
    mesh_b = Mesh(np.array(devs).reshape(2, 4), ("data", "model"))
    x = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
    xa = jax.device_put(x, NamedSharding(mesh_a, P("data", "model")))
    mgr = CheckpointManager(tmp_path)
    mgr.save(1, {"x": xa})
    out = mgr.restore({"x": jax.eval_shape(lambda: x)},
                      shardings={"x": NamedSharding(mesh_b,
                                                    P("data", "model"))})
    assert out["x"].sharding.mesh.shape["model"] == 4
    np.testing.assert_array_equal(np.asarray(out["x"]), np.asarray(x))


def test_crash_resume_end_to_end(tmp_path):
    """Fault injection: training crashes at step 7, recovery resumes from
    the last checkpoint and finishes all steps with a consistent state."""
    from repro.configs import get_config, smoke_variant
    from repro.launch.mesh import make_mesh_shape
    from repro.launch.train import train

    cfg = smoke_variant(get_config("llama3.2-1b"))
    mesh = make_mesh_shape((1, 2), ("data", "model"))
    final, losses = train(cfg, mesh, steps=10, batch=2, seq=32,
                          ckpt_dir=tmp_path, ckpt_every=5, crash_at=7,
                          logger=lambda *a: None)
    assert final == 10
    mgr = CheckpointManager(tmp_path)
    assert mgr.latest_step() == 10


def test_watchdog_flags_stragglers():
    wd = StepWatchdog(k_mad=6.0, warmup=5)
    for i in range(20):
        assert not wd.observe(i, 0.1 + 0.001 * (i % 3))
    assert wd.observe(20, 1.0)          # 10× median → straggler
    assert wd.flagged == [20]


def test_watchdog_stop_without_start_raises():
    """Regression: stop() before start() used to TypeError on None - t0."""
    wd = StepWatchdog()
    with pytest.raises(RuntimeError, match="start"):
        wd.stop(0)
    # and stop() consumes the start: a second stop raises again
    wd.start()
    wd.stop(0, now=wd._t0 + 0.1)
    with pytest.raises(RuntimeError, match="start"):
        wd.stop(1)


def test_watchdog_warmup_boundary():
    """Exactly ``warmup`` history entries is the first flaggable step."""
    wd = StepWatchdog(k_mad=6.0, warmup=5)
    for i in range(5):                   # history 0..4 entries: never flags
        assert not wd.observe(i, 0.1)
    # exactly 5 entries of history now — a 100× outlier must flag
    assert wd.observe(5, 10.0)
    assert wd.flagged == [5]
    # boundary from below: a fresh watchdog with warmup-1 history ignores
    # the same outlier
    wd2 = StepWatchdog(k_mad=6.0, warmup=5)
    for i in range(4):
        wd2.observe(i, 0.1)
    assert not wd2.observe(4, 10.0)


def test_watchdog_window_is_100_entries():
    """The estimate tracks the last 100 steps only: after 100+ slow steps
    the old fast regime has scrolled out and slow is the new normal."""
    wd = StepWatchdog(k_mad=6.0, warmup=5)
    for i in range(10):
        wd.observe(i, 0.1)
    assert wd.observe(10, 10.0)          # slow vs fast history: flags
    for i in range(11, 115):
        wd.observe(i, 10.0)              # regime change
    assert len(wd.times) > 100
    assert not wd.observe(115, 10.0)     # window refilled: no longer flags


def test_flag_stragglers_one_round():
    times = [1.0] * 8
    times[3] = 8.0
    assert flag_stragglers(times) == [3]
    assert flag_stragglers([1.0] * 8) == []
    assert flag_stragglers([]) == []


def test_run_with_restarts_gives_up(tmp_path):
    mgr = CheckpointManager(tmp_path)

    def always_fail(start):
        raise RuntimeError("boom")

    with pytest.raises(RuntimeError):
        run_with_restarts(always_fail, ckpt_manager=mgr, max_restarts=2,
                          logger=lambda *a: None)


def test_run_with_restarts_no_progress_gives_up_early(tmp_path):
    """A crash that never advances the checkpoint must not burn the whole
    restart budget replaying itself — and the give-up log line must not be
    another 'restart N/max'."""
    mgr = CheckpointManager(tmp_path)
    mgr.save(5, _state())                # progress frozen at step 5
    lines, calls = [], []

    def always_fail(start):
        calls.append(start)
        raise RuntimeError("boom")

    with pytest.raises(RuntimeError):
        run_with_restarts(always_fail, ckpt_manager=mgr, max_restarts=10,
                          logger=lines.append)
    assert len(calls) == 2               # initial try + one retry, not 11
    assert "no progress" in lines[-1]
    assert "restart" not in lines[-1].replace("restarts", "")


def test_run_with_restarts_final_raise_not_logged_as_restart():
    lines = []

    def always_fail(start):
        raise ValueError("boom")

    with pytest.raises(ValueError):
        run_with_restarts(always_fail, max_restarts=2, logger=lines.append,
                          progress_fn=None)
    assert "giving up after 2" in lines[-1]
    assert sum("restart " in ln for ln in lines) == 2   # only real retries


def test_run_with_restarts_retry_on_filters():
    """Exceptions outside retry_on propagate without any retry."""
    calls = []

    def fail(start):
        calls.append(start)
        raise KeyError("boom")

    with pytest.raises(KeyError):
        run_with_restarts(fail, max_restarts=5, retry_on=(ValueError,),
                          logger=lambda *a: None)
    assert len(calls) == 1


def test_run_with_restarts_recovers_with_progress():
    """Progress between failures keeps the retry loop alive."""
    state = {"step": 0}

    def fn(start):
        state["step"] += 1
        if state["step"] < 3:
            raise RuntimeError("boom")
        return "done"

    out = run_with_restarts(fn, max_restarts=5, logger=lambda *a: None,
                            progress_fn=lambda: state["step"])
    assert out == "done" and state["step"] == 3


def test_grad_compression_error_feedback():
    """int8 compressed psum with error feedback: SGD on a quadratic must
    converge to the same optimum as exact gradients."""
    from repro.optim.grad_compress import compressed_psum

    p = 4
    devs = jax.devices()[:p]
    mesh = Mesh(np.array(devs), ("data",))
    r = np.random.default_rng(0)
    target = r.normal(size=(32,)).astype(np.float32)
    data = (target[None] + 0.1 * r.normal(size=(p, 32))).astype(np.float32)

    def local_step(w, x, err):
        g = {"w": 2 * (w["w"] - x[0])}
        g, err = compressed_psum(g, err, "data", p)
        return g["w"], err

    w = {"w": jnp.zeros((32,), jnp.float32)}
    err = {"w": jnp.zeros((p, 32), jnp.float32)}
    with mesh:
        stepf = jax.jit(jax.shard_map(
            local_step, mesh=mesh,
            in_specs=(P(), P("data"), P("data")),
            out_specs=(P(), P("data")), check_vma=False))
        for _ in range(200):
            g, err = stepf(w, data, err)
            w = {"w": w["w"] - 0.05 * g}
    got = np.asarray(w["w"])
    assert np.abs(got - data.mean(0)).max() < 2e-2


def test_grad_compression_reduces_wire_bytes():
    """The HLO of the compressed path must move ~4× fewer collective bytes
    than an f32 psum of the same gradient."""
    from repro.launch import hlo_cost
    from repro.optim.grad_compress import compressed_psum_mean
    p = 4
    mesh = Mesh(np.array(jax.devices()[:p]), ("data",))
    g = jnp.zeros((1 << 16,), jnp.float32)
    e = jnp.zeros((1 << 16,), jnp.float32)

    def comp(g, e):
        return compressed_psum_mean(g, e, "data", p)

    def exact(g, e):
        return jax.lax.psum(g, "data") / p, e

    def wire(fn):
        with mesh:
            c = jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=(P(), P()),
                                      out_specs=(P(), P()),
                                      check_vma=False)).lower(g, e).compile()
        a = hlo_cost.analyze(c.as_text())
        return sum(a["collective_bytes"].values())

    assert wire(comp) < 0.45 * wire(exact)


def test_grad_compression_sim_backend():
    """compressed_psum_mean routed through repro.core.comm runs on the sim
    backend at p = 64 emulated PEs (no mesh) and approximates the exact
    mean within int8 quantization error."""
    from repro.core import comm
    from repro.optim.grad_compress import compressed_psum_mean

    p = 64
    r = np.random.default_rng(7)
    data = r.normal(size=(p, 33)).astype(np.float32)
    err0 = np.zeros((p, 33), np.float32)

    def body(g, e):
        return compressed_psum_mean(g, e, "data", p)

    out, err = jax.jit(comm.sim_map(body, "data", p))(
        jnp.asarray(data), jnp.asarray(err0))
    out = np.asarray(out)
    want = data.mean(axis=0)
    # two int8 quantization rounds: error bounded by ~2 quantization steps
    tol = 2.5 * (np.abs(data).max() / 127 + np.abs(want).max() / 127)
    assert np.abs(out - want[None]).max() < tol
    assert np.abs(np.asarray(err)).max() > 0    # residual is being tracked


def test_grad_compression_sim_matches_shard_map_bitwise():
    """Same body, two backends: sim at p = 8 must reproduce the shard_map
    result bit for bit (the comm-layer contract of test_differential)."""
    from repro.core import comm
    from repro.optim.grad_compress import compressed_psum_mean

    p = 8
    r = np.random.default_rng(3)
    data = r.normal(size=(p, 24)).astype(np.float32)
    err0 = np.zeros((p, 24), np.float32)

    def body(g, e):
        return compressed_psum_mean(g, e, "data", p)

    mesh = Mesh(np.array(jax.devices()[:p]), ("data",))

    def blk(g, e):
        o, ne = body(g[0], e[0])
        return o[None], ne[None]

    with mesh:
        out_sm, err_sm = jax.jit(jax.shard_map(
            blk, mesh=mesh, in_specs=(P("data"), P("data")),
            out_specs=(P("data"), P("data")),
            check_vma=False))(jnp.asarray(data), jnp.asarray(err0))
    out_sim, err_sim = jax.jit(comm.sim_map(body, "data", p))(
        jnp.asarray(data), jnp.asarray(err0))
    np.testing.assert_array_equal(np.asarray(out_sm), np.asarray(out_sim))
    np.testing.assert_array_equal(np.asarray(err_sm), np.asarray(err_sim))


def test_elastic_rescale_plan():
    from repro.configs import get_config
    from repro.runtime.elastic import plan_rescale

    cfg = get_config("qwen3-14b")
    # grow 256 → 512 chips keeping model extent
    p = plan_rescale({"data": 16, "model": 16}, 512, cfg, global_batch=256)
    assert p.n_chips == 512 and p.new_shape["model"] == 16
    assert p.grad_accum == 1             # 256 % 32 == 0: no accumulation
    # shrink to 24 chips: model must divide arch dims (17408, 5120)
    p2 = plan_rescale({"data": 16, "model": 16}, 24, cfg, global_batch=256)
    assert p2.n_chips == 24
    assert cfg.d_ff % p2.new_shape["model"] == 0
    # regression: data extent 3 does not divide 256 — the old formula
    # reported accum=1; the plan must pad up to the next multiple of 3
    assert p2.grad_accum == -(-256 // (p2.new_shape["data"] *
                                       p2.new_shape.get("pod", 1)))
    assert p2.grad_accum > 1
    assert any("accum" in nt for nt in p2.notes)
    # degenerate: 1 chip
    p3 = plan_rescale({"data": 16, "model": 16}, 1, cfg, global_batch=256)
    assert p3.new_shape == {"data": 1, "model": 1}
    assert p3.grad_accum == 1            # 256 % 1 == 0


def test_plan_sort_rescale():
    from repro.runtime.elastic import plan_sort_rescale

    # one failure: survivors rounded down to the next power of two
    r = plan_sort_rescale(8, [2])
    assert (r.p_new, r.survivors, r.failed) == (4, 7, (2,))
    # two failures at p=16 → 14 survivors → p=8
    r2 = plan_sort_rescale(16, (3, 9))
    assert r2.p_new == 8
    # exact power of two survivor count is kept
    r3 = plan_sort_rescale(8, [0, 1, 2, 3])
    assert r3.p_new == 4
    # nested: inner extent preserved while it fits, outer absorbs the cut
    r4 = plan_sort_rescale(16, [5], mesh_shape=(4, 4))
    assert r4.p_new == 8 and r4.mesh_shape == (2, 4)
    r5 = plan_sort_rescale(4, [0, 1, 3], mesh_shape=(2, 2))
    assert r5.p_new == 1 and r5.mesh_shape == (1, 1)
    # out-of-range / duplicate ranks are ignored
    assert plan_sort_rescale(8, [2, 2, 99]).p_new == 4
    with pytest.raises(ValueError):
        plan_sort_rescale(2, [0, 1])


def test_elastic_rescale_state_roundtrip(tmp_path):
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config, smoke_variant
    from repro.launch.mesh import make_mesh_shape
    from repro.dist.sharding import make_shardings
    from repro.models import transformer as T
    from repro.runtime.elastic import rescale_state

    cfg = smoke_variant(get_config("llama3.2-1b"))
    mesh_a = make_mesh_shape((4, 2), ("data", "model"))
    mesh_b = make_mesh_shape((2, 4), ("data", "model"))
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    sh_a = make_shardings(jax.eval_shape(lambda: params), cfg, mesh_a)
    params_a = jax.tree.map(jax.device_put, params, sh_a)
    mgr = CheckpointManager(tmp_path)
    mgr.save(5, params_a)
    restored = rescale_state(params_a, params, cfg, mesh_b, mgr)
    got = np.asarray(jax.tree.leaves(restored)[0], np.float32)
    want = np.asarray(jax.tree.leaves(params)[0], np.float32)
    np.testing.assert_array_equal(got, want)
