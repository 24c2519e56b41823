"""paddle_tpu.resilience.distributed: sharded elastic checkpoints
(manifest format_version 2, PT605-PT609), cross-replica divergence
detection, and the step watchdog — all on the 8-virtual-device CPU mesh
the suite's conftest configures. The real-kill / real-hang end-to-end
lives in ``tools/chaos_check.py --multichip`` (CI); these tests cover the
same machinery in-process."""
import json
import os
import time
import warnings

import numpy as np
import pytest

import paddle_tpu as fluid
import paddle_tpu.unique_name as un
from paddle_tpu import monitor, resilience
from paddle_tpu.resilience import (CheckpointCorruptError,
                                   ReplicaDivergenceError, WatchdogTimeout,
                                   fault_plan_guard)
from paddle_tpu.resilience import distributed as rdist

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@pytest.fixture
def flags_guard():
    """Snapshot/restore set_flags overrides AND the divergence-recovery
    registration so a failing test can't leak distributed-resilience
    state into the rest of the suite."""
    from paddle_tpu import flags as F

    saved = dict(F._overrides)
    yield fluid.set_flags
    F._overrides.clear()
    F._overrides.update(saved)
    rdist.set_divergence_recovery(None)


def _dp_mesh(n=8):
    return Mesh(np.array(jax.devices()[:n]), ("dp",))


class _Session:
    """A small MLP whose param/moment dims divide 8, so dp-sharding the
    state produces real per-shard slices."""

    def __init__(self, optimizer="adam"):
        self.guard = un.guard()
        self.guard.__enter__()
        self.main, self.startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(self.main, self.startup):
            x = fluid.layers.data("x", shape=[16], dtype="float32")
            y = fluid.layers.data("y", shape=[1], dtype="float32")
            h = fluid.layers.fc(x, 16)
            pred = fluid.layers.fc(h, 1)
            self.loss = fluid.layers.mean(
                fluid.layers.square_error_cost(pred, y))
            opt = (fluid.optimizer.Adam(learning_rate=0.01)
                   if optimizer == "adam"
                   else fluid.optimizer.SGD(learning_rate=0.1))
            opt.minimize(self.loss)
        self.exe = fluid.Executor(fluid.CPUPlace())
        self.scope = fluid.Scope()
        with fluid.scope_guard(self.scope):
            self.exe.run(self.startup)
        self.guard.__exit__(None, None, None)

    def feed(self, batch=8, seed=0):
        rng = np.random.RandomState(seed)
        x = rng.rand(batch, 16).astype(np.float32)
        return {"x": x, "y": rng.rand(batch, 1).astype(np.float32)}

    def run(self, prog=None, **kw):
        with fluid.scope_guard(self.scope):
            return self.exe.run(prog or self.main, feed=self.feed(),
                                fetch_list=[self.loss], **kw)

    def shard_state(self, mesh):
        """Place every dim0-divisible state var dp-sharded (the live-
        sharding source save_sharded_vars inspects), the rest replicated."""
        n = mesh.shape["dp"]
        with fluid.scope_guard(self.scope):
            for name in list(self.scope.vars):
                v = np.asarray(self.scope.find_var(name))
                spec = P("dp") if (v.ndim >= 1 and v.shape[0] % n == 0) \
                    else P()
                self.scope.set_var(name, jax.device_put(
                    jnp.asarray(v), NamedSharding(mesh, spec)))

    def save(self, dirname, meta=None, mesh=None):
        with fluid.scope_guard(self.scope):
            fluid.io.save_checkpoint(self.exe, dirname, self.main,
                                     scope=self.scope, meta=meta or {},
                                     mesh=mesh)

    def image(self):
        return {n: np.asarray(self.scope.find_var(n)).copy()
                for n in self.scope.vars}


# ---------------------------------------------------------------------------
# pillar 1: sharded elastic checkpoints
# ---------------------------------------------------------------------------

def test_sharded_save_restore_roundtrip(tmp_path):
    s = _Session()
    mesh = _dp_mesh()
    s.run()
    s.shard_state(mesh)
    ck = str(tmp_path / "checkpoint_0")
    s.save(ck, meta={"step": 3}, mesh=mesh)
    manifest = resilience.verify_checkpoint(ck)
    assert manifest["format_version"] == 2
    sh = manifest["sharding"]
    assert sh["num_shards"] == 8 and len(sh["shard_files"]) == 8
    # Adam moments + weights with dim0 % 8 == 0 really did split
    assert any(k.startswith("moment") for k in sh["specs"])
    # every shard file is integrity-hashed
    assert all(f in manifest["files"] for f in sh["shard_files"])
    before = s.image()
    scope2 = fluid.Scope()
    with fluid.scope_guard(scope2):
        meta = fluid.io.load_checkpoint(s.exe, ck, s.main, scope=scope2)
    assert meta["step"] == 3
    for n, v in before.items():
        got = scope2.find_var(n)
        if got is not None:
            np.testing.assert_array_equal(np.asarray(got), v)


def test_elastic_restore_8_4_1_matches_full_gather(tmp_path):
    """A checkpoint saved on dp=8 must restore byte-equal on a dp=4
    submesh and on one device, and match the full-gather (v1) restore of
    the same state exactly."""
    s = _Session()
    mesh8 = _dp_mesh(8)
    s.run()
    s.shard_state(mesh8)
    ck_sharded = str(tmp_path / "checkpoint_0")
    ck_full = str(tmp_path / "full" / "checkpoint_0")
    s.save(ck_sharded, meta={"step": 1}, mesh=mesh8)
    s.save(ck_full, meta={"step": 1})          # the full-gather baseline

    def load_bytes(ck, place_mesh=None, device=None):
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            fluid.io.load_checkpoint(s.exe, ck, s.main, scope=scope)
            if place_mesh is not None:   # resume on a SMALLER mesh
                n = place_mesh.shape["dp"]
                for name in list(scope.vars):
                    v = np.asarray(scope.find_var(name))
                    spec = P("dp") if (v.ndim >= 1 and v.shape[0] % n
                                       == 0) else P()
                    scope.set_var(name, jax.device_put(
                        jnp.asarray(v), NamedSharding(place_mesh, spec)))
            if device is not None:       # resume on ONE host device
                for name in list(scope.vars):
                    scope.set_var(name, jax.device_put(
                        scope.find_var(name), device))
            return {n: np.asarray(scope.find_var(n)).copy()
                    for n in scope.vars}

    gather = load_bytes(ck_full)
    elastic4 = load_bytes(ck_sharded, place_mesh=_dp_mesh(4))
    elastic1 = load_bytes(ck_sharded, device=jax.devices()[0])
    assert set(gather) == set(elastic4) == set(elastic1)
    for n in gather:
        np.testing.assert_array_equal(gather[n], elastic4[n], err_msg=n)
        np.testing.assert_array_equal(gather[n], elastic1[n], err_msg=n)


def test_shard_write_fault_leaves_no_published_checkpoint(tmp_path):
    """An injected failure inside one shard's write (the exception flavour
    of the chaos multichip kill) must leave the serial unpublished and the
    previous checkpoint intact."""
    s = _Session()
    mesh = _dp_mesh()
    s.run()
    s.shard_state(mesh)
    ck = str(tmp_path / "checkpoint_0")
    s.save(ck, meta={"step": 1}, mesh=mesh)
    with fault_plan_guard("shard_write:@4:RuntimeError"):
        with pytest.raises(RuntimeError):
            s.save(str(tmp_path / "checkpoint_1"), meta={"step": 2},
                   mesh=mesh)
    assert [sn for sn, _ in resilience.iter_serials(str(tmp_path))] == [0]
    assert resilience.verify_checkpoint(ck)["format_version"] == 2
    assert [p for p in os.listdir(str(tmp_path)) if ".tmp." in p] == []


def _strip_shard(ck, idx=3, drop_hash=True):
    mpath = os.path.join(ck, "manifest.json")
    with open(mpath) as f:
        man = json.load(f)
    sf = man["sharding"]["shard_files"][idx]
    os.remove(os.path.join(ck, sf))
    if drop_hash:
        del man["files"][sf]
    with open(mpath, "w") as f:
        json.dump(man, f)
    return man


def test_sharded_corruption_codes(tmp_path):
    s = _Session()
    mesh = _dp_mesh()
    s.run()
    s.shard_state(mesh)
    ck = str(tmp_path / "checkpoint_0")
    s.save(ck, mesh=mesh)
    mpath = os.path.join(ck, "manifest.json")

    # PT607: shard declared but absent (torn distributed write, variant A:
    # the file was hashed but the writer's data never landed)
    man = _strip_shard(ck, drop_hash=False)
    with pytest.raises(CheckpointCorruptError) as ei:
        resilience.verify_checkpoint(ck)
    assert ei.value.code == "PT607"

    # PT607 variant B: shard present but never integrity-hashed (a writer
    # died between naming its shard and finalize hashing it)
    s.save(ck, mesh=mesh)
    with open(mpath) as f:
        man = json.load(f)
    del man["files"][man["sharding"]["shard_files"][2]]
    with open(mpath, "w") as f:
        json.dump(man, f)
    with pytest.raises(CheckpointCorruptError) as ei:
        resilience.verify_checkpoint(ck)
    assert ei.value.code == "PT607"

    # PT605: shard-count mismatch
    s.save(ck, mesh=mesh)
    with open(mpath) as f:
        man = json.load(f)
    man["sharding"]["num_shards"] = 4
    with open(mpath, "w") as f:
        json.dump(man, f)
    with pytest.raises(CheckpointCorruptError) as ei:
        resilience.verify_checkpoint(ck)
    assert ei.value.code == "PT605"

    # PT609: malformed sharding section
    s.save(ck, mesh=mesh)
    with open(mpath) as f:
        man = json.load(f)
    del man["sharding"]["shard_files"]
    with open(mpath, "w") as f:
        json.dump(man, f)
    with pytest.raises(CheckpointCorruptError) as ei:
        resilience.verify_checkpoint(ck)
    assert ei.value.code == "PT609"

    # PT606/PT608 are load-time: lie about a spec so reassembly breaks
    s.save(ck, mesh=mesh)
    with open(mpath) as f:
        man = json.load(f)
    name = sorted(man["sharding"]["specs"])[0]
    man["vars"][name]["shape"] = [3, 3, 3]
    with open(mpath, "w") as f:
        json.dump(man, f)
    scope2 = fluid.Scope()
    with pytest.raises(CheckpointCorruptError) as ei:
        with fluid.scope_guard(scope2):
            fluid.io.load_checkpoint(s.exe, ck, s.main, scope=scope2,
                                     verify=False)
    assert ei.value.code in ("PT606", "PT608")
    assert not scope2.vars, "failed sharded load must not touch the scope"


def test_recovery_walk_skips_torn_sharded_serial(tmp_path):
    """Satellite: a serial whose manifest declares more shard files than
    are present must be SKIPPED by the recovery walk (counted on
    trainer_ckpt_fallback_total with its PT6xx code), falling back to the
    previous verified serial — never a raw KeyError."""
    s = _Session()
    mesh = _dp_mesh()
    s.run()
    s.shard_state(mesh)
    s.save(str(tmp_path / "checkpoint_0"), meta={"step": 5}, mesh=mesh)
    s.run()
    s.shard_state(mesh)
    s.save(str(tmp_path / "checkpoint_1"), meta={"step": 9}, mesh=mesh)
    _strip_shard(str(tmp_path / "checkpoint_1"))   # torn distributed write
    before = monitor.metric_value("trainer_ckpt_fallback_total",
                                  default=0.0, code="PT607")
    scope2 = fluid.Scope()
    with fluid.scope_guard(scope2):
        meta, serial, skipped = resilience.load_latest_checkpoint(
            s.exe, str(tmp_path), main_program=s.main, scope=scope2)
    assert meta is not None and meta["step"] == 5 and serial == 0
    assert [k["code"] for k in skipped] == ["PT607"]
    after = monitor.metric_value("trainer_ckpt_fallback_total",
                                 default=0.0, code="PT607")
    assert after == before + 1


def test_trainer_sharded_checkpoint_resume(tmp_path):
    """CheckpointConfig(sharded=True) writes format_version-2 serials the
    normal Trainer resume walk restores from."""
    def train_func():
        x = fluid.layers.data("x", shape=[16], dtype="float32")
        y = fluid.layers.data("y", shape=[1], dtype="float32")
        pred = fluid.layers.fc(x, 1, name="fit")
        return fluid.layers.mean(fluid.layers.square_error_cost(pred, y))

    cfg = fluid.contrib.CheckpointConfig(str(tmp_path), step_interval=2,
                                         sharded=True)
    with un.guard():
        t = fluid.contrib.Trainer(train_func,
                                  lambda: fluid.optimizer.SGD(0.05),
                                  checkpoint_config=cfg)
    rng = np.random.RandomState(0)
    batch = [(rng.rand(16).astype(np.float32),
              rng.rand(1).astype(np.float32)) for _ in range(4)]
    t.train(1, lambda ev: None, lambda: iter([batch, batch]), ["x", "y"])
    serials = t._serials()
    assert serials, "sharded trainer checkpoints were not written"
    man = resilience.verify_checkpoint(t._ckpt_path(serials[-1]))
    assert man["format_version"] == 2 and "sharding" in man
    with un.guard():
        t2 = fluid.contrib.Trainer(train_func,
                                   lambda: fluid.optimizer.SGD(0.05),
                                   checkpoint_config=cfg)
    assert t2._step == t._step
    for n, v in t.scope.vars.items():
        got = t2.scope.find_var(n)
        if got is not None:
            np.testing.assert_array_equal(np.asarray(got), np.asarray(v))


# ---------------------------------------------------------------------------
# pillar 2: cross-replica divergence detection
# ---------------------------------------------------------------------------

def _divergent_replicated(mesh, shape=(4, 8), bad_device=3, eps=1.0):
    """A 'replicated' global array whose physical copy differs on ONE
    device — exactly what silent replica divergence looks like."""
    bufs = []
    for i, d in enumerate(mesh.devices.flat):
        a = np.arange(int(np.prod(shape)), dtype=np.float32).reshape(shape)
        if i == bad_device:
            a = a.copy()
            a.flat[0] += eps
        bufs.append(jax.device_put(a, d))
    return jax.make_array_from_single_device_arrays(
        shape, NamedSharding(mesh, P()), bufs)


def test_divergence_detector_negative():
    mesh = _dp_mesh()
    w = jax.device_put(np.ones((4, 8), np.float32),
                       NamedSharding(mesh, P()))
    m = jax.device_put(np.arange(16, dtype=np.float32),
                       NamedSharding(mesh, P("dp")))
    assert rdist.replica_divergence_check(mesh, {"w": w, "m": m}) == []


def test_divergence_detector_positive_names_the_param():
    mesh = _dp_mesh()
    good = jax.device_put(np.ones((4, 8), np.float32),
                          NamedSharding(mesh, P()))
    bad = _divergent_replicated(mesh)
    got = rdist.replica_divergence_check(mesh, {"w_ok": good,
                                                "w_bad": bad})
    assert got == ["w_bad"]
    # a single-ULP flip on one replica is still caught (bit checksums,
    # not tolerance comparison)
    tiny = _divergent_replicated(mesh, eps=np.float32(1e-6))
    assert rdist.replica_divergence_check(mesh, {"t": tiny}) == ["t"]


def test_divergence_policy_raise_and_restore(flags_guard, tmp_path):
    flags_guard({"FLAGS_replica_divergence_policy": "raise"})
    with pytest.raises(ReplicaDivergenceError) as ei:
        rdist.handle_divergence(["fc_0.w_0", "moment1"], path="parallel")
    assert ei.value.param == "fc_0.w_0"
    # restore: a registered recovery walk resolves it
    calls = []
    rdist.set_divergence_recovery(lambda: calls.append(1) or True)
    flags_guard({"FLAGS_replica_divergence_policy": "restore"})
    rdist.handle_divergence(["fc_0.w_0"], path="parallel")
    assert calls == [1]
    # restore with nothing restorable escalates to raise
    rdist.set_divergence_recovery(lambda: False)
    with pytest.raises(ReplicaDivergenceError):
        rdist.handle_divergence(["fc_0.w_0"], path="parallel")


def test_divergence_never_retried():
    assert not resilience.is_transient(ReplicaDivergenceError(["w"]))
    assert not resilience.is_transient(WatchdogTimeout("step", 1.0))


def test_parallel_step_divergence_check_integration(flags_guard):
    """End to end through CompiledProgram: a clean run under
    FLAGS_replica_check_interval=1 never trips; planting a divergent
    replica into the scope trips the NEXT step's check and names it."""
    s = _Session(optimizer="sgd")
    prog = fluid.CompiledProgram(s.main).with_data_parallel(
        loss_name=s.loss.name)
    flags_guard({"FLAGS_replica_check_interval": 1})
    s.run(prog)
    s.run(prog)          # clean steps: the sweep runs and stays silent
    assert monitor.metric_value("resilience_divergence_checks_total",
                                default=0.0) >= 2
    mesh = prog._mesh
    # corrupt ONE replica of a replicated param; the executor reads its
    # physical copies, so the post-step state stays diverged and the
    # in-step check must catch it
    name = next(n for n in s.scope.vars
                if np.asarray(s.scope.find_var(n)).shape == (16, 1))
    v = np.asarray(s.scope.find_var(name))
    bufs = []
    for i, d in enumerate(mesh.devices.flat):
        a = v.copy()
        if i == 2:
            a.flat[0] += 1.0
        bufs.append(jax.device_put(jnp.asarray(a), d))
    with fluid.scope_guard(s.scope):
        s.scope.set_var(name, jax.make_array_from_single_device_arrays(
            v.shape, NamedSharding(mesh, P()), bufs))
    with pytest.raises(ReplicaDivergenceError):
        s.run(prog)


# ---------------------------------------------------------------------------
# pillar 3: step watchdog
# ---------------------------------------------------------------------------

def test_watchdog_silent_on_normal_run(flags_guard):
    s = _Session(optimizer="sgd")
    before = monitor.metric_value("watchdog_timeouts_total", default=0.0,
                                  section="step")
    flags_guard({"FLAGS_step_timeout_s": 60.0})
    s.run()
    s.run()
    assert monitor.metric_value("watchdog_timeouts_total", default=0.0,
                                section="step") == before
    armed = monitor.metric_value("watchdog_sections_armed_total",
                                 default=0.0, section="step")
    assert armed >= 2, "watchdog must actually arm around the step"


def test_watchdog_converts_injected_hang(flags_guard):
    s = _Session(optimizer="sgd")
    s.run()              # compile once so the hang hits a cached step
    flags_guard({"FLAGS_step_timeout_s": 1.0,
                 "FLAGS_watchdog_hard_exit": 0})
    before = monitor.metric_value("watchdog_timeouts_total", default=0.0,
                                  section="step")
    t0 = time.monotonic()
    with fault_plan_guard("hang:@1:hang"):
        with pytest.raises(WatchdogTimeout) as ei:
            s.run()
    elapsed = time.monotonic() - t0
    assert elapsed < 30, f"watchdog took {elapsed:.1f}s to break the hang"
    assert ei.value.section == "step"
    assert monitor.metric_value("watchdog_timeouts_total", default=0.0,
                                section="step") == before + 1
    # the session survives: the scope was never donated into the hung step
    flags_guard({"FLAGS_step_timeout_s": 0.0})
    s.run()


def test_watchdog_direct_section(flags_guard):
    """watchdog_section is usable standalone (the collective wrappers in
    parallel/pipeline and parallel/ring_attention arm it the same way)."""
    flags_guard({"FLAGS_watchdog_hard_exit": 0})
    with pytest.raises(WatchdogTimeout) as ei:
        with resilience.watchdog_section("collective", detail="unit",
                                         timeout=0.5):
            while True:
                time.sleep(0.02)
    assert ei.value.section == "collective" and "unit" in ei.value.detail
    # disabled timeout is a no-op passthrough
    with resilience.watchdog_section("collective", timeout=0):
        pass


# ---------------------------------------------------------------------------
# satellite: the multichip dryrun entry points stay warning-clean
# ---------------------------------------------------------------------------

def test_multichip_paths_no_dtype_truncation_warnings():
    """The int64 UserWarning the MULTICHIP tail showed came from
    ops/tensor.py's jnp.full boundary requesting a width jax would not
    deliver. jnp_dtype requests what ``jax.config.jax_enable_x64`` says
    jax delivers; this runs an int64-heavy program through the
    CompiledProgram mesh path (the dryrun's route) with
    warnings-as-errors."""
    with un.guard():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            ids = fluid.layers.data("ids", shape=[1], dtype="int64")
            y = fluid.layers.data("y", shape=[4], dtype="float32")
            fc64 = fluid.layers.fill_constant([4], "int64", 3)
            oh = fluid.layers.one_hot(ids, depth=4)
            pred = fluid.layers.fc(oh, 4)
            s = (pred + fluid.layers.cast(fc64, "float32")
                 + fluid.layers.cast(fluid.layers.cast(y, "int64"),
                                     "float32"))
            loss = fluid.layers.mean(
                fluid.layers.square_error_cost(s, y))
            fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    prog = fluid.CompiledProgram(main).with_data_parallel(
        loss_name=loss.name)
    feed = {"ids": np.zeros((8, 1), np.int64),
            "y": np.zeros((8, 4), np.float32)}
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        with fluid.scope_guard(scope):
            exe.run(startup)
            (out,) = exe.run(prog, feed=feed, fetch_list=[loss.name])
    assert np.isfinite(np.asarray(out)).all()


def test_jnp_dtype_canonicalizes_64bit():
    from paddle_tpu.core.types import jnp_dtype, np_dtype

    assert np_dtype("int64") == np.dtype("int64")
    if not jax.config.jax_enable_x64:
        assert jnp_dtype("int64") == np.dtype("int32")
        assert jnp_dtype("float64") == np.dtype("float32")
        assert jnp_dtype("uint64") == np.dtype("uint32")
    assert jnp_dtype("bfloat16").name == "bfloat16"
