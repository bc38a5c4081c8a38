"""Elastic multichip training tests (mxnet_tpu/resilience/elastic.py):
the per-replica fault kinds (chip_loss / replica_delay / param_corrupt),
mesh shrinking, the replica-aware trainer update, dist_tpu mesh-loss
classification (and its elastic-off regression pin), the barrier
watchdog satellite, sharded reshard-on-resume checkpoints with per-shard
CRC + quarantine accounting, the dp8-kill → dp4-resume EXACT loss
parity acceptance, desync-audit detection latency + blame + the
resync → rewind → DivergenceError ladder, straggler detection, and the
<5% disabled-audit overhead bound."""
import os
import warnings

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon
from mxnet_tpu import np as mnp
from mxnet_tpu.base import MXNetError
from mxnet_tpu.parallel import mesh as mesh_mod
from mxnet_tpu.profiler import core as _prof
from mxnet_tpu.resilience import (checkpoint as ckpt, counters, faults,
                                  resilience_stats)
from mxnet_tpu.resilience.elastic import (DesyncAuditHandler,
                                          ElasticBatchProcessor,
                                          ElasticTrainingHandler,
                                          MeshDegraded, StragglerMonitor,
                                          is_mesh_loss, probe_contexts,
                                          replica_fingerprints)
from mxnet_tpu.resilience.faults import ChipLostError
from mxnet_tpu.resilience.guardrails import DivergenceError, all_finite

DP = 8


@pytest.fixture(autouse=True)
def _clean_elastic_state():
    """Every test starts/ends with no fault plan, no straggler monitor,
    reset counters, the default global mesh, and no leftover elastic env
    knobs."""
    faults.clear_plan()
    _prof.reset()
    counters.reset()
    StragglerMonitor.uninstall()
    prev_mesh = mesh_mod.get_mesh()
    saved = {k: os.environ.pop(k, None)
             for k in ("MXNET_FAULT_PLAN", "MXNET_ELASTIC",
                       "MXNET_ELASTIC_MAX_RESTARTS",
                       "MXNET_ELASTIC_MIN_REPLICAS",
                       "MXNET_DESYNC_CHECK_STEPS",
                       "MXNET_DESYNC_MAX_RESYNCS",
                       "MXNET_STRAGGLER_THRESHOLD_MS",
                       "MXNET_COLLECTIVE_TIMEOUT",
                       "MXNET_ELASTIC_REBUILD",
                       "MXNET_ELASTIC_MIN_DP_GROUPS")}
    yield
    faults.clear_plan()
    _prof.reset()
    counters.reset()
    StragglerMonitor.uninstall()
    mesh_mod.set_mesh(prev_mesh)
    for k, v in saved.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v


# ---------------------------------------------------------------------------
# per-replica fault kinds
# ---------------------------------------------------------------------------


def test_chip_loss_kind_raises_with_replica():
    plan = faults.install_plan({"rules": [
        {"site": "s", "kind": "chip_loss", "replica": 5, "at": [1]}]})
    assert plan.check("s") is None
    with pytest.raises(ChipLostError) as ei:
        plan.check("s")
    assert ei.value.replica == 5
    assert plan.check("s") is None  # only once
    assert plan.fired_total() == 1
    assert resilience_stats()["faults_injected"] == 1


def test_chip_loss_never_retried():
    from mxnet_tpu.resilience.retry import is_transient

    assert not is_transient(ChipLostError("chip gone", replica=3))


def test_replica_delay_hits_count_per_target_replica():
    """A replica-targeted rule's `at` indices count the TARGET replica's
    site visits: other replicas pass through without consuming them."""
    plan = faults.install_plan({"rules": [
        {"site": "s", "kind": "replica_delay", "replica": 2,
         "seconds": 0.0, "at": [1]}]})
    # round 0: replicas 0..3 visit; replica 2's first visit is hit 0
    for r in range(4):
        assert plan.check("s", {"replica": r}) is None
    # round 1: replica 2's second visit (hit 1) fires; others don't
    out = [plan.check("s", {"replica": r}) for r in range(4)]
    assert out[0] is None and out[1] is None and out[3] is None
    assert out[2] == {"kind": "replica_delay", "replica": 2,
                      "seconds": 0.0}
    assert plan.fired_total() == 1


def test_param_corrupt_marker_and_replica_matching():
    plan = faults.install_plan({"rules": [
        {"site": "t", "kind": "param_corrupt", "replica": 3, "times": 1}]})
    mk = plan.check("t")  # no replica info: fires for its target
    assert mk == {"kind": "param_corrupt", "replica": 3}
    assert plan.check("t") is None


def test_mesh_loss_classification_markers():
    assert is_mesh_loss(ChipLostError("x", replica=0))
    assert is_mesh_loss(RuntimeError("DEVICE_LOST: peer down"))
    assert not is_mesh_loss(RuntimeError("RESOURCE_EXHAUSTED: oom"))
    assert not is_mesh_loss(ValueError("shape mismatch"))


# ---------------------------------------------------------------------------
# mesh shrinking
# ---------------------------------------------------------------------------


def test_shrink_mesh_power_of_two_and_exact():
    m8 = mesh_mod.make_mesh({"dp": DP})
    m4 = mesh_mod.shrink_mesh(m8, [5], axis="dp")
    assert m4.devices.shape == (4,)  # 7 survivors -> largest 2^k = 4
    assert m4.axis_names == ("dp",)
    m7 = mesh_mod.shrink_mesh(m8, [5], axis="dp", power_of_two=False)
    assert m7.devices.shape == (7,)
    # the lost device is in neither
    lost_dev = m8.devices.flatten()[5]
    assert lost_dev not in set(m4.devices.flatten())
    assert lost_dev not in set(m7.devices.flatten())


def test_shrink_mesh_composite_axis():
    m = mesh_mod.make_mesh({"dp": 4, "tp": 2})
    m2 = mesh_mod.shrink_mesh(m, [1], axis="dp")
    assert m2.devices.shape == (2, 2)  # 3 dp rows -> power-of-two 2
    assert m2.axis_names == ("dp", "tp")


def test_shrink_mesh_validates():
    m8 = mesh_mod.make_mesh({"dp": DP})
    with pytest.raises(MXNetError, match="axis"):
        mesh_mod.shrink_mesh(m8, [0], axis="tp")
    with pytest.raises(MXNetError, match="out of range"):
        mesh_mod.shrink_mesh(m8, [99], axis="dp")
    with pytest.raises(MXNetError, match="no surviving"):
        mesh_mod.shrink_mesh(m8, list(range(DP)), axis="dp")


def test_mesh_contexts_roundtrip():
    m8 = mesh_mod.make_mesh({"dp": DP})
    ctxs = mesh_mod.mesh_contexts(m8)
    assert len(ctxs) == DP
    for ctx, dev in zip(ctxs, m8.devices.flatten()):
        assert ctx.jax_device() == dev


def test_probe_contexts_all_healthy_on_cpu():
    ctxs = mesh_mod.mesh_contexts(mesh_mod.make_mesh({"dp": DP}))
    assert probe_contexts(ctxs) == []


# ---------------------------------------------------------------------------
# dp training: replica-aware forward + per-replica fused update
# ---------------------------------------------------------------------------


def _dp_setup(n_ctx=DP, seed=7, lr=0.05, momentum=0.9):
    from mxnet_tpu.kvstore.dist_tpu import KVStoreDistTPUSync

    mx.random.seed(seed)
    onp.random.seed(seed)
    mesh = mesh_mod.make_mesh({"dp": n_ctx})
    ctxs = mesh_mod.mesh_contexts(mesh)
    net = gluon.nn.Dense(1, in_units=3)
    net.initialize(ctx=ctxs)
    opt = {"learning_rate": lr}
    if momentum:
        opt["momentum"] = momentum
    tr = gluon.Trainer(net.collect_params(), "sgd", opt,
                       kvstore=KVStoreDistTPUSync(mesh=mesh))
    from mxnet_tpu.gluon.contrib.estimator import Estimator

    est = Estimator(net, gluon.loss.L2Loss(), trainer=tr,
                    train_metrics=[gluon.metric.MAE()],
                    batch_processor=ElasticBatchProcessor())
    return net, tr, est


def _make_batches(n=8, batch=8, dim=3, seed=0):
    rng = onp.random.RandomState(seed)
    return [(mnp.array(rng.randn(batch, dim).astype("float32")),
             mnp.array(rng.randn(batch, 1).astype("float32")))
            for _ in range(n)]


def test_replica_context_selects_colocated_replica():
    from mxnet_tpu.gluon.parameter import replica_context

    ctxs = mesh_mod.mesh_contexts(mesh_mod.make_mesh({"dp": 4}))
    p = gluon.Parameter("w", shape=(2,))
    p.initialize(ctx=ctxs)
    assert p.data() is p._data[ctxs[0]]
    with replica_context(ctxs[2]):
        assert p.data() is p._data[ctxs[2]]
        assert p.grad() is p._grad[ctxs[2]]
    assert p.data() is p._data[ctxs[0]]  # scope restored
    # a context the param has no replica on falls back to the first
    with replica_context(mx.cpu(99)):
        assert p.data() is p._data[ctxs[0]]


@pytest.mark.integration
def test_dp8_training_keeps_replicas_bitwise_identical():
    net, tr, est = _dp_setup()
    batches = _make_batches(n=4)
    est.fit(batches, batches=4)
    fps = replica_fingerprints(tr._params)
    assert len(fps) == DP
    assert len(set(fps)) == 1, f"replicas drifted: {fps}"
    assert all_finite([p.data() for p in tr._params])
    # the compiled collective path carried the grads (8 per-device
    # replicas covering the mesh), not the eager fallback
    assert tr._kvstore.last_path == "collective"
    assert tr._kvstore.collective_stats()["eager"] == 0


def test_param_corrupt_site_drifts_exactly_one_replica():
    net, tr, est = _dp_setup(momentum=0.0)
    batches = _make_batches(n=3)
    faults.install_plan({"rules": [
        {"site": "trainer:param", "kind": "param_corrupt", "replica": 4,
         "at": [1]}]})
    est.fit(batches, batches=3)
    faults.clear_plan()
    fps = replica_fingerprints(tr._params)
    majority = max(set(fps), key=fps.count)
    deviants = [i for i, fp in enumerate(fps) if fp != majority]
    assert deviants == [4]
    assert all_finite([p.data() for p in tr._params])  # drift is finite


def test_multi_replica_rejects_unsafe_update_paths():
    net, tr, est = _dp_setup()
    tr._optimizer.fused_safe = False
    batches = _make_batches(n=1)
    with pytest.raises(MXNetError, match="multi-replica"):
        est.fit(batches, batches=1)


# ---------------------------------------------------------------------------
# dist_tpu: elastic classification + barrier satellite
# ---------------------------------------------------------------------------


def _per_device_ones(shape=(4,)):
    import jax

    return [mx.nd.NDArray(jax.device_put(
        onp.ones(shape, "float32"), d)) for d in jax.devices()]


def test_chip_loss_elastic_off_degrades_to_eager_regression_pin():
    """Default-off pin: without MXNET_ELASTIC a chip_loss is just another
    fatal fast-path failure — degrade to eager, count it, keep the PR-2
    semantics bitwise. No MeshDegraded anywhere."""
    from mxnet_tpu.kvstore.dist_tpu import KVStoreDistTPUSync

    kv = KVStoreDistTPUSync()
    assert not kv._elastic
    faults.install_plan({"rules": [
        {"site": "kvstore:allreduce", "kind": "chip_loss", "replica": 2,
         "times": 1}]})
    with pytest.warns(RuntimeWarning, match="degraded to the eager"):
        out = kv.allreduce(_per_device_ones())
    faults.clear_plan()
    onp.testing.assert_allclose(out[0].asnumpy(), float(DP))
    s = kv.collective_stats()
    assert s["degradations"] == 1 and s["mesh_losses"] == 0
    assert kv.last_path == "eager"
    assert resilience_stats()["mesh_losses"] == 0


def test_chip_loss_elastic_on_raises_mesh_degraded():
    os.environ["MXNET_ELASTIC"] = "1"
    from mxnet_tpu.kvstore.dist_tpu import KVStoreDistTPUSync

    kv = KVStoreDistTPUSync()
    faults.install_plan({"rules": [
        {"site": "kvstore:allreduce", "kind": "chip_loss", "replica": 6,
         "times": 1}]})
    with pytest.warns(RuntimeWarning, match="MESH LOSS"):
        with pytest.raises(MeshDegraded) as ei:
            kv.allreduce(_per_device_ones())
    faults.clear_plan()
    assert ei.value.lost_replicas == [6]
    assert ei.value.mesh_size == DP
    s = kv.collective_stats()
    assert s["mesh_losses"] == 1
    assert s["degradations"] == 0  # NOT a degradation: it escalated
    assert resilience_stats()["mesh_losses"] == 1
    # transients still degrade/retry exactly as before, even elastic-on
    faults.install_plan({"rules": [
        {"site": "kvstore:allreduce", "kind": "fatal", "times": 1}]})
    with pytest.warns(RuntimeWarning, match="degraded to the eager"):
        out = kv.allreduce(_per_device_ones())
    faults.clear_plan()
    onp.testing.assert_allclose(out[0].asnumpy(), float(DP))


def test_breaker_open_probes_devices_for_mesh_loss():
    """With the breaker open the fast path (and its fault sites) never
    runs, so a chip dying during the cooldown throws no classifiable
    error — the elastic path must PROBE the devices instead of letting
    the eager fallback silently sum a dead replica's stale buffer."""
    os.environ["MXNET_ELASTIC"] = "1"
    from mxnet_tpu.kvstore.dist_tpu import KVStoreDistTPUSync

    kv = KVStoreDistTPUSync()
    for _ in range(kv._breaker.failure_threshold):
        kv._breaker.record_failure()
    assert not kv._breaker.allow()  # open (consumes one cooldown call)
    # healthy devices: breaker-skip degrades to eager exactly as before
    out = kv.allreduce(_per_device_ones())
    onp.testing.assert_allclose(out[0].asnumpy(), float(DP))
    assert kv.collective_stats()["mesh_losses"] == 0
    # dead device 5: the probe classifies it as mesh loss
    kv._probe_lost_devices = lambda: [5]
    with pytest.warns(RuntimeWarning, match="MESH LOSS"):
        with pytest.raises(MeshDegraded) as ei:
            kv.allreduce(_per_device_ones())
    assert ei.value.lost_replicas == [5]
    assert kv.collective_stats()["mesh_losses"] == 1
    # elastic OFF: the probe never runs, breaker-skip stays pure PR-2
    os.environ.pop("MXNET_ELASTIC")
    kv2 = KVStoreDistTPUSync()
    kv2._probe_lost_devices = lambda: [5]
    for _ in range(kv2._breaker.failure_threshold):
        kv2._breaker.record_failure()
    out = kv2.allreduce(_per_device_ones())
    onp.testing.assert_allclose(out[0].asnumpy(), float(DP))


def test_partial_batch_smaller_than_replica_count_stays_finite():
    """Regression: a final batch with fewer rows than replicas must not
    NaN the mesh (empty-slice mean) nor sum stale grads from idle
    replicas."""
    net, tr, est = _dp_setup(momentum=0.0)
    batches = _make_batches(n=3) + _make_batches(n=1, batch=4, seed=9)
    est.fit(batches, batches=4)
    assert all_finite([p.data() for p in tr._params])
    assert len(set(replica_fingerprints(tr._params))) == 1


def test_barrier_fires_fault_site_and_watchdog():
    """Satellite: barrier runs under the MXNET_COLLECTIVE_TIMEOUT
    watchdog and fires collective:barrier — a hung barrier becomes a
    diagnosable CollectiveTimeoutError, not an infinite wait."""
    from mxnet_tpu.kvstore.dist_tpu import KVStoreDistTPUSync
    from mxnet_tpu.resilience.retry import CollectiveTimeoutError

    # the psum's first call compiles (0.18-0.35 s here): outside the budget
    KVStoreDistTPUSync().barrier()
    os.environ["MXNET_COLLECTIVE_TIMEOUT"] = "0.2"
    kv = KVStoreDistTPUSync()
    kv.barrier()  # clean barrier passes under the watchdog
    plan = faults.install_plan({"rules": [
        {"site": "collective:barrier", "kind": "delay", "seconds": 2.0,
         "times": 1}]})
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # orphan-accounting warning
        with pytest.raises(CollectiveTimeoutError, match="barrier"):
            kv.barrier()
    faults.clear_plan()
    assert plan.fired_total() == 1
    kv.barrier()  # recovered
    assert resilience_stats()["watchdog_timeouts"] >= 1


def test_barrier_fault_site_without_watchdog():
    from mxnet_tpu.kvstore.dist_tpu import KVStoreDistTPUSync

    kv = KVStoreDistTPUSync()
    faults.install_plan({"rules": [
        {"site": "collective:barrier", "kind": "fatal", "times": 1}]})
    with pytest.raises(faults.InjectedFaultError):
        kv.barrier()
    faults.clear_plan()
    kv.barrier()


# ---------------------------------------------------------------------------
# sharded reshard-on-resume checkpoints
# ---------------------------------------------------------------------------


def _trained_dp(n_ctx, seed=7, steps=2):
    net, tr, est = _dp_setup(n_ctx=n_ctx, seed=seed)
    est.fit(_make_batches(n=steps), batches=steps)
    return net, tr


def test_sharded_roundtrip_same_dp(tmp_path):
    net, tr = _trained_dp(DP)
    before = {k: v.data().asnumpy().copy()
              for k, v in net.collect_params().items()}
    path = str(tmp_path / "s.ckpt")
    ckpt.save_sharded_checkpoint(path, net=net, trainer=tr,
                                 num_shards=DP, mesh_axes={"dp": DP},
                                 meta={"note": "x"})
    shard_files = [f for f in os.listdir(tmp_path) if ".shard" in f]
    assert len(shard_files) == DP  # CRC-per-shard: one container each
    net2, tr2 = _trained_dp(DP, seed=99, steps=1)
    params, meta = ckpt.load_checkpoint(path, net=net2, trainer=tr2)
    assert meta["sharded"] and meta["mesh_axes"] == {"dp": DP}
    assert meta["note"] == "x"
    for k, v in net2.collect_params().items():
        onp.testing.assert_array_equal(v.data().asnumpy(), before[k])
    assert tr2._step_count == tr._step_count
    fps = replica_fingerprints(tr2._params)
    assert len(set(fps)) == 1  # restored onto every replica


def test_sharded_reshard_dp8_save_dp4_resume(tmp_path):
    net, tr = _trained_dp(DP)
    before = {k: v.data().asnumpy().copy()
              for k, v in net.collect_params().items()}
    path = str(tmp_path / "r.ckpt")
    ckpt.save_sharded_checkpoint(path, net=net, trainer=tr,
                                 num_shards=DP, mesh_axes={"dp": DP})
    net4, tr4 = _trained_dp(4, seed=99, steps=1)
    with pytest.warns(RuntimeWarning, match="resharding"):
        ckpt.load_checkpoint(path, net=net4, trainer=tr4)
    for k, v in net4.collect_params().items():
        onp.testing.assert_array_equal(v.data().asnumpy(), before[k])
        assert len(v._data) == 4  # restored onto the dp4 replica set
    assert len(set(replica_fingerprints(tr4._params))) == 1
    assert resilience_stats()["reshard_resumes"] == 1


def test_sharded_corrupt_shard_fails_atomically_and_quarantines(tmp_path):
    net, tr = _trained_dp(DP)
    mgr = ckpt.CheckpointManager(tmp_path, max_keep=5)
    mgr.save(1, net=net, trainer=tr, sharded=True, num_shards=DP,
             mesh_axes={"dp": DP})
    good = {k: v.data().asnumpy().copy()
            for k, v in net.collect_params().items()}
    # train on, save step 2 sharded, then corrupt ONE of its shards
    est_net, est_tr = net, tr
    path2 = mgr.save(2, net=est_net, trainer=est_tr, sharded=True,
                     num_shards=DP, mesh_axes={"dp": DP})
    victim = [f for f in sorted(os.listdir(tmp_path))
              if "-000000000002" in f and ".shard03" in f][0]
    vpath = os.path.join(tmp_path, victim)
    raw = bytearray(open(vpath, "rb").read())
    raw[len(raw) // 2] ^= 0xFF
    open(vpath, "wb").write(bytes(raw))

    net2, tr2 = _trained_dp(DP, seed=99, steps=1)
    with pytest.warns(RuntimeWarning, match="corrupt checkpoint"):
        meta = mgr.load_latest(net=net2, trainer=tr2)
    assert meta["step"] == 1  # rolled back past the torn step
    for k, v in net2.collect_params().items():
        onp.testing.assert_array_equal(v.data().asnumpy(), good[k])
    # manifest AND shards quarantined together
    assert os.path.exists(mgr._path(2) + ".corrupt")
    orphans = [f for f in os.listdir(tmp_path)
               if "-000000000002" in f and ".shard" in f
               and not f.endswith(".corrupt")]
    assert orphans == []
    assert resilience_stats()["checkpoints_quarantined"] == 1


def test_sharded_missing_shard_detected(tmp_path):
    net, tr = _trained_dp(DP, steps=1)
    path = str(tmp_path / "m.ckpt")
    ckpt.save_sharded_checkpoint(path, net=net, trainer=tr, num_shards=4)
    os.remove(path + ".shard02-of04")
    with pytest.raises(ckpt.CheckpointCorruptError, match="missing shard"):
        ckpt.load_checkpoint(path)


def test_quarantine_counter_and_warning_names_file(tmp_path):
    """Satellite: load_latest quarantine events are visible — a counter
    plus a rate-limited warning naming the quarantined file (previously a
    silent rename)."""
    net, tr = _trained_dp(2, steps=1)
    mgr = ckpt.CheckpointManager(tmp_path, max_keep=5)
    mgr.save(1, net=net, trainer=tr)
    mgr.save(2, net=net, trainer=tr)
    p2 = mgr._path(2)
    raw = bytearray(open(p2, "rb").read())
    raw[-6] ^= 0x55
    open(p2, "wb").write(bytes(raw))
    with pytest.warns(RuntimeWarning) as rec:
        meta = mgr.load_latest(net=net, trainer=tr)
    assert meta["step"] == 1
    quarantine_warnings = [w for w in rec
                           if "checkpoint quarantined" in str(w.message)]
    assert len(quarantine_warnings) == 1
    assert os.path.basename(p2) in str(quarantine_warnings[0].message)
    assert resilience_stats()["checkpoints_quarantined"] == 1


# ---------------------------------------------------------------------------
# acceptance: dp8 kill -> dp4 resume, exact parity (seed-swept)
# ---------------------------------------------------------------------------


@pytest.mark.integration
@pytest.mark.parametrize("seed", [7, 14])  # 14 kills replica 0 (the
                                           # state-migration edge)
def test_kill_and_reshard_resume_exact_parity(seed):
    """The acceptance scenario, via the soak harness's kill leg: a dp8
    run killed mid-step by an injected chip_loss resumes at dp4 from its
    own sharded checkpoint and matches — bitwise — an uninterrupted dp4
    run continued from that checkpoint over the same remaining
    batches."""
    from tools.elastic_soak import run_kill_reshard

    violations, row = run_kill_reshard(seed=seed, n_batches=10)
    assert violations == []
    assert row["steps_lost"] == 1  # exactly the killed batch
    assert row["dp_from"] == DP and row["dp_to"] == DP // 2
    assert row["data_parity"] == "exact"  # iterator rewound with params
    assert row["recovery_wall_s"] is not None
    assert resilience_stats()["mesh_losses"] == 1
    assert resilience_stats()["elastic_restarts"] == 1


@pytest.mark.integration
def test_elastic_restart_budget_exhausted_reraises(tmp_path):
    os.environ["MXNET_ELASTIC"] = "1"
    net, tr, est = _dp_setup()
    eh = ElasticTrainingHandler(str(tmp_path), batch_period=1,
                                max_restarts=0)
    faults.install_plan({"rules": [
        {"site": "kvstore:allreduce", "kind": "chip_loss", "replica": 1,
         "at": [4]}]})
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        with pytest.raises(MeshDegraded):
            est.fit(_make_batches(n=6), batches=6, event_handlers=[eh])
    faults.clear_plan()
    assert eh.stats["restarts"] == 0
    assert eh.stats["mesh_losses"] == 1


@pytest.mark.integration
def test_chip_loss_before_first_save_leaves_process_unmutated(tmp_path):
    """Regression: a mesh loss with NO checkpoint on disk must re-raise
    WITHOUT half-restarting the process — mesh, kvstore, and replica set
    all stay at dp8 (the bug: shrink+rebind+reset_ctx ran before the
    restore was known to be possible)."""
    os.environ["MXNET_ELASTIC"] = "1"
    net, tr, est = _dp_setup()
    kv_before = tr.kvstore
    eh = ElasticTrainingHandler(str(tmp_path), batch_period=1)
    faults.install_plan({"rules": [
        {"site": "kvstore:allreduce", "kind": "chip_loss", "replica": 2,
         "at": [0]}]})  # first allreduce of the FIRST batch
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        with pytest.raises(MeshDegraded):
            est.fit(_make_batches(n=4), batches=4, event_handlers=[eh])
    faults.clear_plan()
    assert tr._kvstore is kv_before          # kvstore not rebound
    assert tr._kvstore._mesh.size == DP      # mesh not shrunk
    assert len(tr._params[0]._data) == DP    # replicas not re-homed
    assert eh.stats["restarts"] == 0


def test_spurious_mesh_loss_with_healthy_probe_refuses_restart(tmp_path):
    """A MeshDegraded that names no lost replica AND whose probe finds
    every context healthy is a misclassified transient — the handler
    must re-raise rather than shrink a healthy mesh or burn a restart."""
    net, tr, est = _dp_setup()
    eh = ElasticTrainingHandler(str(tmp_path), batch_period=1)
    with pytest.warns(RuntimeWarning, match="misclassified transient"):
        absorbed = eh.step_error(est, MeshDegraded("flaky", mesh_size=DP))
    assert absorbed is False
    assert eh.stats["restarts"] == 0


def test_quarantined_shards_survive_rotation_and_requarantine(tmp_path):
    """Regression: rotation and re-quarantine must not touch
    already-quarantined .corrupt shard siblings (the evidence files the
    quarantine exists to preserve)."""
    net, tr = _trained_dp(2, steps=1)
    mgr = ckpt.CheckpointManager(tmp_path, max_keep=2)
    mgr.save(1, net=net, trainer=tr, sharded=True, num_shards=2)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        assert mgr.quarantine(1)
    corrupt = sorted(f for f in os.listdir(tmp_path)
                     if f.endswith(".corrupt"))
    assert len(corrupt) == 3  # manifest + 2 shards
    # new saves under the same steps rotate old ones out — the .corrupt
    # files must survive, and quarantining step 1 again must not
    # double-rename them
    for s in (1, 2, 3, 4):
        mgr.save(s, net=net, trainer=tr, sharded=True, num_shards=2)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        mgr.quarantine(1)
    still = sorted(f for f in os.listdir(tmp_path)
                   if f.endswith(".corrupt"))
    assert [f for f in still if f in corrupt] == corrupt
    assert not any(f.endswith(".corrupt.corrupt") for f in
                   os.listdir(tmp_path))


@pytest.mark.integration
def test_elastic_min_replicas_floor(tmp_path):
    """Survivor count below MXNET_ELASTIC_MIN_REPLICAS re-raises instead
    of resuming on a sliver of the mesh."""
    os.environ["MXNET_ELASTIC"] = "1"
    net, tr, est = _dp_setup()
    eh = ElasticTrainingHandler(str(tmp_path), batch_period=1,
                                min_replicas=DP)  # any loss is fatal
    faults.install_plan({"rules": [
        {"site": "kvstore:allreduce", "kind": "chip_loss", "replica": 3,
         "at": [2]}]})
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        with pytest.raises(MeshDegraded):
            est.fit(_make_batches(n=4), batches=4, event_handlers=[eh])
    faults.clear_plan()


# ---------------------------------------------------------------------------
# desync audit
# ---------------------------------------------------------------------------


CORRUPT_STEP = 3


def _fit_with_audit(audit, n=8, rules=None, ctx_n=DP):
    net, tr, est = _dp_setup(n_ctx=ctx_n, momentum=0.0)
    if rules:
        faults.install_plan({"rules": rules})
    try:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            est.fit(_make_batches(n=n), batches=n, event_handlers=[audit])
    finally:
        faults.clear_plan()
    return net, tr, est


@pytest.mark.integration
def test_desync_detected_within_cadence_and_blamed():
    """Acceptance: a single-replica corruption at step k is detected
    within MXNET_DESYNC_CHECK_STEPS batches and blames the right
    replica."""
    cadence = 2
    audit = DesyncAuditHandler(check_steps=cadence)
    _fit_with_audit(audit, rules=[
        {"site": "trainer:param", "kind": "param_corrupt", "replica": 5,
         "at": [CORRUPT_STEP]}])
    assert audit.stats["trips"] == 1
    assert audit.stats["last_blamed"] == [5]
    assert audit.stats["resyncs"] == 1
    # detection latency: the first audit at/after the corruption caught
    # it — within `cadence` batches by construction (trips==1 on the
    # first post-corruption audit, and later audits found agreement)
    assert resilience_stats()["desync_trips"] == 1
    assert resilience_stats()["desync_resyncs"] == 1


@pytest.mark.integration
def test_desync_resync_restores_agreement_and_training_continues():
    audit = DesyncAuditHandler(check_steps=1)
    net, tr, _ = _fit_with_audit(audit, rules=[
        {"site": "trainer:param", "kind": "param_corrupt", "replica": 2,
         "at": [2]}])
    fps = replica_fingerprints(tr._params)
    assert len(set(fps)) == 1  # resynced, group bitwise-identical again
    assert all_finite([p.data() for p in tr._params])
    assert audit.stats["trips"] == 1  # later audits found agreement


@pytest.mark.integration
def test_desync_escalates_resync_budget_to_rewind(tmp_path):
    """Resync budget 0 + a manager: the ladder escalates straight to
    rewind (consistent-by-construction restore)."""
    net, tr, est = _dp_setup(momentum=0.0)
    from mxnet_tpu.gluon.contrib.estimator import \
        ResilientCheckpointHandler

    ck = ResilientCheckpointHandler(str(tmp_path), batch_period=1)
    audit = DesyncAuditHandler(manager=ck, check_steps=1, max_resyncs=0)
    faults.install_plan({"rules": [
        {"site": "trainer:param", "kind": "param_corrupt", "replica": 1,
         "at": [2]}]})
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        est.fit(_make_batches(n=6), batches=6,
                event_handlers=[ck, audit])
    faults.clear_plan()
    assert audit.stats["rewinds"] == 1
    assert audit.stats["resyncs"] == 0
    assert len(set(replica_fingerprints(tr._params))) == 1
    assert resilience_stats()["desync_rewinds"] == 1


def test_desync_no_manager_no_budget_diverges():
    audit = DesyncAuditHandler(check_steps=1, max_resyncs=0)
    with pytest.raises(DivergenceError, match="no CheckpointManager"):
        _fit_with_audit(audit, rules=[
            {"site": "trainer:param", "kind": "param_corrupt",
             "replica": 1, "at": [1]}])


def test_desync_audit_disabled_is_inert():
    audit = DesyncAuditHandler(check_steps=0)
    _fit_with_audit(audit, n=3, rules=[
        {"site": "trainer:param", "kind": "param_corrupt", "replica": 1,
         "at": [1]}])
    assert audit.stats["audits"] == 0
    assert audit.stats["trips"] == 0  # corruption sailed through, by
    # design: the knob is off (the default-off contract)


# ---------------------------------------------------------------------------
# straggler detection
# ---------------------------------------------------------------------------


@pytest.mark.integration
def test_straggler_flagged_with_correct_replica():
    mon = StragglerMonitor(threshold_ms=8.0).install()
    net, tr, est = _dp_setup()
    faults.install_plan({"rules": [
        {"site": "trainer:replica_step", "kind": "replica_delay",
         "replica": 6, "seconds": 0.02, "times": 8}]})
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        est.fit(_make_batches(n=4), batches=4)
    faults.clear_plan()
    StragglerMonitor.uninstall()
    assert mon.stats["flags"] >= 1
    assert mon.stats["last_straggler"] == 6
    snap = mon.snapshot()
    assert snap["lag_ms"][6] > max(
        v for r, v in snap["lag_ms"].items() if r != 6)
    assert resilience_stats()["stragglers"] >= 1
    # per-replica step-time gauges landed on the profiler counter bus
    assert _prof.get_counter("resilience.replica_step_ms[6]") > 0


def test_straggler_monitor_observe_via_allreduce_site():
    """The kvstore:allreduce site reports injected replica_delay lag to
    the installed monitor (the collective-arrival path)."""
    from mxnet_tpu.kvstore.dist_tpu import KVStoreDistTPUSync

    mon = StragglerMonitor(threshold_ms=1.0).install()
    kv = KVStoreDistTPUSync()
    faults.install_plan({"rules": [
        {"site": "kvstore:allreduce", "kind": "replica_delay",
         "replica": 3, "seconds": 0.005, "times": 2}]})
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        kv.allreduce(_per_device_ones())
        kv.allreduce(_per_device_ones())
    faults.clear_plan()
    StragglerMonitor.uninstall()
    assert mon.stats["last_straggler"] == 3
    assert mon.stats["flags"] >= 1


def test_straggler_threshold_zero_tracks_but_never_flags():
    mon = StragglerMonitor(threshold_ms=0.0)
    mon.observe(2, 10.0)  # a 10-SECOND lag
    assert mon.stats["flags"] == 0
    assert mon.snapshot()["lag_ms"][2] > 0


# ---------------------------------------------------------------------------
# soak harness + overhead bound + tier-1 wiring
# ---------------------------------------------------------------------------


@pytest.mark.integration
def test_elastic_soak_smoke():
    """One seeded kill/lag/corrupt sweep through the importable harness —
    the closed-taxonomy contract (no hang, no silent divergence)."""
    from tools.elastic_soak import run_soak

    report = run_soak(seed=3, n_batches=10, verbose=False)
    assert report["ok"], report["violations"]
    assert report["kill"]["steps_lost"] == 1
    assert report["corrupt"]["trips"] >= 1


@pytest.mark.slow
@pytest.mark.parametrize("seed", list(range(20, 28)))
def test_elastic_soak_seed_sweep(seed):
    from tools.elastic_soak import run_soak

    report = run_soak(seed=seed, n_batches=12, verbose=False)
    assert report["ok"], report["violations"]


def test_disabled_audit_overhead_under_5pct():
    """An installed-but-disabled DesyncAuditHandler (check_steps=0, the
    production default) must stay within the 5% overhead bound on a
    small fit loop — measurement discipline from
    test_disabled_guardrail_overhead_under_5pct, including the 15%
    hard-fail threshold for suite-load noise."""
    import time as _time

    net, tr, est = _dp_setup(n_ctx=1)
    batches = _make_batches(n=20, batch=4)
    idle = DesyncAuditHandler(check_steps=0)

    def loop(handlers):
        t0 = _time.perf_counter()
        est.fit(batches, batches=len(batches), event_handlers=handlers)
        return _time.perf_counter() - t0

    def measure(rounds=5):
        base = active = float("inf")
        for _ in range(rounds):
            base = min(base, loop(None))
            active = min(active, loop([idle]))
        return base, active

    loop(None)  # warm executables
    base, active = measure()
    if active > base * 1.05:
        base, active = measure(rounds=7)
    if active > base * 1.05:
        base, active = measure(rounds=9)
    assert active <= base * 1.15, (
        f"disabled-audit overhead {active / base - 1:.1%} "
        f"(no-handler {base:.3f}s, idle-audit {active:.3f}s)")
    assert idle.stats["audits"] == 0


def test_run_tier1_carries_elastic_smoke():
    """Satellite: the tier-1 gate runs the elastic soak smoke
    (TIER1_ELASTIC=0 skips), like the serve and chaos smokes."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "run_tier1.sh")
    src = open(path).read()
    assert "elastic_soak" in src
    assert "TIER1_ELASTIC" in src


def test_elastic_knobs_registered_and_default_off():
    from mxnet_tpu import config

    assert config.get("MXNET_ELASTIC") is False
    assert config.get("MXNET_DESYNC_CHECK_STEPS") == 0
    assert config.get("MXNET_STRAGGLER_THRESHOLD_MS") == 0.0
    assert config.get("MXNET_ELASTIC_MAX_RESTARTS") == 2
    assert config.get("MXNET_ELASTIC_MIN_REPLICAS") == 1
    assert config.get("MXNET_DESYNC_MAX_RESYNCS") == 2


# ---------------------------------------------------------------------------
# composed-mesh elasticity (dp×tp): rebuild_mesh policy, coordinate
# faults, layout-carrying sharded checkpoints, the dp2×tp2 kill pin
# ---------------------------------------------------------------------------


def _mesh_2x2():
    return mesh_mod.make_mesh({"dp": 2, "tp": 2})


def test_rebuild_mesh_drops_touched_group_flat_and_coord():
    """One lost chip — addressed by flat mesh index OR by dp-coordinate —
    drops its whole dp-group; the tp extent is pinned and the survivor
    group keeps its devices."""
    m = _mesh_2x2()
    for lost in ([1], [{"axis": "dp", "index": 0}]):  # both chips of g0
        nm, gmap = mesh_mod.rebuild_mesh(m, lost)
        assert dict(zip(nm.axis_names, nm.devices.shape)) == \
            {"dp": 1, "tp": 2}
        assert gmap == {1: 0}
        assert list(nm.devices[0]) == list(m.devices[1])


def test_rebuild_mesh_multi_loss_and_power_of_two():
    """dp4×tp2: one lost chip → dp2 survivors renumbered contiguously;
    two chips in distinct groups → 2 survivors (power of two, kept);
    with 3 survivors the composite mesh truncates to 2."""
    m = mesh_mod.make_mesh({"dp": 4, "tp": 2})
    nm, gmap = mesh_mod.rebuild_mesh(m, [{"axis": "dp", "index": 2}])
    assert nm.devices.shape[0] == 2  # 3 survivors -> pow2 truncation
    assert gmap == {0: 0, 1: 1}
    nm, gmap = mesh_mod.rebuild_mesh(m, [0, 7])  # groups 0 and 3
    assert nm.devices.shape[0] == 2
    assert gmap == {1: 0, 2: 1}
    with pytest.raises(MeshDegraded):
        mesh_mod.rebuild_mesh(m, [{"axis": "dp", "index": 3}],
                              power_of_two=False)


def test_rebuild_mesh_single_axis_any_size_exception():
    """The pure-dp any-survivor-count exception survives the rebuild
    path: dp8 minus one group may resume at dp7 with
    power_of_two=False, exactly like shrink_mesh."""
    m = mesh_mod.make_mesh({"dp": 8})
    nm, gmap = mesh_mod.rebuild_mesh(m, [3], power_of_two=False)
    assert nm.devices.shape[0] == 7
    assert gmap[4] == 3  # renumbered past the hole
    nm, _ = mesh_mod.rebuild_mesh(m, [3])  # default truncates to pow2
    assert nm.devices.shape[0] == 4


def test_rebuild_mesh_no_survivors_raises_populated():
    m = _mesh_2x2()
    with pytest.raises(MeshDegraded) as ei:
        mesh_mod.rebuild_mesh(m, [0, 2])  # one chip in each group
    assert ei.value.lost_replicas == [0, 1]
    assert ei.value.mesh_size == 4


def test_rebuild_mesh_ep_sp_pinned_unsupported():
    """MeshDegraded-on-purpose pins: MoE ('ep') and ring-attention
    ('sp') compositions cannot survive a dp-group drop — the loss
    raises loudly with mesh_size/lost_replicas populated instead of
    silently misplacing expert / sequence shards."""
    for extra in ("ep", "sp"):
        m = mesh_mod.make_mesh({"dp": 2, extra: 2})
        with pytest.raises(MeshDegraded) as ei:
            mesh_mod.rebuild_mesh(m, [{"axis": "dp", "index": 0}])
        assert extra in str(ei.value)
        assert ei.value.mesh_size == 4
        assert ei.value.lost_replicas == [0]


def test_shrink_mesh_error_paths_populate_degraded_fields():
    """Bugfix pin: shrink_mesh's MeshDegraded paths (model-parallel
    axis, composite non-power-of-two) carry mesh_size and
    lost_replicas, like every other mesh-loss raise."""
    m = _mesh_2x2()
    with pytest.raises(MeshDegraded) as ei:
        mesh_mod.shrink_mesh(m, 0, axis="tp")
    assert ei.value.mesh_size == 4
    assert ei.value.lost_replicas == [0]
    m3 = mesh_mod.make_mesh({"dp": 4, "tp": 2})
    with pytest.raises(MeshDegraded) as ei:
        mesh_mod.shrink_mesh(m3, 1, power_of_two=False)
    assert ei.value.mesh_size == 8
    assert ei.value.lost_replicas == [1]


def test_chip_loss_device_coordinate_forms():
    """Satellite: chip_loss rules address the victim by mesh coordinate
    or flat device index; the error carries .device for the handler's
    coordinate-aware classification."""
    for dev in ({"axis": "dp", "index": 1}, 3):
        faults.install_plan({"seed": 0, "rules": [
            {"site": "kvstore:allreduce", "kind": "chip_loss",
             "device": dev, "at": [0]}]})
        with pytest.raises(ChipLostError) as ei:
            faults.fault_point("kvstore:allreduce")
        assert ei.value.device == dev
        faults.clear_plan()


def test_chip_loss_replica_plans_unchanged():
    """Replica-int plans are byte-for-byte the old behaviour: .replica
    set, .device unset."""
    faults.install_plan({"seed": 0, "rules": [
        {"site": "kvstore:allreduce", "kind": "chip_loss",
         "replica": 5, "at": [0]}]})
    with pytest.raises(ChipLostError) as ei:
        faults.fault_point("kvstore:allreduce")
    assert ei.value.replica == 5
    assert getattr(ei.value, "device", None) is None


def test_chip_loss_device_validation():
    for dev in ({"axis": "dp"}, {"index": 0}, "g0", 1.5):
        with pytest.raises(MXNetError):
            faults.install_plan({"seed": 0, "rules": [
                {"site": "kvstore:allreduce", "kind": "chip_loss",
                 "device": dev}]})


def _tiny_3d_trainer(dp=2, tp=2, seed=0, mesh=None):
    from tools.elastic_soak import _make_3d_trainer

    return _make_3d_trainer(seed, dp=dp, tp=tp, mesh=mesh)


@pytest.mark.integration
def test_sharded_checkpoint_layouts_cross_mesh_roundtrip(tmp_path):
    """A dp2×tp2 trainer's sharded checkpoint carries the saving layout
    (tp-split weight) and restores exactly onto a dp1×tp2 mesh; the
    reshard counter splits by axis."""
    net, tr = _tiny_3d_trainer(dp=2, tp=2, seed=11)
    x = onp.random.RandomState(0).randn(8, 4).astype("float32")
    y = onp.random.RandomState(1).randn(8, 2).astype("float32")
    tr.step(mx.nd.array(x), mx.nd.array(y))
    assert tr.checkpoint_layouts()  # the tp-split weight is recorded
    eh = ElasticTrainingHandler(str(tmp_path))
    eh.save_sharded_trainer(tr, 0)
    want = tr.export_state()["params"]

    net2, tr2 = _tiny_3d_trainer(dp=1, tp=2, seed=99)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        params, meta = ckpt.load_checkpoint(
            eh.manager._path(0), trainer=tr2,
            mesh_axes={"dp": 1, "tp": 2})
    tr2.import_params(params)
    got = tr2.export_state()["params"]
    assert set(got) == set(want)
    for k in want:
        assert onp.array_equal(got[k], want[k]), k
    assert counters.get("resilience.reshard_resumes[dp]") == 1


@pytest.mark.integration
def test_sharded_layout_missing_slice_fails_loudly(tmp_path):
    """An unreconstructable tp-extent change (a layout slice missing
    from every shard) raises CheckpointCorruptError, never a silently
    misassembled tensor."""
    import json as _json

    net, tr = _tiny_3d_trainer(dp=2, tp=2, seed=11)
    eh = ElasticTrainingHandler(str(tmp_path))
    eh.save_sharded_trainer(tr, 0)
    # rewrite the manifest to declare a tp4 layout the tp2 shard set
    # cannot express (slices ::02/::03 do not exist anywhere)
    mpath = eh.manager._path(0)
    sections, meta = ckpt._unpack(open(mpath, "rb").read(), path=mpath)
    manifest = _json.loads(sections["manifest"])
    assert manifest["layouts"]  # the tp-split weight is recorded
    for lay in manifest["layouts"].values():
        lay["parts"] *= 2
    secs = [("manifest", _json.dumps(manifest).encode())]
    if "trainer" in sections:
        secs.append(("trainer", sections["trainer"]))
    ckpt._atomic_write(mpath, ckpt._pack(secs, meta))
    net2, tr2 = _tiny_3d_trainer(dp=1, tp=2, seed=99)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        with pytest.raises(ckpt.CheckpointCorruptError,
                           match="cannot be reconstructed"):
            ckpt.load_checkpoint(mpath, trainer=tr2,
                                 mesh_axes={"dp": 1, "tp": 2})


def test_reassemble_layouts_missing_slice_unit():
    from mxnet_tpu.ndarray.ndarray import NDArray

    params = {"weight::00": NDArray(onp.zeros((2, 2), "float32"))}
    manifest = {"layouts": {"weight": {"axis": "tp", "dim": 1,
                                       "parts": 2}}}
    with pytest.raises(ckpt.CheckpointCorruptError,
                       match="weight::01"):
        ckpt._reassemble_layouts("<p>", params, manifest)


@pytest.mark.integration
def test_kill_one_chip_dp2_tp2_recovers_without_degrade():
    """THE composed-mesh acceptance pin: a dp2×tp2 run killed by a
    coordinate-addressed chip_loss recovers WITHOUT MeshDegraded —
    rebuilds to dp1×tp2 (tp pinned), reshards from its own sharded
    checkpoint, and lands bitwise on a clean dp1×tp2 run from the same
    checkpoint. One step lost, dp_history records (2, 1)."""
    from tools.elastic_soak import run_kill_reshard_3d

    violations, row = run_kill_reshard_3d(seed=7, n_batches=10)
    assert violations == []
    assert row["resume_parity"] == "bitwise"
    assert row["steps_lost"] == 1
    assert row["dp_from"] == 2 and row["dp_to"] == 1 and row["tp"] == 2
    assert counters.get("resilience.elastic_restarts") == 1


@pytest.mark.integration
def test_rebuild_disabled_reraises_mesh_loss(tmp_path):
    """MXNET_ELASTIC_REBUILD=0 pins the pre-rebuild degrade path on
    composed meshes: recover_sharded declines and the loss re-raises."""
    os.environ["MXNET_ELASTIC_REBUILD"] = "0"
    net, tr = _tiny_3d_trainer(dp=2, tp=2, seed=5)
    eh = ElasticTrainingHandler(str(tmp_path))
    eh.save_sharded_trainer(tr, 0)
    exc = ChipLostError("chip down", device={"axis": "dp", "index": 0})
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        assert eh.recover_sharded(tr, exc, lambda m: None) is None
    assert eh.stats["restarts"] == 0


@pytest.mark.integration
def test_min_dp_groups_floor_declines_rebuild(tmp_path):
    """A loss that would leave fewer dp-groups than
    MXNET_ELASTIC_MIN_DP_GROUPS declines the rebuild (the caller's
    mesh loss re-raises)."""
    os.environ["MXNET_ELASTIC_MIN_DP_GROUPS"] = "2"
    net, tr = _tiny_3d_trainer(dp=2, tp=2, seed=5)
    eh = ElasticTrainingHandler(str(tmp_path))
    eh.save_sharded_trainer(tr, 0)
    exc = ChipLostError("chip down", device={"axis": "dp", "index": 1})
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        assert eh.recover_sharded(tr, exc, lambda m: None) is None
    assert eh.stats["restarts"] == 0
    assert eh.stats["dp_history"] == []


def test_parallel_config_validates_and_shapes():
    from mxnet_tpu.parallel import ParallelConfig

    assert ParallelConfig(dp=2, tp=2).mesh_shape() == {"dp": 2, "tp": 2}
    assert ParallelConfig(dp=4).mesh_shape() == {"dp": 4}
    assert ParallelConfig(dp=1, tp=1, pp=2).mesh_shape() == \
        {"dp": 1, "pp": 2}
    with pytest.raises(MXNetError):
        ParallelConfig(dp=0)
    with pytest.raises(MXNetError):
        ParallelConfig(dp=1, tp=-1)


def test_run_tier1_carries_elastic3d_leg():
    """Satellite: the tier-1 gate grows the opt-in TIER1_ELASTIC3D
    composed-mesh leg (with its MXNET_LOCKDEP re-run)."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "run_tier1.sh")
    src = open(path).read()
    assert "TIER1_ELASTIC3D" in src
    assert "--legs 3d" in src
    assert src.count("--legs 3d") >= 2  # plain + MXNET_LOCKDEP re-run


def test_composed_elastic_knobs_registered_defaults():
    from mxnet_tpu import config

    assert config.get("MXNET_ELASTIC_REBUILD") is True
    assert config.get("MXNET_ELASTIC_MIN_DP_GROUPS") == 1
