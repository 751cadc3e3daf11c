"""Fault-injection drills for the robustness layer (repro.health).

Every fault class the harness can inject — NaN/spiked gradients, ESS
collapse, index corruption and overflow, corrupt/torn checkpoints,
mid-run kills — is driven end to end here: inject -> detect (verdict /
probe / checksum) -> recover (skip, rollback, ladder rung, checkpoint
fallback, resume) -> the trajectory re-converges. The flip side is the
no-op guarantee: with no fault fired, the guarded trainer walks a
BITWISE-identical trajectory to the unguarded one.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.fopo import FOPOConfig, fopo_loss
from repro.core.policy import SoftmaxPolicy, linear_tower_apply, linear_tower_init
from repro.core.rewards import make_session_reward
from repro.data import SyntheticConfig, generate_sessions
from repro.health import (
    ESS_COLLAPSE,
    FaultPlan,
    GRAD_SPIKE,
    HealthConfig,
    IndexHealthConfig,
    IndexHealthMonitor,
    KILL_EXIT_CODE,
    LADDER,
    NONFINITE_GRADS,
    NONFINITE_LOSS,
    SimulatedPreemption,
    WBAR_COLLAPSE,
    corrupt_checkpoint,
    corrupt_index_state,
    decode_verdict,
    health_verdict,
    init_guard_state,
    torn_checkpoint_writes,
    transient_save_failures,
    update_guard_state,
)
from repro.mips.refresh import RefreshConfig, sampled_recall
from repro.train import (
    CheckpointCorruptError,
    FOPOTrainer,
    TrainerConfig,
    restore_checkpoint,
    save_checkpoint,
)

MULTI = jax.device_count() >= 4
multi_device = pytest.mark.skipif(
    not MULTI,
    reason="needs >= 4 devices (XLA_FLAGS=--xla_force_host_platform_device_count=4)",
)


# ---------------------------------------------------------------------------
# shared fixtures
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def ds():
    full = generate_sessions(SyntheticConfig(
        num_items=300, num_users=200, embed_dim=16, session_len=8, seed=0
    ))
    train, _ = full.split(0.85, seed=0)
    return train


def make_trainer(ds, health=None, fault=None, *, steps=6, seed=0,
                 ckpt_dir=None, ckpt_every=0, retriever="exact",
                 grad_clip=0.0, fused=False, **fopo_kw):
    fopo = FOPOConfig(
        num_items=300, num_samples=32, top_k=16, epsilon=0.8,
        retriever=retriever, fused=fused, **fopo_kw,
    )
    tc = TrainerConfig(
        estimator="fopo", fopo=fopo, batch_size=8, learning_rate=3e-3,
        num_steps=steps, grad_clip=grad_clip, checkpoint_dir=ckpt_dir,
        checkpoint_every=ckpt_every, seed=seed, health=health,
    )
    return FOPOTrainer(tc, ds, fault_plan=fault)


def make_refresh_trainer(ds, health=None, fault=None, *, steps=6,
                         ckpt_dir=None, ckpt_every=0, every=2,
                         compact_every=0, cap=None):
    from repro.mips.ivf import build_ivf

    items = jnp.asarray(ds.item_embeddings)
    # cap=None sizes the lists to the largest cluster, so the index holds
    # every item: this catalog is lopsided (one k-means cluster takes
    # most of the 300 items), and a fixed cap below that cluster's size
    # drops its overflow from the lists (counted as index overflow)
    index = build_ivf(
        jax.random.PRNGKey(1), items, num_clusters=8, cap=cap,
        kmeans_iters=3, cap_tile=32,
    )
    fopo = FOPOConfig(
        num_items=300, num_samples=32, top_k=16, epsilon=0.8,
        retriever="ivf_pallas",
        index_refresh=RefreshConfig(every=every, minibatch=64,
                                    compact_every=compact_every,
                                    delta_cap=16),
    )
    tc = TrainerConfig(
        estimator="fopo", fopo=fopo, batch_size=8, learning_rate=3e-3,
        num_steps=steps, checkpoint_dir=ckpt_dir,
        checkpoint_every=ckpt_every, seed=0, health=health,
    )
    return FOPOTrainer(
        tc, ds, retriever_kwargs={"index": index, "n_probe": 4,
                                  "cap_tile": 32},
        fault_plan=fault,
    )


def assert_tree_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# config validation + verdict unit tests
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kw", [
    {"ess_floor": -1.0},
    {"max_wbar_ceiling": 0.0},
    {"max_wbar_ceiling": 1.5},
    {"grad_spike_factor": 0.5},
    {"ema_decay": 1.0},
    {"max_consecutive_bad": 0},
    {"snapshot_every": 0},
    {"save_retries": -1},
])
def test_health_config_validation(kw):
    with pytest.raises(ValueError):
        HealthConfig(**kw)


@pytest.mark.parametrize("kw", [
    {"probe_every": -1},
    {"probe_rows": 0},
    {"probe_k": 0},
    {"recall_floor": 1.5},
    {"recall_floor": -0.1},
    {"overflow_budget": -1},
    {"cooldown": -1},
    {"rebuild_iters": 0},
])
def test_index_health_config_validation(kw):
    with pytest.raises(ValueError):
        IndexHealthConfig(**kw)


def test_decode_verdict():
    assert decode_verdict(0) == []
    assert decode_verdict(NONFINITE_LOSS) == ["nonfinite_loss"]
    assert set(decode_verdict(NONFINITE_GRADS | ESS_COLLAPSE)) == {
        "nonfinite_grads", "ess_collapse",
    }
    assert len(decode_verdict(0b11111)) == 5


def _verdict(cfg, loss, gnorm, aux=None, state=None):
    state = state if state is not None else init_guard_state()
    return int(health_verdict(
        cfg, jnp.float32(loss), jnp.float32(gnorm), aux or {}, state
    ))


def test_verdict_nonfinite_checks_always_on():
    cfg = HealthConfig()
    assert _verdict(cfg, 1.0, 1.0) == 0
    assert _verdict(cfg, np.nan, 1.0) == NONFINITE_LOSS
    assert _verdict(cfg, np.inf, 1.0) == NONFINITE_LOSS
    assert _verdict(cfg, 1.0, np.nan) == NONFINITE_GRADS
    assert _verdict(cfg, np.nan, np.inf) == NONFINITE_LOSS | NONFINITE_GRADS


def test_verdict_grad_spike_arms_after_warmup():
    cfg = HealthConfig(grad_spike_factor=10.0, warmup_steps=3)
    cold = init_guard_state()._replace(grad_ema=jnp.float32(1.0))
    warm = cold._replace(good_steps=jnp.int32(3))
    # 100x the EMA: quiet during warmup, fires once armed
    assert _verdict(cfg, 1.0, 100.0, state=cold) == 0
    assert _verdict(cfg, 1.0, 100.0, state=warm) == GRAD_SPIKE
    assert _verdict(cfg, 1.0, 5.0, state=warm) == 0


def test_verdict_snis_checks_key_on_aux():
    cfg = HealthConfig(ess_floor=2.0, max_wbar_ceiling=0.9)
    ok = {"ess": jnp.float32(10.0), "max_wbar": jnp.float32(0.2)}
    assert _verdict(cfg, 1.0, 1.0, aux=ok) == 0
    low = dict(ok, ess=jnp.float32(1.0))
    assert _verdict(cfg, 1.0, 1.0, aux=low) == ESS_COLLAPSE
    hi = dict(ok, max_wbar=jnp.float32(0.99))
    assert _verdict(cfg, 1.0, 1.0, aux=hi) == WBAR_COLLAPSE
    # estimators that don't report the diagnostics simply don't trace them
    assert _verdict(cfg, 1.0, 1.0, aux={}) == 0


def test_update_guard_state_counters_and_ema():
    cfg = HealthConfig(ema_decay=0.5)
    s0 = init_guard_state()
    good = update_guard_state(cfg, s0, jnp.int32(0), jnp.float32(4.0))
    assert float(good.grad_ema) == 4.0  # first good step seeds the EMA
    assert int(good.good_steps) == 1 and int(good.bad_total) == 0
    good2 = update_guard_state(cfg, good, jnp.int32(0), jnp.float32(8.0))
    assert float(good2.grad_ema) == pytest.approx(6.0)  # 0.5*4 + 0.5*8
    bad = update_guard_state(
        cfg, good2, jnp.int32(NONFINITE_GRADS), jnp.float32(np.nan)
    )
    # a bad step freezes the EMA and bumps the counters
    assert float(bad.grad_ema) == pytest.approx(6.0)
    assert int(bad.consecutive_bad) == 1 and int(bad.bad_total) == 1
    assert int(bad.last_verdict) == NONFINITE_GRADS
    again = update_guard_state(cfg, bad, jnp.int32(0), jnp.float32(6.0))
    assert int(again.consecutive_bad) == 0 and int(again.bad_total) == 1


# ---------------------------------------------------------------------------
# the no-op guarantee: guarded == unguarded, bitwise
# ---------------------------------------------------------------------------

def test_guarded_trainer_bitwise_noop(ds):
    """THE acceptance bar: with every check armed and nothing firing,
    the guarded trainer's params AND optimizer state are bitwise
    identical to the unguarded trainer's after 6 steps."""
    h = HealthConfig(ess_floor=1.5, grad_spike_factor=100.0,
                     max_wbar_ceiling=0.999)
    a = make_trainer(ds)
    b = make_trainer(ds, health=h)
    ha = a.train()
    hb = b.train()
    assert ha["loss"] == hb["loss"]
    assert hb["health"] == []
    assert_tree_equal(a.params, b.params)
    assert_tree_equal(a.opt_state, b.opt_state)


def test_guarded_trainer_bitwise_noop_with_clip_and_fused(ds):
    """Same guarantee on the fused kernel path with grad clipping (the
    clip shares the norm reduction pattern the guard adds — the classic
    re-fusion trap)."""
    h = HealthConfig(ess_floor=1.5, grad_spike_factor=100.0)
    a = make_trainer(ds, steps=3, grad_clip=5.0, fused=True)
    b = make_trainer(ds, health=h, steps=3, grad_clip=5.0, fused=True)
    a.train()
    b.train()
    assert_tree_equal(a.params, b.params)
    assert_tree_equal(a.opt_state, b.opt_state)


def test_armed_clear_fault_plan_is_bitwise_noop(ds):
    """A FaultPlan whose faults never fire changes the compiled program
    (the injection ops trace) but NOT the trajectory: clear signals are
    multiplicative identity on every grad leaf."""
    h = HealthConfig()
    a = make_trainer(ds, health=h, steps=4)
    b = make_trainer(ds, health=h, steps=4,
                     fault=FaultPlan(nan_grads_at=(99,)))
    a.train(4)
    b.train(4)
    assert_tree_equal(a.params, b.params)
    assert_tree_equal(a.opt_state, b.opt_state)


# ---------------------------------------------------------------------------
# inject -> detect -> skip
# ---------------------------------------------------------------------------

def test_nan_grads_detected_and_step_skipped(ds):
    t = make_trainer(ds, health=HealthConfig(), steps=6,
                     fault=FaultPlan(nan_grads_at=(2,)))
    t.train(2)
    frozen = jax.tree.map(np.asarray, t.params)
    h = t.train(1)  # the faulted step
    assert len(h["health"]) == 1
    assert h["health"][0]["verdict"] & NONFINITE_GRADS
    assert "nonfinite_grads" in h["health"][0]["checks"]
    # the skip is a pass-through: params bitwise unchanged
    assert_tree_equal(frozen, t.params)
    t.train(3)
    assert int(t.guard_state.bad_total) == 1
    assert int(t.guard_state.consecutive_bad) == 0
    assert np.isfinite(np.asarray(t.params["w"])).all()


def test_grad_spike_detected(ds):
    # factor 50: far above this data's genuine batch-to-batch norm
    # spread (~13x the EMA at the widest), far below the injected 1e4
    h = HealthConfig(grad_spike_factor=50.0, warmup_steps=2,
                     max_consecutive_bad=10)
    t = make_trainer(ds, health=h, steps=6,
                     fault=FaultPlan(spike_grads_at=(4,), spike_factor=1e4))
    hist = t.train()
    fired = [e for e in hist["health"] if e["verdict"] & GRAD_SPIKE]
    assert len(fired) == 1
    assert np.isfinite(np.asarray(t.params["w"])).all()


def test_ess_collapse_detected(ds):
    h = HealthConfig(ess_floor=1.5, max_consecutive_bad=10)
    t = make_trainer(ds, health=h, steps=5,
                     fault=FaultPlan(ess_collapse_at=(3,), ess_value=1.0))
    hist = t.train()
    fired = [e for e in hist["health"] if e["verdict"] & ESS_COLLAPSE]
    assert len(fired) == 1
    assert int(t.guard_state.bad_total) == 1


def test_history_and_diagnostics_wiring(ds):
    """Satellite: the snis_diagnostics aux contract lands in history —
    one finite float per step for each of ess/rbar/max_wbar."""
    t = make_trainer(ds, health=HealthConfig(), steps=4)
    hist = t.train()
    for k in ("ess", "rbar", "max_wbar"):
        assert len(hist[k]) == 4
        assert np.isfinite(hist[k]).all()
    assert len(hist["loss"]) == 4 and len(hist["step_time"]) == 4


# ---------------------------------------------------------------------------
# rollback escalation
# ---------------------------------------------------------------------------

def test_rollback_after_consecutive_bad_steps(ds):
    """3 NaN steps in a row with max_consecutive_bad=2: two skips, then
    a rollback to the last good snapshot with a re-split key. Fire-once
    faults stay quiet on the replay, so the run re-converges."""
    h = HealthConfig(max_consecutive_bad=2, snapshot_every=1)
    t = make_trainer(ds, health=h, steps=10,
                     fault=FaultPlan(nan_grads_at=(3, 4, 5)))
    hist = t.train()
    rollbacks = [e for e in hist["events"] if e["event"] == "rollback"]
    assert len(rollbacks) == 1
    assert t._restarts == 1
    assert int(t.guard_state.consecutive_bad) == 0
    assert np.isfinite(np.asarray(t.params["w"])).all()
    # post-rollback the replayed steps ran clean (fresh key stream)
    assert np.isfinite(hist["loss"][-1])


def test_rollback_resets_guard_and_resplits_key(ds):
    h = HealthConfig(max_consecutive_bad=1, snapshot_every=1)
    t = make_trainer(ds, health=h, steps=6,
                     fault=FaultPlan(nan_grads_at=(2,)))
    key_before = np.asarray(t._train_key).copy()
    hist = t.train()
    assert [e["event"] for e in hist["events"]] == ["rollback"]
    assert not np.array_equal(np.asarray(t._train_key), key_before)
    assert int(t.guard_state.bad_total) == 0  # reset with the rollback


# ---------------------------------------------------------------------------
# checkpoint integrity: checksums, fallback, retries, torn writes
# ---------------------------------------------------------------------------

def _state(seed=0):
    k = jax.random.PRNGKey(seed)
    return {"w": jax.random.normal(k, (8, 8)), "step_count": jnp.int32(3)}


def test_checkpoint_checksum_roundtrip(tmp_path):
    d = str(tmp_path)
    s = _state()
    save_checkpoint(d, 5, s)
    manifest = json.load(open(os.path.join(d, "step_0000000005", "manifest.json")))
    assert len(manifest["checksums"]) == 2
    step, out, _ = restore_checkpoint(d, s)
    assert step == 5
    assert_tree_equal(s, out)


def test_checkpoint_without_checksums_still_loads(tmp_path):
    """Pre-integrity checkpoints (no checksum field) stay restorable."""
    d = str(tmp_path)
    s = _state()
    save_checkpoint(d, 1, s)
    mpath = os.path.join(d, "step_0000000001", "manifest.json")
    manifest = json.load(open(mpath))
    del manifest["checksums"]
    json.dump(manifest, open(mpath, "w"))
    step, out, _ = restore_checkpoint(d, s)
    assert step == 1
    assert_tree_equal(s, out)


@pytest.mark.parametrize("mode", ["truncate", "bitflip"])
def test_corrupt_checkpoint_detected(tmp_path, mode):
    d = str(tmp_path)
    s = _state()
    save_checkpoint(d, 7, s)
    corrupt_checkpoint(d, 7, mode=mode)
    with pytest.raises(CheckpointCorruptError):
        restore_checkpoint(d, s)


@pytest.mark.parametrize("mode", ["truncate", "bitflip"])
def test_corrupt_latest_falls_back_to_previous(tmp_path, mode):
    d = str(tmp_path)
    s = _state()
    save_checkpoint(d, 2, s)
    save_checkpoint(d, 4, _state(seed=1))
    corrupt_checkpoint(d, 4, mode=mode)
    step, out, _ = restore_checkpoint(d, s, fallback=True)
    assert step == 2
    assert_tree_equal(s, out)
    # all candidates corrupt -> aggregate error, not silence
    corrupt_checkpoint(d, 2, mode=mode)
    with pytest.raises(CheckpointCorruptError, match="all candidate"):
        restore_checkpoint(d, s, fallback=True)


def test_trainer_resumes_past_corrupt_checkpoint(ds, tmp_path):
    d = str(tmp_path / "ckpt")
    t = make_trainer(ds, health=HealthConfig(), steps=4,
                     ckpt_dir=d, ckpt_every=2)
    t.train()
    corrupt_checkpoint(d, 4, mode="bitflip")
    t2 = make_trainer(ds, health=HealthConfig(), steps=4,
                      ckpt_dir=d, ckpt_every=2)
    assert t2.maybe_restore()
    assert t2.step == 2  # fell back past the corrupt step-4 checkpoint


def test_transient_save_failures_retried(tmp_path):
    d = str(tmp_path)
    s = _state()
    with transient_save_failures(2):
        save_checkpoint(d, 3, s, retries=2, backoff=0.001)
    step, out, _ = restore_checkpoint(d, s)
    assert step == 3
    # without retries the same fault surfaces
    with transient_save_failures(1):
        with pytest.raises(OSError, match="injected"):
            save_checkpoint(d, 9, s, retries=0)
    assert not os.path.exists(os.path.join(d, "step_0000000009"))


def test_torn_write_leaves_no_partial_checkpoint(tmp_path):
    d = str(tmp_path)
    with torn_checkpoint_writes():
        with pytest.raises(OSError):
            save_checkpoint(d, 1, _state(), retries=1, backoff=0.001)
    assert [p for p in os.listdir(d) if p.startswith("step_")] == []


def test_trainer_save_retries_via_health_config(ds, tmp_path):
    d = str(tmp_path / "ckpt")
    h = HealthConfig(save_retries=2, save_backoff=0.001)
    t = make_trainer(ds, health=h, steps=2, ckpt_dir=d, ckpt_every=2)
    with transient_save_failures(2):
        t.train()
    t2 = make_trainer(ds, health=h, steps=2, ckpt_dir=d, ckpt_every=2)
    assert t2.maybe_restore() and t2.step == 2


# ---------------------------------------------------------------------------
# kill-and-resume: trajectory parity (the resume-gap satellite)
# ---------------------------------------------------------------------------

def test_preemption_resume_trajectory_parity(ds, tmp_path):
    """Kill at step 4, resume from the step-4 checkpoint, finish — the
    final params/opt state are BITWISE what an uninterrupted run
    produces (train_key + loader state round-trip the checkpoint)."""
    d = str(tmp_path / "ckpt")
    a = make_trainer(ds, steps=6)
    a.train()

    b = make_trainer(ds, steps=6, ckpt_dir=d, ckpt_every=2,
                     fault=FaultPlan(kill_at=4))
    with pytest.raises(SimulatedPreemption):
        b.train()

    c = make_trainer(ds, steps=6, ckpt_dir=d, ckpt_every=2)
    assert c.maybe_restore()
    assert c.step == 4
    c.train(2)
    assert_tree_equal(a.params, c.params)
    assert_tree_equal(a.opt_state, c.opt_state)


def test_preemption_resume_parity_with_index_refresh(ds, tmp_path):
    """Same drill on the maintained-index path: RefreshState (incl. the
    overflow counter) and the refresh RNG key ride the checkpoint, so
    the resumed index trajectory matches the uninterrupted one too."""
    d = str(tmp_path / "ckpt")
    a = make_refresh_trainer(ds, steps=6)
    a.train()

    b = make_refresh_trainer(ds, steps=6, ckpt_dir=d, ckpt_every=2,
                             fault=FaultPlan(kill_at=4))
    with pytest.raises(SimulatedPreemption):
        b.train()

    c = make_refresh_trainer(ds, steps=6, ckpt_dir=d, ckpt_every=2)
    assert c.maybe_restore()
    assert c.step == 4
    c.train(2)
    assert_tree_equal(a.params, c.params)
    assert_tree_equal(a.index_state, c.index_state)


KILL_RESUME_SCRIPT = r"""
import sys
import jax, jax.numpy as jnp
import numpy as np

from repro.core.fopo import FOPOConfig
from repro.data import SyntheticConfig, generate_sessions
from repro.health import FaultPlan, KILL_EXIT_CODE
from repro.train import FOPOTrainer, TrainerConfig

mode, ckpt_dir = sys.argv[1], sys.argv[2]
full = generate_sessions(SyntheticConfig(
    num_items=300, num_users=200, embed_dim=16, session_len=8, seed=0
))
ds, _ = full.split(0.85, seed=0)
fopo = FOPOConfig(num_items=300, num_samples=32, top_k=16, epsilon=0.8,
                  retriever="exact")
tc = TrainerConfig(estimator="fopo", fopo=fopo, batch_size=8,
                   learning_rate=3e-3, num_steps=6,
                   checkpoint_dir=ckpt_dir, checkpoint_every=2, seed=0)
fault = FaultPlan(kill_at=4, hard_kill=True) if mode == "kill" else None
t = FOPOTrainer(tc, ds, fault_plan=fault)
if mode == "resume":
    assert t.maybe_restore(), "no checkpoint to resume from"
    assert t.step == 4, t.step
    t.train(6 - t.step)
else:
    t.train()  # dies at step 4 via os._exit(KILL_EXIT_CODE)
print("FINAL", np.asarray(t.params["w"]).tobytes().hex())
"""


def test_hard_kill_and_resume_subprocess(ds, tmp_path):
    """The real preemption shape: os._exit mid-run (no atexit, no
    finally), then a fresh process resumes from disk and lands on the
    uninterrupted trajectory bitwise."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = tmp_path / "kill_resume.py"
    script.write_text(KILL_RESUME_SCRIPT)
    d = str(tmp_path / "ckpt")
    env = {**os.environ, "PYTHONPATH": os.path.join(root, "src"),
           "JAX_PLATFORMS": "cpu"}

    killed = subprocess.run(
        [sys.executable, str(script), "kill", d],
        capture_output=True, text=True, env=env, cwd=root, timeout=600,
    )
    assert killed.returncode == KILL_EXIT_CODE, killed.stderr[-3000:]
    assert "FINAL" not in killed.stdout  # really died mid-run

    resumed = subprocess.run(
        [sys.executable, str(script), "resume", d],
        capture_output=True, text=True, env=env, cwd=root, timeout=600,
    )
    assert resumed.returncode == 0, resumed.stderr[-3000:]
    final = [ln for ln in resumed.stdout.splitlines() if ln.startswith("FINAL")]
    assert final, resumed.stdout

    a = make_trainer(ds, steps=6)
    a.train()
    assert final[0].split()[1] == np.asarray(a.params["w"]).tobytes().hex()


# ---------------------------------------------------------------------------
# the retrieval degradation ladder
# ---------------------------------------------------------------------------

def test_monitor_escalates_one_rung_per_unhealthy_probe():
    m = IndexHealthMonitor(IndexHealthConfig(
        probe_every=1, recall_floor=0.9, cooldown=0,
    ))
    assert m.observe(0.5, 0) == "compact"
    assert m.observe(0.5, 0) == "rebuild"
    assert m.observe(0.5, 0) == "fallback"
    assert m.exhausted
    assert m.observe(0.5, 0) is None  # nothing left to take


def test_monitor_healthy_probe_resets_ladder():
    m = IndexHealthMonitor(IndexHealthConfig(
        probe_every=1, recall_floor=0.9, cooldown=0,
    ))
    assert m.observe(0.5, 0) == "compact"
    assert m.observe(0.95, 0) is None  # the rung healed it
    assert m.level == 0
    assert m.observe(0.5, 0) == "compact"  # ladder restarts from rung 0


def test_monitor_cooldown_swallows_observations():
    m = IndexHealthMonitor(IndexHealthConfig(
        probe_every=1, recall_floor=0.9, cooldown=2,
    ))
    assert m.observe(0.5, 0) == "compact"
    assert m.observe(0.5, 0) is None  # cooling down
    assert m.observe(0.5, 0) is None
    assert m.observe(0.5, 0) == "rebuild"


def test_monitor_overflow_delta_trigger():
    m = IndexHealthMonitor(IndexHealthConfig(overflow_budget=10))
    assert m.observe(None, 5) is None  # delta 5 <= budget
    assert m.observe(None, 40) == "compact"  # delta 35 > budget
    m.note_compaction(0)
    assert m.last_overflow == 0
    assert m.observe(None, 5) is None  # re-based after compaction


def test_fixed_cap_build_drop_is_overflow_the_ladder_sees(ds):
    """A fixed-cap build that cannot hold the largest cluster drops its
    overflow from the lists; the maintained state counts the missing
    items as overflow from the start, so the ladder's overflow watch
    escalates on its first observation. A build that holds every item
    starts with no overflow."""
    assert int(make_refresh_trainer(ds).index_state.overflow) == 0
    ih = IndexHealthConfig(overflow_budget=8, cooldown=0)
    t = make_refresh_trainer(ds, health=HealthConfig(index=ih), steps=1,
                             every=0, cap=128)
    lists = np.asarray(t.index_state.lists)
    held = int(np.sum(lists >= 0))
    assert held < 300
    assert int(t.index_state.overflow) == 300 - held
    probes = t.train(1)["index_health"]
    assert probes[0]["overflow"] == 300 - held
    assert probes[0]["action"] == "compact"


def test_corrupt_index_recall_collapses_and_compact_heals(ds):
    """corrupt_index_state scrambles the stored list embeddings: the
    sampled recall probe sees the collapse, the ladder's first rung
    (forced compact) rebuilds the lists from the live catalog, and the
    next probe reads healthy again."""
    # probe ALL 8 clusters: healthy recall is ~exact (only delta-buffer
    # placement can miss), so the floor cleanly separates corruption
    ih = IndexHealthConfig(probe_every=1, probe_rows=32, probe_k=16,
                           recall_floor=0.7, cooldown=0, n_probe=8)
    t = make_refresh_trainer(ds, health=HealthConfig(index=ih), steps=4,
                             every=0)
    queries = t.policy.user_embedding(
        t.params, jnp.asarray(ds.contexts[:32])
    )
    healthy = sampled_recall(t.index_state, t.beta, queries, 16, n_probe=8)
    assert healthy > 0.9
    t.index_state = corrupt_index_state(
        t.index_state, jax.random.PRNGKey(9)
    )
    broken = sampled_recall(t.index_state, t.beta, queries, 16, n_probe=8)
    assert broken < 0.5
    hist = t.train(2)
    probes = hist["index_health"]
    assert probes[0]["action"] == "compact"
    assert probes[0]["recall"] < 0.7
    assert probes[1]["action"] is None
    assert probes[1]["recall"] > 0.7
    assert t._monitor.level == 0  # healthy probe reset the ladder


def test_full_ladder_walk_to_exact_fallback(ds):
    """recall_floor=1.01 makes every probe unhealthy by construction:
    the trainer walks compact -> rebuild -> fallback deterministically,
    lands on the plan's pre-resolved exact retriever, and keeps
    training (maintenance stops — the index left the serving path)."""
    ih = IndexHealthConfig(probe_every=1, probe_rows=32, probe_k=16,
                           recall_floor=1.01, cooldown=0)
    t = make_refresh_trainer(ds, health=HealthConfig(index=ih), steps=6)
    assert not t.plan.degraded
    hist = t.train()
    actions = [e["action"] for e in hist["index_health"] if e["action"]]
    assert actions == list(LADDER)
    assert t._degraded and t.plan.degraded
    assert t._monitor.exhausted
    assert np.isfinite(hist["loss"]).all()
    # degraded retrieval is the exact retriever: training still steps
    assert len(hist["loss"]) == 6


def test_live_tile_share_gauge_at_build_and_ladder_heals(ds, monkeypatch):
    """`ivf.live_tile_share` is read from the lists where they are built
    or rebuilt on the host: the trainer's construction (emitted by the
    first train()), then each ladder compaction and rebuild — never per
    step, and not for the scheduled centroid refresh."""
    from repro.kernels.ivf_topk import live_tile_share
    from repro.obs.bus import MetricsBus

    seen = []
    gauge = MetricsBus.gauge

    def spy(self, name, value, **kw):
        if name == "ivf.live_tile_share":
            seen.append((kw.get("step"), value))
        gauge(self, name, value, **kw)

    monkeypatch.setattr(MetricsBus, "gauge", spy)
    ih = IndexHealthConfig(probe_every=1, probe_rows=32, probe_k=16,
                           recall_floor=1.01, cooldown=0)
    t = make_refresh_trainer(ds, health=HealthConfig(index=ih), steps=6)
    built = live_tile_share(t.index_state.lists, 32)
    assert 0 < built < 1  # a lopsided catalog: most lists end early
    actions = [e["action"] for e in t.train(3)["index_health"]]
    t.train(3)
    assert actions == list(LADDER)
    # construction, then the compact (step 1) and the rebuild (step 2)
    assert [step for step, _ in seen] == [0, 1, 2]
    assert seen[0][1] == built
    assert all(0 < v <= 1 for _, v in seen)


def test_degrade_requires_fallback_retriever():
    from repro.core.plan import ExecutionPlan

    plan = ExecutionPlan.resolve(
        FOPOConfig(num_items=100, num_samples=8, top_k=4, retriever="exact")
    )
    assert plan.fallback_retriever is None
    with pytest.raises(ValueError, match="fallback"):
        plan.degrade_to_fallback()


def test_plan_clamps_top_k_to_catalog():
    # clamp-and-write-back, same rule as sample_tile: an out-of-range K
    # (e.g. the default 256 on a tiny catalog) must never reach the
    # retriever, and plan.cfg must show what actually runs
    from repro.core.plan import ExecutionPlan

    plan = ExecutionPlan.resolve(
        FOPOConfig(num_items=8, num_samples=4, top_k=16, retriever="exact")
    )
    assert plan.cfg.top_k == 8


# ---------------------------------------------------------------------------
# degenerate-input hardening: finite loss, exact-zero gradient
# ---------------------------------------------------------------------------

def _degenerate_loss_and_grads(fused, dist=None):
    p, l, b, s = 120, 8, 4, 16
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    beta = jax.random.normal(keys[0], (p, l))
    x = jax.random.normal(keys[1], (b, l))
    params = linear_tower_init(keys[2], l, l)
    policy = SoftmaxPolicy(tower=linear_tower_apply, item_dim=l)
    positives = jnp.full((b, 8), -1, jnp.int32)  # every row fully masked
    reward_fn = make_session_reward(positives)
    cfg = FOPOConfig(
        num_items=p, num_samples=s, top_k=16, epsilon=0.8,
        retriever="exact" if dist is None else "streaming",
        fused=fused, dist=dist,
    )
    (loss, aux), grads = jax.value_and_grad(
        lambda pr: fopo_loss(policy, pr, keys[3], x, beta, reward_fn, cfg),
        has_aux=True,
    )(params)
    return loss, aux, grads


@pytest.mark.parametrize("fused", [False, True])
def test_zero_reward_batch_finite_loss_zero_grad(fused):
    """positives all -1 => every reward is 0 => the covariance
    coefficients vanish identically: finite (zero) loss and an EXACTLY
    zero gradient — no NaNs from the degenerate weights."""
    loss, aux, grads = _degenerate_loss_and_grads(fused)
    assert np.isfinite(float(loss))
    assert float(loss) == 0.0
    for g in jax.tree.leaves(grads):
        np.testing.assert_array_equal(np.asarray(g), 0.0)
    assert np.isfinite(float(aux["ess"]))
    assert float(aux["rbar"]) == 0.0


@multi_device
def test_zero_reward_batch_zero_grad_dist():
    from repro.dist.fopo import make_debug_dist

    loss, aux, grads = _degenerate_loss_and_grads(
        fused=False, dist=make_debug_dist(2, 2)
    )
    assert np.isfinite(float(loss)) and float(loss) == 0.0
    for g in jax.tree.leaves(grads):
        np.testing.assert_array_equal(np.asarray(g), 0.0)


def test_guarded_trainer_survives_degenerate_batch(ds):
    """An all-masked batch through the full guarded trainer: the step
    stays finite (zero loss, zero grad) and the guard does NOT flag it
    — degenerate-but-valid input is not a fault."""
    import dataclasses as dc

    dead = dc.replace(ds, positives=np.full_like(ds.positives, -1))
    t = make_trainer(dead, health=HealthConfig(), steps=3)
    hist = t.train()
    assert hist["loss"] == [0.0, 0.0, 0.0]
    assert hist["health"] == []
    assert np.isfinite(np.asarray(t.params["w"])).all()


# ---------------------------------------------------------------------------
# dist: verdict agreement across the mesh
# ---------------------------------------------------------------------------

@multi_device
def test_dist_guarded_parity_and_nan_skip(ds):
    from repro.dist.fopo import make_debug_dist

    dist = make_debug_dist(2, 2)
    h = HealthConfig(max_consecutive_bad=10)
    kw = dict(steps=4, retriever="streaming", dist=dist)
    a = make_trainer(ds, **kw)
    b = make_trainer(ds, health=h, **kw)
    a.train(4)
    b.train(4)
    assert_tree_equal(a.params, b.params)

    c = make_trainer(ds, health=h, fault=FaultPlan(nan_grads_at=(1,)), **kw)
    hist = c.train(4)
    assert any(e["verdict"] & NONFINITE_GRADS for e in hist["health"])
    assert np.isfinite(np.asarray(c.params["w"])).all()


def test_dist_verdict_agree_is_pmax():
    """psum would alias bitmask bits (2 shards x bit 1 = bit 2); the
    agreement reduction must be a max. Unit-checked via the helper's
    math on a 1-device mesh (full mesh semantics covered above)."""
    from repro.dist.fopo import dist_verdict_agree, make_debug_dist

    if jax.device_count() < 4:
        pytest.skip("needs a mesh")
    dist = make_debug_dist(2, 2)
    v = dist_verdict_agree(jnp.int32(NONFINITE_GRADS), dist)
    assert int(v) == NONFINITE_GRADS  # identical shards: unchanged, not summed
