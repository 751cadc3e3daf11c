"""The FOPO training driver — Algorithm 1 end to end, production posture.

Wires together: data loader (checkpointable), policy + fixed beta
(Assumption 1), MIPS retriever, proposal, SNIS covariance gradient,
optimizer, rotated checkpoints and restart-from-latest. The same driver
runs the REINFORCE baseline (`estimator="reinforce"`) and the dense
exact-gradient reference (`estimator="exact"`), which is how the RQ
benchmarks compare methods under one roof.

With `TrainerConfig.health` set the step runs guarded
(`repro.health.guard`): in-graph verdicts over loss/grads/SNIS
diagnostics, skip-step recovery via an in-graph select, checkpoint
rollback after `max_consecutive_bad` bad steps, and (with
`HealthConfig.index`) the retrieval degradation ladder — forced
compaction -> warm rebuild -> plan-level exact fallback. A `FaultPlan`
(`repro.health.faults`) can be injected for deterministic fault drills;
its signals ride the step as operands, so arming a fault never
retraces.
"""
from __future__ import annotations

import dataclasses
import time
from typing import TYPE_CHECKING, Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.fopo import FOPOConfig, fopo_loss, reinforce_loss
from repro.core.gradients import exact_objective
from repro.core.plan import ExecutionPlan
from repro.core.policy import SoftmaxPolicy, linear_tower_apply, linear_tower_init
from repro.core.proposals import adaptive_epsilon
from repro.core.rewards import make_session_reward
from repro.core.snis import DIAGNOSTIC_KEYS
from repro.data.loader import BatchLoader
from repro.health.guard import grad_global_norm, init_guard_state
from repro.data.synthetic import SessionDataset
from repro.kernels.ivf_topk.ops import live_tile_share
from repro.mips.exact import topk_exact
from repro.obs.run import ObsConfig, ObsRun
from repro.obs.schema import validate_history
from repro.obs.sinks import format_rollback_line, format_train_line
from repro.obs.trace import span
from repro.optim.optimizers import Optimizer, adam, clip_by_global_norm
from repro.train import checkpoint as ckpt

if TYPE_CHECKING:
    from repro.health.faults import FaultPlan
    from repro.health.guard import HealthConfig


@dataclasses.dataclass
class TrainerConfig:
    estimator: str = "fopo"  # fopo | reinforce | exact
    fopo: FOPOConfig = dataclasses.field(
        default_factory=lambda: FOPOConfig(num_items=0)
    )
    batch_size: int = 32
    learning_rate: float = 1e-4
    num_steps: int = 1000
    grad_clip: float = 0.0
    adaptive_eps: bool = False  # beyond-paper: schedule eps 1.0 -> 0.1
    checkpoint_dir: str | None = None
    checkpoint_every: int = 500
    keep_checkpoints: int = 3
    eval_every: int = 0
    seed: int = 0
    # robustness layer (repro.health): None runs the bare step — with a
    # HealthConfig the step is guarded (verdict + in-graph skip), bad
    # runs roll back to the last good snapshot, and HealthConfig.index
    # arms the retrieval degradation ladder
    health: "HealthConfig | None" = None
    # telemetry (repro.obs): history and log lines always route through
    # the metrics bus; an ObsConfig additionally leaves run artifacts
    # (JSONL stream, Chrome trace, optional jax.profiler) and arms the
    # roofline-drift monitor
    obs: ObsConfig | None = None


class FOPOTrainer:
    def __init__(
        self,
        cfg: TrainerConfig,
        dataset: SessionDataset,
        *,
        retriever_kwargs: dict | None = None,
        fault_plan: "FaultPlan | None" = None,
    ):
        self.cfg = cfg
        self.dataset = dataset
        p, l = dataset.item_embeddings.shape
        fopo_cfg = cfg.fopo
        if fopo_cfg.num_items == 0:
            fopo_cfg = dataclasses.replace(fopo_cfg, num_items=p)
        if cfg.estimator == "fopo":
            # resolve the whole knob matrix ONCE at wiring time:
            # interpret mode, tile clamp, retriever construction,
            # sampler selection, single-vs-dist routing — and fail
            # invalid knob combinations here, before any tracing
            self.plan = ExecutionPlan.resolve(
                fopo_cfg, retriever_kwargs=retriever_kwargs or {}
            )
            fopo_cfg = self.plan.cfg
            self.retriever = self.plan.retriever
        else:
            # reinforce / exact read num_samples off the config only
            self.plan = None
            self.retriever = None
        if fopo_cfg is not cfg.fopo:
            cfg = dataclasses.replace(cfg, fopo=fopo_cfg)
            self.cfg = cfg
        self.policy = SoftmaxPolicy(tower=linear_tower_apply, item_dim=l)
        key = jax.random.PRNGKey(cfg.seed)
        self.params = linear_tower_init(key, l, l)
        self.beta = jnp.asarray(dataset.item_embeddings)
        dist = cfg.fopo.dist
        if dist is not None and p % dist.n_model == 0:
            # place the catalog row-sharded over `model` up front so no
            # step ever materialises it on one device (ragged catalogs
            # stay host-side; the dist step pads and shards them itself)
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            self.beta = jax.device_put(
                self.beta, NamedSharding(dist.mesh, P(dist.model_axis, None))
            )
        self.optimizer: Optimizer = adam(cfg.learning_rate)
        self.opt_state = self.optimizer.init(self.params)
        self.step = 0
        self.loader = BatchLoader(
            {"contexts": dataset.contexts, "positives": dataset.positives},
            cfg.batch_size,
            seed=cfg.seed,
        )
        # incremental index maintenance (cfg.fopo.index_refresh): the
        # plan built the initial RefreshState from the caller's index;
        # the trainer owns it from here and dispatches the jitted
        # maintenance ops asynchronously between steps (see train())
        # the retriever's index operand (None without an indexed
        # retriever); only a refresh plan maintains — and checkpoints — it
        self.index_state = (
            self.plan.initial_index_state if self.plan is not None else None
        )
        self._maintained = self.plan is not None and self.plan.refresh is not None
        # the share of the index's cap tiles that hold any item (the
        # `ivf.live_tile_share` gauge), read from the lists where they
        # are built or rebuilt on the host: here, and after a ladder
        # compaction or rebuild; the first train() emits this reading
        self._cap_tile = (retriever_kwargs or {}).get("cap_tile")
        self._pending_tile_share = (
            live_tile_share(self.index_state.lists, self._cap_tile)
            if self.index_state is not None else None
        )
        self._refresh_fns = self._build_refresh() if self._maintained else None
        self._refresh_key = jax.random.PRNGKey(cfg.seed + 31)
        # the training RNG is OWNED (not a train()-local): it rides the
        # checkpoint, so a killed-and-resumed run continues the exact
        # key sequence of an uninterrupted one
        self._train_key = jax.random.PRNGKey(cfg.seed + 17)
        # --- robustness state (all None/zero when cfg.health is None) -
        self.fault_plan = fault_plan
        self.guard_state = None
        self._snapshot: dict | None = None
        self._restarts = 0  # rollbacks taken (folds into the re-split key)
        self._degraded = False  # ladder's terminal rung taken
        self._monitor = None
        if cfg.health is not None:
            self.guard_state = init_guard_state()
            if cfg.health.index is not None:
                from repro.health.index_health import IndexHealthMonitor

                self._monitor = IndexHealthMonitor(cfg.health.index)
        self._train_step = self._build_step()

    # ------------------------------------------------------------------
    def _build_step(self) -> Callable:
        cfg = self.cfg
        policy = self.policy
        optimizer = self.optimizer
        health = cfg.health
        guard_dist = cfg.fopo.dist if cfg.estimator == "fopo" else None

        # beta and index_state ride as OPERANDS, not closure captures:
        # `update_items` (catalog churn) and the async refresh ops
        # produce new arrays each cadence — captured values would pin
        # the trace to the build-time tables and silently serve them
        def loss_fn(params, key, contexts, positives, eps, beta, index_state):
            reward_fn = make_session_reward(positives)
            if cfg.estimator == "fopo":
                loss, aux = fopo_loss(
                    policy, params, key, contexts, beta, reward_fn,
                    cfg.fopo, self.retriever,
                    epsilon=eps if cfg.adaptive_eps else None,
                    plan=self.plan,  # resolved once in __init__
                    index_state=index_state,
                )
                return loss, aux
            if cfg.estimator == "reinforce":
                loss = reinforce_loss(
                    policy, params, key, contexts, beta, reward_fn,
                    cfg.fopo.num_samples,
                )
                return loss, {}
            if cfg.estimator == "exact":
                p = beta.shape[0]
                dense = jnp.zeros((contexts.shape[0], p))
                safe = jnp.maximum(positives, 0)
                dense = dense.at[
                    jnp.arange(contexts.shape[0])[:, None], safe
                ].max((positives >= 0).astype(jnp.float32))
                loss = exact_objective(policy, params, contexts, beta, dense)
                return loss, {}
            raise ValueError(cfg.estimator)

        # whether guard/fault code traces is STATIC (config presence);
        # whether a check/fault fires is data — one trace either way
        @jax.jit
        def train_step(
            params, opt_state, guard_state, key, contexts, positives, eps,
            beta, index_state, fault,
        ):
            (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, key, contexts, positives, eps, beta, index_state
            )
            # The guard's bitwise-no-op guarantee needs the backward
            # pass and the optimizer update to compile IDENTICALLY in
            # the guarded and unguarded programs, so the guard may add
            # ZERO consumers to either subgraph (an extra consumer
            # makes XLA duplicate cheap elementwise chains into it with
            # different FMA contraction — 1-ULP drift; optimization_
            # barrier fences are stripped before fusion on CPU and
            # cannot pin this). Hence:
            #  - the grad-norm reduction runs IN BOTH programs and is
            #    returned via aux["grad_norm"], so `grads` has the same
            #    consumer set either way (the verdict reads the scalar,
            #    never the grad tree);
            #  - the clip + optimizer apply live in `do_update`, which
            #    the guarded program runs inside a `lax.cond` branch —
            #    a separate HLO computation fusion cannot reach into
            #    (see repro.health.guard.guarded_update).
            if fault is not None:
                from repro.health.faults import inject_aux, inject_grads

                grads = inject_grads(grads, fault)
                aux = inject_aux(aux, fault)
            gnorm = grad_global_norm(grads)
            aux = dict(aux, grad_norm=gnorm)

            def do_update(p, o):
                g = grads
                if cfg.grad_clip > 0:
                    g = clip_by_global_norm(g, cfg.grad_clip)
                return optimizer.update(g, o, p)

            if guard_state is None:
                new_params, new_opt_state = do_update(params, opt_state)
                return (
                    new_params, new_opt_state, None, loss, aux,
                    jnp.zeros((), jnp.int32),
                )
            from repro.health.guard import guarded_update

            out_params, out_opt, out_guard, verdict = guarded_update(
                health, guard_state, loss, gnorm, aux,
                params, opt_state, do_update,
                dist=guard_dist,
            )
            return out_params, out_opt, out_guard, loss, aux, verdict

        return train_step

    def _build_refresh(self) -> dict:
        """jit the maintenance ops ONCE with the schedule's static knobs
        (minibatch / count_decay / num_items baked in): every later
        dispatch reuses the trace — no recompiles, no host syncs."""
        from functools import partial

        from repro.mips import refresh as R

        rc = self.plan.refresh
        p = self.cfg.fopo.num_items
        health = self.cfg.health
        iters = (
            health.index.rebuild_iters
            if health is not None and health.index is not None
            else 4
        )
        if self.cfg.fopo.dist is None:
            return {
                "refresh": jax.jit(partial(
                    R.refresh_step,
                    minibatch=rc.minibatch, count_decay=rc.count_decay,
                )),
                "append": jax.jit(partial(R.delta_append)),
                "compact": jax.jit(partial(R.compact)),
                "rebuild": jax.jit(partial(R.rebuild, iters=iters)),
            }
        return {
            "refresh": jax.jit(partial(
                R.refresh_step_sharded,
                minibatch=rc.minibatch, count_decay=rc.count_decay,
            )),
            "append": jax.jit(partial(R.delta_append_sharded, num_items=p)),
            "compact": jax.jit(partial(R.compact_sharded)),
            "rebuild": jax.jit(partial(R.rebuild_sharded, iters=iters)),
        }

    # ------------------------------------------------------------------
    def update_items(self, ids, embs) -> None:
        """Catalog churn entry point: overwrite beta rows `ids` with
        `embs` and (when maintaining an index) delta-append them so the
        very next retrieval can serve the fresh embeddings — no rebuild.
        Fixed-size batches keep the append on its single trace; pad
        with id -1 rows to reuse a batch shape."""
        ids = jnp.asarray(ids, jnp.int32)
        embs = jnp.asarray(embs, self.beta.dtype)
        # pad rows (-1) scatter to the OOB sentinel P and are dropped —
        # never -1 (wraps) or a clamped 0 (would race a real row-0 write)
        idx = jnp.where(ids >= 0, ids, self.beta.shape[0])
        self.beta = self.beta.at[idx].set(embs, mode="drop")
        if self._refresh_fns is not None and not self._degraded:
            self.index_state = self._refresh_fns["append"](
                self.index_state, ids, embs
            )

    def _maybe_refresh_index(self) -> None:
        """The async trainer hook: dispatch this step's scheduled
        maintenance WITHOUT blocking — JAX's async dispatch is the
        separate stream (the fused train step already in flight never
        waits on it; the next step consumes the new state through an
        ordinary data dependency). A degraded trainer (exact fallback)
        skips maintenance — the index is out of the serving path."""
        if self._degraded:
            return
        rc = self.plan.refresh
        done = self.step + 1  # steps completed incl. the one in flight
        if rc.every and done % rc.every == 0:
            self._refresh_key, sub = jax.random.split(self._refresh_key)
            self.index_state = self._refresh_fns["refresh"](
                self.index_state, sub, self.beta
            )
        if rc.compact_every and done % rc.compact_every == 0:
            self.index_state = self._refresh_fns["compact"](
                self.index_state, self.beta
            )

    # ------------------------------------------------------------------
    # the retrieval degradation ladder (repro.health.index_health)
    # ------------------------------------------------------------------
    def _maybe_probe_index(self, bus) -> None:
        """Feed the ladder monitor and execute its escalations. Runs at
        the probe cadence (host-side — the sampled recall probe blocks,
        which is exactly why it is periodic, not per-step). Observations
        land on the metrics bus as index_health events."""
        monitor = self._monitor
        if monitor is None or self._degraded or not self._maintained:
            return
        ih = monitor.cfg
        cadence = ih.probe_every if ih.probe_every else 1
        if self.step % cadence != 0:
            return
        recall = None
        if ih.probe_every:
            from repro.mips.ivf import DEFAULT_N_PROBE
            from repro.mips.refresh import sampled_recall

            rows = min(ih.probe_rows, len(self.dataset.contexts))
            queries = self.policy.user_embedding(
                self.params, jnp.asarray(self.dataset.contexts[:rows])
            )
            recall = sampled_recall(
                self.index_state, self.beta, queries, ih.probe_k,
                n_probe=ih.n_probe or DEFAULT_N_PROBE,
            )
        overflow = int(jnp.max(self.index_state.overflow))  # sharded: worst
        action = monitor.observe(recall, overflow)
        if recall is not None or action:
            bus.event(
                "index_health",
                {"step": self.step, "recall": recall, "overflow": overflow,
                 "action": action},
                step=self.step,
            )
        if action in ("compact", "rebuild"):
            with span(f"index_{action}", step=self.step):
                self.index_state = self._refresh_fns[action](
                    self.index_state, self.beta
                )
            bus.gauge(
                "ivf.live_tile_share",
                live_tile_share(self.index_state.lists, self._cap_tile),
                step=self.step,
            )
        elif action == "fallback":
            self._degrade()

    def _degrade(self) -> None:
        """The ladder's last rung: swap the plan's retriever for its
        pre-resolved exact fallback and rebuild the jitted step against
        it (operands unchanged — index_state still rides, unused)."""
        if self._degraded or self.plan is None:
            return
        self.plan = self.plan.degrade_to_fallback()
        self.retriever = self.plan.retriever
        self._degraded = True
        self._train_step = self._build_step()

    # ------------------------------------------------------------------
    # snapshot / rollback (the guard's escalation path)
    # ------------------------------------------------------------------
    def _take_snapshot(self) -> None:
        """In-memory last-good state: device-array REFERENCES (JAX
        arrays are immutable — no copies, no host syncs)."""
        self._snapshot = {
            "step": self.step,
            "state": self._ckpt_state(),
            "loader": self.loader.state.to_dict(),
        }

    def _rollback(self) -> None:
        """max_consecutive_bad exceeded: restore the last good snapshot
        and RE-SPLIT the training key (replaying the same keys would
        deterministically reproduce a data-dependent bad step; folding
        in the restart count gives the replay a fresh stream)."""
        self._restarts += 1
        snap = self._snapshot
        if snap is not None:
            st = snap["state"]
            self.params = st["params"]
            self.opt_state = st["opt_state"]
            self._refresh_key = st["refresh_key"]
            if "index_state" in st:
                self.index_state = st["index_state"]
            self.step = snap["step"]
            self.loader.state = self.loader.state.from_dict(snap["loader"])
            base = st["train_key"]
        else:
            base = self._train_key
        self._train_key = jax.random.fold_in(base, self._restarts)
        self.guard_state = init_guard_state()

    # ------------------------------------------------------------------
    def _ckpt_state(self) -> dict:
        """EVERYTHING resume needs, as one pytree: params, opt state,
        the maintained index (RefreshState incl. its overflow counter),
        the guard state, and both RNG keys — a restart resumes the
        exact trajectory, not just the params."""
        state: dict[str, Any] = {
            "params": self.params,
            "opt_state": self.opt_state,
            "train_key": self._train_key,
            "refresh_key": self._refresh_key,
        }
        if self._maintained:
            state["index_state"] = self.index_state
        if self.guard_state is not None:
            state["guard_state"] = self.guard_state
        return state

    def _adopt_state(self, state: dict) -> None:
        def as_jnp(x):
            return jnp.asarray(x) if x is not None else None

        self.params = jax.tree.map(as_jnp, state["params"])
        self.opt_state = jax.tree.map(as_jnp, state["opt_state"])
        self._train_key = jnp.asarray(state["train_key"])
        self._refresh_key = jnp.asarray(state["refresh_key"])
        if "index_state" in state:
            self.index_state = jax.tree.map(as_jnp, state["index_state"])
        if "guard_state" in state:
            self.guard_state = jax.tree.map(as_jnp, state["guard_state"])

    def maybe_restore(self) -> bool:
        cfg = self.cfg
        if not cfg.checkpoint_dir:
            return False
        latest = ckpt.latest_checkpoint(cfg.checkpoint_dir)
        if latest is None:
            return False
        # fallback=True: a corrupt latest checkpoint (checksum mismatch,
        # torn npz) walks back to the previous rotated one instead of
        # resuming garbage or dying
        with span("checkpoint_restore", step=self.step):
            step, state, extra = ckpt.restore_checkpoint(
                cfg.checkpoint_dir, self._ckpt_state(), fallback=True
            )
        self._adopt_state(state)
        self.step = step
        if "loader" in extra:
            self.loader.state = self.loader.state.from_dict(extra["loader"])
        self._restarts = int(extra.get("restarts", 0))
        if extra.get("degraded"):
            self._degrade()
        return True

    def save(self) -> None:
        cfg = self.cfg
        if not cfg.checkpoint_dir:
            return
        health = cfg.health
        with span("checkpoint_save", step=self.step):
            ckpt.save_checkpoint(
                cfg.checkpoint_dir,
                self.step,
                self._ckpt_state(),
                extra={
                    "loader": self.loader.state.to_dict(),
                    "restarts": self._restarts,
                    "degraded": self._degraded,
                },
                keep=cfg.keep_checkpoints,
                retries=health.save_retries if health is not None else 0,
                backoff=health.save_backoff if health is not None else 0.05,
            )

    # ------------------------------------------------------------------
    def train(self, num_steps: int | None = None, log_every: int = 0) -> dict:
        """Run ``num_steps`` steps; returns the validated history.

        Spans (`repro.obs.trace`, with ``step=`` args): ``train`` around
        the call, one ``train_step`` a step with children ``next_batch``
        (loader, key split, eps, fault signals), ``dispatch`` (placement
        and the jitted step's enqueue), ``index_refresh`` (when armed),
        ``drain`` (the wait for the loss) and ``record`` (the bus, step
        time, verdict, snapshot, probe, checkpoint, eval, log line)."""
        with span("train", step=self.step):
            return self._train(num_steps, log_every)

    def _train(self, num_steps: int | None, log_every: int) -> dict:
        cfg = self.cfg
        health = cfg.health
        n = num_steps if num_steps is not None else cfg.num_steps
        if health is not None and self._snapshot is None:
            self._take_snapshot()  # step-0 rollback target
        t_total = time.perf_counter()
        # one telemetry run per train() call: the bus's ring sink IS the
        # history backing (cfg.obs=None still runs bus + ring + human
        # log sink — no files, no tracer, no drift monitor)
        with ObsRun(cfg.obs, predicted_step_s=self._predicted_step_s()) as run:
            if self._monitor is not None:
                self._monitor.bind_bus(run.bus)
            if self._pending_tile_share is not None:
                run.bus.gauge(
                    "ivf.live_tile_share", self._pending_tile_share, step=self.step
                )
                self._pending_tile_share = None
            for _ in range(n):
                with span("train_step", step=self.step):
                    self._step(run, log_every)
            history = run.history()
        history["total_time"] = time.perf_counter() - t_total
        return validate_history(history)

    def _step(self, run: ObsRun, log_every: int) -> None:
        """One step of `train` (see its docstring for the spans)."""
        cfg = self.cfg
        step = self.step
        with span("next_batch", step=step):
            if self.fault_plan is not None:
                self.fault_plan.maybe_kill(step)
            batch = self.loader.next_batch()
            self._train_key, sub = jax.random.split(self._train_key)
            eps = adaptive_epsilon(step, cfg.num_steps) if cfg.adaptive_eps else 0.0
            fault = (
                self.fault_plan.signals(step)
                if self.fault_plan is not None else None
            )
        t0 = time.perf_counter()
        with span("dispatch", step=step):
            (
                self.params, self.opt_state, self.guard_state, loss,
                aux, verdict,
            ) = self._train_step(
                self.params,
                self.opt_state,
                self.guard_state,
                sub,
                self._place_batch(batch["contexts"]),
                self._place_batch(batch["positives"]),
                eps,
                self.beta,
                self.index_state,
                fault,
            )
        if self._refresh_fns is not None:
            # dispatched async while the step above is in flight —
            # the step never blocks on maintenance (and vice versa)
            with span("index_refresh", step=step):
                self._maybe_refresh_index()
        with span("drain", step=step):
            jax.block_until_ready(loss)
        step_time = time.perf_counter() - t0
        with span("record", step=step):
            self._record(run, log_every, step_time, loss, aux, verdict)

    def _record(self, run: ObsRun, log_every: int, step_time: float, loss, aux,
                verdict) -> None:
        """The host side of a finished step: the bus, the guard's
        verdict, snapshot or rollback, probe, checkpoint, eval, log."""
        cfg = self.cfg
        health = cfg.health
        bus = run.bus
        bus.gauge("loss", loss, step=self.step)
        for k in DIAGNOSTIC_KEYS:
            if k in aux:
                bus.gauge(k, aux[k], step=self.step)
        run.observe_step_time(step_time, self.step)
        self.step += 1
        # the verdict is consumed HERE, after the step result is
        # already on host — reading it adds no step-time sync
        v = int(verdict) if health is not None else 0
        if v:
            from repro.health.guard import verdict_record

            bus.event("health", verdict_record(self.step, v), step=self.step)
            if int(self.guard_state.consecutive_bad) >= health.max_consecutive_bad:
                rolled_to = self._snapshot["step"] if self._snapshot else self.step
                self._rollback()
                bus.event(
                    "events",
                    {"step": self.step, "event": "rollback",
                     "to": rolled_to, "restarts": self._restarts},
                    step=self.step,
                )
                if log_every:
                    bus.log(format_rollback_line(
                        self.step, rolled_to, self._restarts
                    ))
                bus.drain()
                return
        elif health is not None and self.step % health.snapshot_every == 0:
            self._take_snapshot()
        with span("index_probe", step=self.step):
            self._maybe_probe_index(bus)
        if cfg.checkpoint_every and self.step % cfg.checkpoint_every == 0:
            self.save()
        if cfg.eval_every and self.step % cfg.eval_every == 0:
            with span("eval", step=self.step):
                bus.event(
                    "reward",
                    {"step": self.step, "value": self.evaluate()},
                    step=self.step,
                )
        if log_every and self.step % log_every == 0:
            from repro.health.guard import decode_verdict

            bus.log(format_train_line(
                self.step, float(loss),
                {k: float(aux[k]) for k in DIAGNOSTIC_KEYS if k in aux},
                decode_verdict(v) if v else (),
                self._degraded,
            ))
        bus.drain()  # post-block: futures -> host floats, logs out

    def _predicted_step_s(self) -> float | None:
        """Analytic roofline prediction of one step's wall time — the
        drift monitor's denominator. None (monitor stays off) when the
        estimator has no resolved plan, the obs config doesn't arm
        drift, or the roofline models aren't importable."""
        obs = self.cfg.obs
        if obs is None or obs.drift is None or self.plan is None:
            return None
        from repro.obs.drift import predict_step_seconds

        return predict_step_seconds(
            self.plan, self.cfg.batch_size, self.beta.shape[1]
        )

    # ------------------------------------------------------------------
    def _place_batch(self, arr) -> jnp.ndarray:
        """Data-parallel placement: batches land row-sharded over the
        mesh `data` axis in dist mode (otherwise a plain asarray)."""
        arr = jnp.asarray(arr)
        dist = self.cfg.fopo.dist
        if dist is None or self.cfg.estimator != "fopo":
            return arr
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        spec = P(dist.data_axis, *(None,) * (arr.ndim - 1))
        return jax.device_put(arr, NamedSharding(dist.mesh, spec))

    # ------------------------------------------------------------------
    def evaluate(self, dataset: SessionDataset | None = None, max_rows: int = 4096) -> float:
        """R_test: fraction of argmax recommendations that hit Y (paper's
        test metric), with the argmax served through MIPS like production."""
        ds = dataset or self.dataset
        n = min(len(ds.contexts), max_rows)
        contexts = jnp.asarray(ds.contexts[:n])
        h = self.policy.user_embedding(self.params, contexts)
        top1 = topk_exact(h, self.beta, 1).indices[:, 0]
        pos = ds.positives[:n]
        hits = (np.asarray(top1)[:, None] == pos).any(axis=1)
        return float(hits.mean())
