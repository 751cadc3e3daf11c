"""Runner for retrieval-serving cells: `ServingEngine` -> `RecsysMIPSRoute`
-> `QueryPlanner.query` (the `ivf_topk` kernel over the live index).

The load is open-loop on the real clock. Every due time and payload is
made before the window. One loop, in this process, admits the requests
that are due, asks the program's coalescing policy
(`repro.serve.coalescer.next_batch`) when the next batch launches,
waits for that moment (sleeping, then spinning for the last 1.5 ms, so
it does not oversleep), and hands the batch to `ServingEngine.serve_batch`.
A request's latency runs from its due time to the moment its top-k is
on the host after `finalize`. Nothing prints and no collection runs
inside the window; results land in preallocated arrays. The window's
close ends serving (a saturated queue never empties); the warm-up serves
every request it offers.

Compared with the reference on a seeded sample of the answered requests
(the longest history always in it):
  answer_err  the widest gap, over the request's largest reference
              score, by which a returned item misses: its returned score
              against the reference's score of the same item, or its
              reference score below the reference's k-th best
  index_faults  catalog items no list holds, plus repeats
  partition_gap  how far an item's list lies from its nearest centroid
              (`harness/partition.py`), over the reference's catalog rows
  failed      answered requests whose answer is not k distinct valid ids
              with finite scores
"""
from __future__ import annotations

import collections
import contextlib
import itertools
import math
import time

import numpy as np

from perfbench.harness import partition, session, traffic as gen
from perfbench.harness.session import clock, log

SPIN_S = 0.0015
SAMPLE = 256
REF_BLOCK = 64


def wait_until(t: float) -> None:
    """Return at ``t``: sleep, then spin, so as not to oversleep."""
    while True:
        dt = t - clock()
        if dt <= 0.0:
            return
        if dt > SPIN_S:
            time.sleep(dt - SPIN_S)


class OpenLoop:
    """One open-loop stream against one engine (see the module doc)."""

    def __init__(self, engine, policy, due, payloads):
        from repro.serve.coalescer import Request

        self.engine, self.policy = engine, policy
        self.due = np.asarray(due, np.float64)
        n = len(self.due)
        self.requests = [Request(rid=r, payload=payloads[r], arrival=float(self.due[r]))
                         for r in range(n)]
        self.finish = np.full(n, np.nan)
        self.results: list = [None] * n
        self.batch_launch = np.full(n, np.nan)
        self.batch_total = np.full(n, np.nan)
        self.batch_size = np.zeros(n, np.int32)
        self.batches = 0

    def run(self, seconds: float, complete: bool = False, spans: bool = False) -> float:
        """Serve the stream until the window closes (every request, with
        ``complete``); returns the window's start on the clock."""
        from repro.serve.coalescer import next_batch

        engine, policy, n = self.engine, self.policy, len(self.due)
        mb = policy.max_batch
        t0 = clock() + 0.002
        due = t0 + self.due
        end = t0 + seconds
        give_up = end + 60.0
        q: collections.deque = collections.deque()
        i = 0
        # a fresh annotation each time: one is not re-entered
        span = session.span if spans else (lambda _name: contextlib.nullcontext())
        while True:
            now = clock()
            while i < n and due[i] <= now:
                q.append(i)
                i += 1
            if not q:
                if i >= n or (not complete and now >= end) or now >= give_up:
                    break
                with span("wait"):
                    wait_until(due[i])
                continue
            size, launch = next_batch([due[r] for r in itertools.islice(q, mb)],
                                      now, policy)
            if launch > now:
                with span("wait"):
                    wait_until(min(launch, due[i]) if i < n else launch)
                continue
            rids = [q.popleft() for _ in range(size)]
            tl = clock()
            with span("serve_batch"):
                out = engine.serve_batch([self.requests[r] for r in rids])
            tf = clock()
            self.finish[rids] = tf
            for rec in out:
                self.results[rec.rid] = rec.result
            b = self.batches
            self.batch_launch[b] = tl
            self.batch_total[b] = tf - tl
            self.batch_size[b] = size
            self.batches = b + 1
            if (not complete and tf >= end) or tf >= give_up:
                break
        return t0


def build(config: dict, ref, seed: int):
    from repro.models.configs_base import RecsysConfig
    from repro.serve import CoalescePolicy, RecsysMIPSRoute, ServingEngine

    params = ref.make_params(config, gen.jax_key(seed))
    rcfg = RecsysConfig(
        name=config["name"], kind=config["kind"], item_vocab=config["item_vocab"],
        embed_dim=config["embed_dim"], seq_len=config["seq_len"],
        num_blocks=config["num_blocks"], num_heads=config["num_heads"])
    route = RecsysMIPSRoute(rcfg, params, k=config["top_k"],
                            num_clusters=config["num_clusters"],
                            n_probe=config["n_probe"], seed=gen.program_seed(seed))
    policy = CoalescePolicy(max_batch=config["max_batch"],
                            max_wait_s=config["max_wait_ms"] / 1e3)
    return params, route, policy, ServingEngine(route, policy)


def stream(config: dict, traffic: dict, seed: int, seconds: float, name: str,
           count: int | None = None):
    """(due times, histories) of one named stream of the mix; ``count``
    requests all due at once instead, where given (the warm-up)."""
    s = hash_seed(seed, name)
    due = (np.zeros(count) if count is not None
           else gen.open_loop_arrivals(s, float(traffic["rate"]), seconds))
    hists = gen.histories(s, len(due), config["seq_len"], config["item_vocab"],
                          *history_shape(traffic))
    return due, hists


def history_shape(traffic: dict) -> tuple:
    """(shortest, mean, longest) history of the mix."""
    h = traffic["history"]
    return int(h["min"]), float(h["mean"]), int(h["max"])


def hash_seed(seed: int, name: str) -> int:
    """A seed per stream, so the window, the warm-up and the traced
    window draw different requests from one --seed."""
    return (int(seed) * 1_000_003 + sum(ord(c) * 31 ** i for i, c in enumerate(name))) % 2**62


def run(*, cell, config, traffic, ref, limits, seed, seconds, trace, t_start,
        devices, tamper=None):
    params, route, policy, engine = build(config, ref, seed)
    planner = route.planner
    if devices[0].platform == "tpu" and planner.plan.interpret:
        raise RuntimeError("the serving plan resolved interpret mode on the chip")
    engine.warmup()
    if tamper is not None:
        tamper(route, engine)
    warm_n = int(traffic["warmup_batches"]) * policy.max_batch
    OpenLoop(engine, policy, *stream(config, traffic, seed, 0.0, "warmup",
                                     count=warm_n)).run(60.0, complete=True)
    due, hists = stream(config, traffic, seed, seconds, "window")
    loop = OpenLoop(engine, policy, due, hists)
    setup_s = clock() - t_start

    watch = session.CompileWatch()
    with session.quiet_gc(), watch.watching():
        t0 = loop.run(seconds)
    host = summarize(loop, t0, seconds)
    log(f"window: due {host['due_in_window']} answered {host['answered_in_window']} "
        f"in {host['batches']} batches; programs traced or compiled in the window: "
        f"{watch.count}")

    traced = reduction = None
    if trace:
        t_due, t_hists = stream(config, traffic, seed, float(traffic["trace_seconds"]), "trace")
        tloop = OpenLoop(engine, policy, t_due, t_hists)
        with session.traced_window() as tw:
            tloop.run(float(traffic["trace_seconds"]), spans=True)
        reduction = tw["reduction"]
        traced = {"batches": tloop.batches, "batch_rows": policy.max_batch}
    mem = session.memory_peak_bytes(devices)

    state = planner.index_state
    lists = np.concatenate([np.asarray(state.lists), np.asarray(state.delta_lists)], axis=1)
    centroids = np.asarray(state.centroids)
    del route, planner, engine, state
    import gc

    gc.collect()
    t_ref = clock()
    checks, invalid = compare(config, ref, params, lists, centroids, loop, hists, seed,
                              limits)
    log(f"reference: {SAMPLE} requests in {clock() - t_ref:.3f} s")
    return {
        "setup_s": setup_s, "host": host, "traced": traced, "reduction": reduction,
        "memory_peak_bytes": mem, "job": config,
        "attempted": host["answered_in_window"], "failed": invalid, "checks": checks,
    }


def summarize(loop: OpenLoop, t0: float, seconds: float) -> dict:
    end = t0 + seconds
    due_abs = t0 + loop.due
    in_window = due_abs < end
    answered = ~np.isnan(loop.finish)
    nb = loop.batches
    return {
        "window_s": seconds,
        "due_in_window": int(np.sum(in_window)),
        "answered_in_window": int(np.sum(answered & (loop.finish <= end))),
        "batches_in_window": int(np.sum(loop.batch_launch[:nb] + loop.batch_total[:nb]
                                        <= end)),
        "batches": nb,
        "latency_s": (loop.finish - due_abs)[in_window & answered],
        "batch_total_s": loop.batch_total[:nb],
        "batch_size": loop.batch_size[:nb],
    }


def compare(config, ref, params, lists, centroids, loop, hists, seed, limits):
    import jax.numpy as jnp

    n = len(loop.due)
    answered = [r for r in range(n) if loop.results[r] is not None]
    k, v = config["top_k"], config["item_vocab"]
    invalid = 0
    for r in answered:
        ids, scores = loop.results[r]
        ids = np.asarray(ids)
        if (ids.shape != (k,) or np.any(ids < 0) or np.any(ids >= v)
                or len(set(ids.tolist())) != k
                or not np.all(np.isfinite(np.asarray(scores)))):
            invalid += 1
    checks = session.Checks()
    checks.add("index_faults", session.index_faults(lists, v), 0)
    checks.add("partition_gap", partition.partition_gap(params["items"], lists, centroids),
               limits["partition_gap"])
    if not answered:
        checks.add("answer_err", math.inf, limits["answer_err"])
        return checks, invalid
    lengths = np.sum(hists >= 0, axis=1)
    pick = gen.rng(seed, "check").choice(answered, size=min(SAMPLE, len(answered)),
                                         replace=False)
    longest = max(answered, key=lambda r: lengths[r])
    if longest not in set(pick.tolist()):
        pick[0] = longest
    items = params["items"]
    lists_d, cents_d = jnp.asarray(lists), jnp.asarray(centroids)
    worst = 0.0
    for lo in range(0, len(pick), REF_BLOCK):
        rows = pick[lo: lo + REF_BLOCK]
        h = ref.user_vector(params, jnp.asarray(hists[rows]),
                            heads=config["num_heads"], precision="highest")
        _, ref_top = ref.ivf_topk(h, items, lists_d, cents_d, k=k,
                                  n_probe=config["n_probe"], precision="highest")
        ids = np.stack([np.asarray(loop.results[r][0]) for r in rows])
        got = np.stack([np.asarray(loop.results[r][1]) for r in rows])
        worst = max(worst, answer_err(ref, h, items, ids, got, np.asarray(ref_top), v))
    checks.add("answer_err", worst, limits["answer_err"])
    return checks, invalid


def answer_err(ref, h, items, ids, scores, ref_top, vocab) -> float:
    """The compared number of a block of answers (module doc): per slot,
    the larger of |returned score - reference score of the id| and the
    id's reference score below the reference's k-th best, over the
    row's largest |reference score|; the widest over the block."""
    import jax.numpy as jnp

    mine = np.asarray(ref.scores_of(h, items, jnp.asarray(np.clip(ids, 0, vocab - 1)),
                                    precision="highest"))
    scale = np.maximum(np.max(np.abs(ref_top), axis=1, keepdims=True), 1e-30)
    miss = np.maximum(np.abs(np.asarray(scores) - mine), ref_top[:, -1:] - mine)
    return float(np.max(miss / scale))
