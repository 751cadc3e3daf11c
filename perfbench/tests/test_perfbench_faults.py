"""A whole run at a small size on the CPU (the look for a chip skipped),
sound and with the timed path broken underneath: each fault a cell can
have must come out as not correct, and so must centroids out of step
with the lists the timed path probes. (One chip per cell: no exchange
between chips exists to leave out.)"""
import jax.numpy as jnp
import numpy as np
import pytest

TRAIN = "train.fopo-paper.paper"
SERVE = "serve.sasrec.saturated"


def _state_unchanged(trainer):
    step = trainer._train_step

    def frozen(params, opt_state, guard_state, *rest):
        out = step(params, opt_state, guard_state, *rest)
        return (params, opt_state) + tuple(out[2:])

    trainer._train_step = frozen


def _half_batch(trainer):
    step = trainer._train_step

    def half(params, opt_state, guard_state, key, contexts, positives, *rest):
        n = contexts.shape[0] // 2
        return step(params, opt_state, guard_state, key, contexts[:n], positives[:n], *rest)

    trainer._train_step = half


def _answer_altered(route, engine):
    finalize = route.finalize
    vocab = route.cfg.item_vocab

    def altered(out, n):
        res = finalize(out, n)
        return [(np.concatenate([[(ids[0] + 1) % vocab], ids[1:]]), scores)
                for ids, scores in res]

    route.finalize = altered


def _half_batch_served(route, engine):
    prepare = route.prepare

    def half(payloads):
        real = sum(not np.array_equal(p, route.pad_payload) for p in payloads)
        keep = real // 2
        return prepare(payloads[:keep] + [route.pad_payload] * (len(payloads) - keep))

    route.prepare = half


def _rolled(state):
    return state._replace(centroids=jnp.roll(state.centroids, 1, axis=0))


def _centroids_rolled_train(trainer):
    trainer.index_state = _rolled(trainer.index_state)


def _centroids_rolled_served(route, engine):
    route.planner.index_state = _rolled(route.planner.index_state)


@pytest.mark.parametrize("workload", [TRAIN, SERVE])
def test_a_sound_run_is_correct(small_run, workload):
    rc, res = small_run(workload)
    assert rc == 0 and res["correct"] is True, res
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res["checks"])[-1] == "failed"
    assert set(res["metrics"]) >= {"setup_s"}


@pytest.mark.parametrize("workload,fault", [
    (TRAIN, _state_unchanged), (TRAIN, _half_batch), (TRAIN, _centroids_rolled_train),
    (SERVE, _answer_altered), (SERVE, _half_batch_served),
    (SERVE, _centroids_rolled_served)],
    ids=["train-state-unchanged", "train-half-batch", "train-centroids-rolled",
         "serve-answer-altered", "serve-half-batch", "serve-centroids-rolled"])
def test_a_broken_timed_path_is_not_correct(small_run, workload, fault):
    rc, res = small_run(workload, tamper=fault)
    assert rc == 0 and res["correct"] is False, res["checks"]
