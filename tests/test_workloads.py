"""Roofline-cost-aware placement + the compiled-step cache (ISSUE 8).

Control-plane half (no jax): cost vectors / classification, steering tags,
queue-name routing, dispatcher tier preference, autoscaler family classes,
and the acceptance guarantee that cost-aware OFF (or an unpriced task) is
behavior-identical to the depth-aware-only plane.

Workload half (jax): TrainerCache hit/miss/evict semantics, warm-worker
reuse through the composer, and exactly-once step accounting when a train
task resumes from its own checkpoint.
"""
import pytest

from repro.core.plane import ManagementPlane, SimLocalPlane
from repro.pipelines import DAG, Task, HybridComposer
from repro.pipelines.scheduler import queue_for
from repro.roofline.cost import (ACCEL_CAP, CHEAP_IO_CAP, CostVector,
                                 classify, steering_tag, task_cost)
from tests.conftest import make_plane


# ------------------------------------------------------------ cost vectors
def test_classification_roofline_split():
    assert classify(CostVector(flops=0.0, io_bytes=1e9)) == "io"
    assert classify(CostVector(flops=1e12, hbm_bytes=1e9)) == "compute"
    assert classify(CostVector(flops=1e9, hbm_bytes=1e9)) == "memory"


def test_builtin_kinds_priced_analytically():
    train = Task("t", kind="train", payload={"steps": 10, "seq_len": 64,
                                             "global_batch": 8})
    ev = Task("e", kind="eval", payload={"seq_len": 64, "global_batch": 8})
    etl = Task("x", kind="etl", payload={"batches": 2})
    exp = Task("o", kind="export")
    srv = Task("s", kind="serve", payload={"slots": 4})
    assert classify(task_cost(train)) == "compute"
    assert steering_tag(train) == ACCEL_CAP
    assert classify(task_cost(ev)) == "compute"
    assert classify(task_cost(etl)) == "io"
    assert steering_tag(etl) == CHEAP_IO_CAP
    assert classify(task_cost(exp)) == "io"
    # decode: ~slots flops per HBM byte, below the machine balance
    assert classify(task_cost(srv)) == "memory"
    assert steering_tag(srv) == ACCEL_CAP


def test_unpriced_tasks_never_steered():
    py = Task("p", kind="python")
    assert task_cost(py) is None and steering_tag(py) is None
    unknown = Task("u", kind="train", payload={"arch": "no-such-arch"})
    assert task_cost(unknown) is None and steering_tag(unknown) is None
    # cost-aware routing is a strict no-op for both
    assert queue_for(py, cost_aware=True) == "default"
    assert queue_for(unknown, cost_aware=True) == "default"


def test_explicit_cost_and_artifact_beat_the_estimate():
    # an etl task whose committed dry-run artifact says it is compute-bound
    t = Task("t", kind="etl", cost={"flops": 1e12, "hbm_bytes": 1e9})
    assert steering_tag(t) == ACCEL_CAP
    # same artifact inlined in the payload (hlo_stats.stats_to_json shape)
    t2 = Task("t2", kind="etl",
              payload={"hlo_stats": {"flops": 1e12, "hbm_bytes": 1e9}})
    assert steering_tag(t2) == ACCEL_CAP


# ---------------------------------------------------------- queue routing
def test_queue_for_cost_aware_off_is_todays_behavior():
    tasks = [Task("a", kind="train", payload={"steps": 5}),
             Task("b", kind="etl"),
             Task("c", kind="python", requires=("onprem",)),
             Task("d", kind="eval", requires=("gpu", "onprem"))]
    expected = ["default", "default", "onprem", "gpu,onprem"]
    for t, q in zip(tasks, expected):
        assert queue_for(t) == q                      # default: off
        assert queue_for(t, cost_aware=False) == q


def test_queue_for_cost_aware_merges_steering_tag():
    t = Task("t", kind="train", payload={"steps": 5}, requires=("onprem",))
    assert queue_for(t, cost_aware=True) == "accel,onprem"
    assert queue_for(Task("x", kind="etl"), cost_aware=True) == "cheap-io"


def test_cost_aware_off_runs_priced_dag_on_default_queue_only():
    """Acceptance: with cost_aware off, priced tasks route exactly as today —
    the broker only ever sees the queues the requires tags imply."""
    plane = ManagementPlane()
    plane.add_cluster("master", is_master=True)
    plane.add_cluster("onprem-a")
    comp = HybridComposer(plane, workers={"onprem-a": ["w0"]})

    def instant(p):
        return {"ok": 1}

    comp.workers[0].register("sim_train", instant)
    comp.workers[0].register("sim_etl", instant)
    dag = DAG("d", [Task("t", kind="sim_train",
                         cost={"flops": 1e12, "hbm_bytes": 1e9}),
                    Task("x", kind="sim_etl", cost={"io_bytes": 1e9},
                         upstream=("t",))])
    comp.add_dag(dag)
    assert comp.run_dag("d", max_ticks=60)
    assert set(comp.broker.queues) == {"default"}


# ------------------------------------------------------- dispatcher tiers
def test_dispatcher_prefers_matching_tier_for_cost_class():
    plane = make_plane(2, caps={0: ("cpu", "accel"),
                                1: ("cpu", "cheap-io")})
    jid = plane.submit_job("sim", steps=5,
                           tags={"requires": ("cpu",),
                                 "cost_class": "compute"})
    placed = plane.overwatch.handle(
        {"op": "get", "key": f"/jobs/{jid}/placement"})["value"]
    assert placed["cluster"] == "onprem-0"
    jid2 = plane.submit_job("sim", steps=5,
                            tags={"requires": ("cpu",), "cost_class": "io"})
    placed2 = plane.overwatch.handle(
        {"op": "get", "key": f"/jobs/{jid2}/placement"})["value"]
    assert placed2["cluster"] == "onprem-1"


def test_dispatcher_cost_class_degrades_without_matching_tier():
    # no accel-tier cluster registered: the preference is soft — placement
    # falls back to plain least-load instead of failing
    plane = make_plane(2)
    jid = plane.submit_job("sim", steps=5,
                           tags={"requires": ("cpu",),
                                 "cost_class": "compute"})
    placed = plane.overwatch.handle(
        {"op": "get", "key": f"/jobs/{jid}/placement"})["value"]
    assert placed["cluster"] in ("onprem-0", "onprem-1")


def test_dispatcher_untagged_job_pick_unchanged():
    plane = make_plane(2, caps={0: ("cpu", "accel"),
                                1: ("cpu", "cheap-io")})
    # cost_class absent: byte-identical to the pre-cost plane (least-load)
    picked = {plane.dispatcher.pick({"job_id": f"j{i}",
                                     "tags": {"requires": ("cpu",)}})
              for i in range(4)}
    assert picked == {"onprem-0", "onprem-1"}      # round-robin over the tie


# ------------------------------------------------------ autoscaler family
def test_scaling_policy_folds_cost_class_into_requires():
    from repro.autoscale import ScalingPolicy
    pol = ScalingPolicy(family="train", queues=("accel",), requires=("cpu",),
                        cost_class="compute")
    assert ACCEL_CAP in pol.requires
    pol2 = ScalingPolicy(family="etl", queues=("cheap-io",),
                         cost_class="io")
    assert CHEAP_IO_CAP in pol2.requires
    with pytest.raises(ValueError):
        ScalingPolicy(family="bad", queues=("q",), cost_class="quantum")


# ------------------------------------------------------ compiled-step cache
def _train_cfg(**kw):
    from repro.runtime.train_loop import TrainJobConfig
    base = dict(arch="qwen3-0.6b", seq_len=8, global_batch=2, steps=1)
    base.update(kw)
    return TrainJobConfig(**base)


def test_trainer_cache_hit_miss_evict():
    from repro.runtime.step_cache import TrainerCache
    cache = TrainerCache(capacity=1)
    a = cache.get(_train_cfg())
    # per-run knobs (steps, seed, checkpoint_dir) are NOT part of the key
    a2 = cache.get(_train_cfg(steps=3, seed=7))
    assert a2 is a
    assert a2.cfg.steps == 3 and a2.step == 0     # rebound to the new task
    # a different compiled family misses and (capacity=1) evicts the first
    b = cache.get(_train_cfg(seq_len=16))
    assert b is not a
    a3 = cache.get(_train_cfg())
    assert a3 is not a
    assert cache.stats() == {"hits": 1, "misses": 3, "evictions": 2,
                             "size": 1}


def test_cache_capacity_zero_always_builds_cold():
    from repro.runtime.step_cache import TrainerCache
    cache = TrainerCache(capacity=0)
    a = cache.get(_train_cfg())
    b = cache.get(_train_cfg())
    assert b is not a and len(cache) == 0
    assert cache.stats()["misses"] == 2


def test_rebind_reproduces_cold_run(tmp_path):
    """A warm trainer re-armed for a new task must produce bit-identical
    losses to a cold build with the same config."""
    from repro.runtime.train_loop import Trainer
    cfg = _train_cfg(steps=4, seed=3)
    cold = Trainer(cfg)
    cold.run()
    warm = Trainer(_train_cfg(steps=2, seed=3))    # same family, other task
    warm.run()
    warm.rebind(cfg)
    assert warm.step == 0
    warm.run()
    assert cold.metrics.series("loss") == pytest.approx(
        warm.metrics.series("loss"), rel=1e-6)


def test_rebind_with_donated_state_is_bit_identical():
    """Each step donates the state it is given, so no initial copy survives:
    ``rebind`` (and ``release``) rebuild it from the seed, bit for bit."""
    import jax
    import numpy as np
    from repro.runtime.train_loop import Trainer
    cfg = _train_cfg(steps=3, seed=5)
    tr = Trainer(cfg)
    first = jax.tree_util.tree_leaves(tr.state)
    want = [np.array(x) for x in first]           # copies: no view pins x
    tr.run()
    assert all(x.is_deleted() for x in first)      # donated to the step
    losses = tr.metrics.series("loss")
    tr.release()
    assert tr.state is None
    tr.rebind(cfg)
    for a, b in zip(jax.tree_util.tree_leaves(tr.state), want):
        np.testing.assert_array_equal(np.asarray(a), b)
    tr.run()
    assert tr.metrics.series("loss") == losses


def test_worker_cache_reuse_through_composer():
    plane = ManagementPlane()
    plane.add_cluster("master", is_master=True)
    plane.add_cluster("onprem-a")
    comp = HybridComposer(plane, workers={"onprem-a": ["w0"]}, step_cache=4)
    payload = {"arch": "qwen3-0.6b", "steps": 1, "seq_len": 8,
               "global_batch": 2}
    dag = DAG("c", [Task(f"s{i}", kind="train", payload=dict(payload),
                         upstream=(f"s{i - 1}",) if i else ())
                    for i in range(3)])
    comp.add_dag(dag)
    assert comp.run_dag("c", max_ticks=100)
    stats = comp.workers[0]._trainer_cache.stats()
    assert stats["misses"] == 1 and stats["hits"] == 2
    state = comp.taskdb.handle({"op": "dag_state", "dag": "c"})["tasks"]
    for row in state.values():
        assert row["status"] == "success"
        assert row["result"]["steps"] == 1 and row["result"]["ran_steps"] == 1


def test_train_task_resume_exactly_once_accounting(tmp_path):
    """The handler-level resume contract: a re-delivered/continued train task
    restores the committed step and runs only the remainder."""
    from repro.runtime.step_cache import run_train_task
    payload = {"arch": "qwen3-0.6b", "seq_len": 8, "global_batch": 2,
               "steps": 4, "checkpoint_every": 2,
               "checkpoint_dir": str(tmp_path / "ck")}
    r1 = run_train_task(None, payload)
    assert r1["steps"] == 4 and r1["ran_steps"] == 4
    assert r1["resumed_from"] == 0 and r1["checkpoint"]["step"] == 4
    # mean host ms per phase of the step, for the worker's execute span
    assert set(r1["phase_ms"]) == {"step", "batch", "dispatch", "sync", "log",
                                   "checkpoint"}
    # redelivery after the checkpoint committed: nothing re-runs
    r2 = run_train_task(None, dict(payload))
    assert r2["steps"] == 4 and r2["ran_steps"] == 0
    assert r2["resumed_from"] == 4
    # a later stage raising the target runs only the delta
    r3 = run_train_task(None, {**payload, "steps": 6})
    assert r3["steps"] == 6 and r3["ran_steps"] == 2
    assert r3["resumed_from"] == 4
