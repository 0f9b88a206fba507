"""The host loops' spans and counters (``repro.runtime.telemetry``): a
reduced ``Trainer`` and ``Server`` run under the profiler on the CPU, and the
trace's host plane must hold their ``repro.*`` spans, nested as the loops
run; the counters must count what the loops do, and compiles must land
under the span and step that caused them."""
import glob

import jax
import pytest
from jax.profiler import ProfileData

from repro.runtime.serve_loop import Server, ServeJobConfig
from repro.runtime.telemetry import COMPILES, LoopSpans, StepTimer
from repro.runtime.train_loop import Trainer, TrainJobConfig

TRAIN = dict(arch="qwen3-0.6b", steps=3, seq_len=16, global_batch=2, seed=5)
PHASES = ("batch", "dispatch", "sync", "log")


def host_events(trace_dir, prefix):
    """(name, t0, t1, thread, stats) of the host events named ``prefix*``."""
    path = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(prefix):
                    out.append((e.name, e.start_ns, e.start_ns + e.duration_ns,
                                i, dict(e.stats)))
    return out


def traced(trace_dir, fn):
    jax.profiler.start_trace(str(trace_dir))
    try:
        return fn()
    finally:
        jax.profiler.stop_trace()


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    """A reduced trainer's 3 steps under the profiler, one at a time, with
    its counts (steps, tokens, arrays brought to the host) read after
    each."""
    d = tmp_path_factory.mktemp("train_trace")
    tr = Trainer(TrainJobConfig(**TRAIN))
    per_step = []

    def run():
        for _ in range(3):
            tr.step_once()
            per_step.append((tr.step, tr.timer.steps,
                             tr.timer.steps * tr.timer.tokens_per_step,
                             tr.spans.host_transfers))
    traced(d, run)
    return tr, per_step, host_events(d, "repro.")


def test_each_step_holds_its_phases_once(trained):
    _, _, events = trained
    steps = sorted((e for e in events if e[0] == "repro.train.step"),
                   key=lambda e: e[1])
    assert [int(e[4]["step_num"]) for e in steps] == [0, 1, 2]
    for _, a, b, thread, _ in steps:
        inside = [e[0] for e in events if e[3] == thread and a <= e[1]
                  and e[2] <= b and e[0] != "repro.train.step"]
        assert sorted(inside) == sorted(f"repro.train.{p}" for p in PHASES)
    # every train span lies inside some step
    for name, a, b, thread, _ in events:
        if name.startswith("repro.train."):
            assert any(s[3] == thread and s[1] <= a and b <= s[2]
                       for s in steps), name


def test_counters_count_steps_tokens_and_transfers(trained):
    tr, per_step, _ = trained
    tokens = TRAIN["global_batch"] * TRAIN["seq_len"]
    assert per_step == [(n, n, n * tokens, 5 * n) for n in (1, 2, 3)]
    # the five metrics brought to the host in each sync
    assert len(tr.metrics.latest()) - 1 == 5
    assert set(tr.timer.phases) == {"step", *PHASES}
    assert all(n == 3 for n, _ in tr.timer.phases.values())
    snap = tr.timer.snapshot()
    assert set(snap["phase_ms"]) == {"step", *PHASES}
    assert snap["phase_ms"]["step"] >= snap["phase_ms"]["sync"] > 0
    assert tr.timer.steps == 3 and tr.timer.ema_s > 0


def test_compiles_land_in_the_first_step(trained):
    tr, _, _ = trained
    assert tr.spans.compiles, "the step program compiled under no span"
    assert {step for _, step in tr.spans.compiles} == {0}
    assert tr.spans.compiles["repro.train.dispatch", 0] >= 1


def test_loss_sequence_unchanged(trained):
    """The spans leave the numbers alone: the same steps driven by hand,
    without ``step_once``, give the same losses."""
    tr, _, _ = trained
    ref = Trainer(TrainJobConfig(**TRAIN))
    losses = []
    for s in range(3):
        ref.state, m = ref.step_fn(ref.state, ref._sync_batch(s))
        losses.append(float(m["loss"]))
    assert tr.metrics.series("loss") == losses


def test_local_sgd_round_is_one_step_span():
    """In local_sgd mode a round of ``inner_steps`` optimizer steps is one
    ``step_once``: one span of each phase, and its steps counted."""
    from repro.optim.local_sgd import LocalSGDConfig
    tr = Trainer(TrainJobConfig(**dict(TRAIN, global_batch=4),
                                mode="local_sgd",
                                local_sgd=LocalSGDConfig(inner_steps=2)))
    m = tr.step_once()
    assert tr.step == 2
    assert tr.spans.host_transfers == len(m)
    assert {p: n for p, (n, _) in tr.timer.phases.items()} == {
        "step": 1, "batch": 1, "dispatch": 1, "sync": 1, "log": 1}
    assert tr.spans.compiles["repro.train.dispatch", 0] >= 1


def test_checkpoint_spans(tmp_path):
    tr = Trainer(TrainJobConfig(**dict(TRAIN, steps=2, checkpoint_every=2),
                                checkpoint_dir=str(tmp_path / "ck")))
    traced(tmp_path / "trace", lambda: (tr.run(), tr.ckpt.wait(),
                                        tr.restore()))
    events = host_events(tmp_path / "trace", "repro.")
    names = [e[0] for e in events]
    # the due save inside step 1 and the restore; the write on its thread
    assert names.count("repro.train.checkpoint") == 2
    assert names.count("repro.ckpt.write") == 1
    (step1,) = [e for e in events if e[0] == "repro.train.step"
                and int(e[4]["step_num"]) == 1]
    (write,) = [e for e in events if e[0] == "repro.ckpt.write"]
    assert write[3] != step1[3] and write[1] >= step1[1]
    assert tr.timer.phases["checkpoint"][0] == 2
    assert tr.ckpt.spans.timer.phases["write"][0] == 1


def test_server_spans_counters_and_stamps(tmp_path):
    sv = Server(ServeJobConfig(arch="qwen3-0.6b", slots=2, max_len=32,
                               seed=3))
    prefill = "repro.serve.prefill"

    def prefill_compiles():
        return sum(n for (name, _), n in sv.spans.compiles.items()
                   if name == prefill)

    actives = []

    def serve(prompts):
        for p in prompts:
            sv.submit(p, max_new=3)
        while sv.pending():
            actives.append(sv.step())

    # a new prompt length compiles two programs under ``prefill``: the
    # prefill itself and the eager int32 conversion of the prompt's list
    traced(tmp_path, lambda: serve([[1, 2, 3, 4, 5], [6, 7, 8]]))
    n_traced = len(actives)
    assert prefill_compiles() == 2 * 2       # two new prompt lengths
    serve([[9, 9, 9, 9, 9]])                 # a length seen before
    assert prefill_compiles() == 2 * 2
    serve([[4, 4]])                          # a new one
    assert prefill_compiles() == 2 * 3
    assert {step for (name, step) in sv.spans.compiles
            if name == "repro.serve.decode"} == {0}

    # one array for each request's first token, one a step per active slot
    assert sv.spans.host_transfers == 4 + sum(actives)
    assert sum(len(r.generated) for r in sv.requests.values()) == 4 * 3
    for r in sv.requests.values():
        assert r.t_submit <= r.t_admit <= r.t_first

    events = host_events(tmp_path, "repro.serve.")
    steps = [e for e in events if e[0] == "repro.serve.step"]
    assert len(steps) == n_traced
    assert sorted(int(e[4]["length"]) for e in events
                  if e[0] == prefill) == [3, 5]
    for name in ("admit", "prefill", "splice", "sync", "decode"):
        assert any(e[0] == f"repro.serve.{name}" for e in events), name
    for name, a, b, thread, _ in events:
        assert any(s[3] == thread and s[1] <= a and b <= s[2]
                   for s in steps), name


def test_compile_counter_puts_compiles_under_the_open_span():
    """One listener for the process: a program load counts once in the
    process totals and, inside a span, under that span and its step."""
    spans = LoopSpans("probe", StepTimer())
    mark = COMPILES.mark()
    f = jax.jit(lambda x: x * 3 + 1)
    with spans.step(7):
        with spans.span("work", length=2):
            f(jax.numpy.ones(2)).block_until_ready()
        f(jax.numpy.ones(2)).block_until_ready()     # cached: no compile
    f(jax.numpy.ones(3)).block_until_ready()         # under no span
    got = COMPILES.since(mark)
    assert got["loads"] >= 2 and got["compiles"] >= 2
    assert spans.compiles["repro.probe.work", 7] >= 1
    assert ("repro.probe.step", 7) not in spans.compiles
    assert spans.timer.steps == 1 and spans.timer.phases["work"][0] == 1
