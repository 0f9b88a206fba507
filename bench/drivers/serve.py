"""Open-loop serving: requests arrive on a fixed schedule and go through
``Server.submit`` and ``Server.step`` of the server that the plane's
``ServerCache.get`` hands a serve task, loaded with the benchmark's weights.

The schedule: ``round(rate_per_s * seconds)`` requests at arrival times drawn
once from ``work_seed`` (a Poisson process conditioned on its count: sorted
uniform times over the window). Each prompt length is drawn from a
log-normal (``prompt``: ``median``, ``sigma``) and put in the nearest bin of
the fixed table ``prompt["lengths"]`` (bins split at geometric midpoints;
a longer prompt is cut to the longest length); each output length from a
log-normal (``output``: ``median``, ``sigma``, clipped to ``min``..``max``
and to what ``max_len`` leaves). All of that comes from ``work_seed``, so
every seed gets the same requests at the same times; the seed draws the
prompts' token ids (and the weights). Decoding is greedy, with no end token.

Times are the host's (``time.perf_counter``), all read at one point: the
end of the ``Server.step`` call that produced a token, when the caller can
first see it in ``server.requests[rid].generated``.
  * TTFT: a request's due time to the end of the step that produced its
    first token. Requests due in the window are followed for ``grace_s``
    after it; one with no first token by then has failed, and its TTFT is
    taken as the time to the end of the grace period, in the tail.
  * ITL: every gap between consecutive tokens of a request; two tokens
    that one step produced (the prefill's and the first decode's) are 0
    apart. Gaps ending after the window are left out.
  * queue wait: due time to the start of the step that produced the
    request's first token (the step that admitted it).
The load generator is part of the same loop: before each step it submits
every request already due, and ``late`` is how long after its due time each
request was submitted.

The check: once the window and grace period are over, ``check.requests``
finished requests, each from a different slot (the one that served the
longest output first, the others drawn from the seed), are run through the
float32 reference over their prompt and served tokens. Of each, the first
and last ``check.tokens_per_request / 2`` served tokens are compared: the
number is the widest gap by which a served token's reference logit lies
below the reference's best at that position.
"""
from __future__ import annotations

import gc
import math
import time
from collections import deque
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench import arch as A
from bench import flops as FL
from bench import harness as H
from bench import reference as R
from bench import trace_reduce
from bench import weights as W

# the Server's prefill is a jitted lambda
PREFILL_PROGRAM = r"^jit__lambda\("
DECODE_PROGRAM = r"^jit_decode_step\("


# ------------------------------------------------------------------ schedule
def prompt_weights(prompt: dict) -> np.ndarray:
    """Each table length's share of the log-normal: the mass between the
    geometric midpoints to its neighbours; the ends take the tails."""
    lengths = np.asarray(prompt["lengths"], dtype=float)
    edges = np.sqrt(lengths[1:] * lengths[:-1])
    z = (np.log(edges) - math.log(prompt["median"])) / prompt["sigma"]
    cdf = np.concatenate([[0.0], [0.5 * (1 + math.erf(x / math.sqrt(2)))
                                  for x in z], [1.0]])
    return np.diff(cdf)


def schedule(traffic: dict, seed: int, seconds: float, vocab: int) -> List[dict]:
    """The window's requests: ``due`` (seconds from the window's start),
    ``prompt`` (token ids) and ``max_new``."""
    n = max(1, round(traffic["rate_per_s"] * seconds))
    work = np.random.default_rng(traffic["work_seed"])
    lengths = np.asarray(traffic["prompt"]["lengths"])
    prompt_lens = work.choice(lengths, size=n,
                              p=prompt_weights(traffic["prompt"]))
    out = traffic["output"]
    outs = np.exp(math.log(out["median"])
                  + out["sigma"] * work.standard_normal(n))
    outs = np.clip(np.round(outs), out["min"], out["max"]).astype(int)
    dues = np.sort(work.uniform(0.0, seconds, n))
    # the longest output that still fits: the server stops a request at
    # max_len - 1 positions
    outs = np.minimum(outs, traffic["max_len"] - 2 - prompt_lens)
    rng = np.random.default_rng(seed)
    return [{"due": float(due), "max_new": int(m),
             "prompt": rng.integers(0, vocab, int(p)).tolist()}
            for due, p, m in zip(dues, prompt_lens, outs)]


def warmup_requests(traffic: dict, slots: int, vocab: int) -> List[dict]:
    """Every prompt length of the table, and enough requests to fill every
    slot, each for two tokens: every program and host path the window uses."""
    lengths = list(traffic["prompt"]["lengths"])
    n = max(slots, len(lengths))
    return [{"prompt": [(7 * i + j) % vocab for j in
                        range(lengths[i % len(lengths)])], "max_new": 2}
            for i in range(n)]


def percentile(values, q: float) -> float:
    """Nearest-rank percentile over every value (q in 0..100)."""
    v = sorted(values)
    if not v:
        return float("nan")
    return v[max(0, math.ceil(q / 100 * len(v)) - 1)]


def summarize(recs: List[dict], t_end: float, t_stop: float) -> dict:
    """Every request's TTFT (ms from its due time; one with no first token
    has failed and counts to ``t_stop``), every token gap that ends by
    ``t_end``, queue waits and how late each request was submitted."""
    ms = lambda s: s * 1e3
    return {
        "sent": len(recs),
        "failed": sum(r["first"] is None for r in recs),
        "ttft": [ms((t_stop if r["first"] is None else r["first"]) - r["due"])
                 for r in recs],
        "itl": [ms(b - a) for r in recs
                for a, b in zip(r["times"], r["times"][1:]) if b <= t_end],
        "waits": [ms(r["admit_step"] - r["due"]) for r in recs
                  if r["admit_step"] is not None],
        "late": [ms(r["submitted"] - r["due"]) for r in recs]}


# ------------------------------------------------------------------- driving
class Tracker:
    """Token times of every request, read after each ``Server.step`` from
    the public ``server.requests``: a token is stamped at the end of the
    step that produced it."""

    def __init__(self, server, spans: H.Spans):
        self.srv = server
        self.recs: Dict[str, dict] = {}
        self.live: Dict[str, None] = {}      # in submission order
        self.prefills: List[tuple] = []     # (t, prompt_len)
        self.decodes: List[tuple] = []      # (t, contexts of decoded slots)
        self._step = spans.wrap("serve.step", server.step)

    def submit(self, req: dict, due: float) -> None:
        rid = self.srv.submit(req["prompt"], max_new=req["max_new"])
        self.recs[rid] = {"due": due, "submitted": time.perf_counter(),
                          "first": None, "admit_step": None, "times": [],
                          "slot": None, "prompt": req["prompt"]}
        self.live[rid] = None

    def step(self) -> None:
        start = time.perf_counter()
        self._step()
        now = time.perf_counter()
        slot_of = {r.req_id: i for i, r in enumerate(self.srv.slots) if r}
        contexts = []
        for rid in list(self.live):
            req, rec = self.srv.requests[rid], self.recs[rid]
            new = len(req.generated) - len(rec["times"])
            if new and rec["first"] is None:
                rec["first"], rec["admit_step"] = now, start
                rec["slot"] = slot_of.get(rid)
                self.prefills.append((now, len(req.prompt)))
                new -= 1                    # the prefill's token
            if new:                         # one token from the decode step
                contexts.append(len(req.prompt) + len(req.generated) - 1)
            rec["times"].extend([now] * (len(req.generated)
                                         - len(rec["times"])))
            if req.done:
                del self.live[rid]
        if contexts:
            self.decodes.append((now, contexts))


def build(run: H.Run):
    from repro.launch.steps import named
    from repro.runtime.serve_loop import ServeJobConfig
    from repro.runtime.step_cache import ServerCache
    name, tr = run.cell["config"], run.traffic
    name = A.register(name, run.config).name
    arch = A.arch(run.config)
    job = ServeJobConfig(arch=name, reduced=False, slots=tr["slots"],
                         max_len=tr["max_len"], greedy=True, eos_id=None,
                         seed=run.seed % (1 << 31))
    srv = ServerCache(1).get(job)
    srv.params = srv._init_params = None     # free the program's own draw
    gc.collect()
    dtype = jnp.dtype(run.config["torch_dtype"])
    make = jax.jit(lambda k: W.to_program(W.all_weights(arch, k, dtype)),
                   out_shardings=named(srv.model.plan.mesh,
                                       srv.model.param_specs()))
    srv.params = srv._init_params = make(W.seed_key(run.seed))
    return srv, arch


def drain(tracker: Tracker) -> None:
    while tracker.srv.pending():
        tracker.step()


def prepare(run: H.Run):
    """Build the server with the benchmark's weights and warm up every
    program and host path the traffic uses."""
    with run.spans.span("setup.build"):
        srv, arch = build(run)
    tracker = Tracker(srv, run.spans)
    with run.spans.span("setup.warmup"):
        for r in warmup_requests(run.traffic, run.traffic["slots"],
                                 arch["vocab_size"]):
            tracker.submit(r, time.perf_counter())
        drain(tracker)
    tracker.recs.clear()
    tracker.prefills.clear()
    tracker.decodes.clear()
    return srv, arch, tracker


def drive(tracker: Tracker, reqs: List[dict], seconds: float,
          traced: H.TracedWindow, trace_start: float = 0.0,
          trace_seconds: float = 0.0) -> tuple:
    """The window: submit each request when due, step while anything is
    pending; requests still unsent at the end are sent then. Returns the
    window's (start, end)."""
    srv = tracker.srv
    pending = deque(reqs)
    t0 = time.perf_counter()
    t_end = t0 + seconds
    try:
        while True:
            now = time.perf_counter()
            if now >= t_end:
                break
            if now - t0 >= trace_start:
                traced.start()
            if traced.active and now - traced.t0 >= trace_seconds:
                traced.stop()
            while pending and t0 + pending[0]["due"] <= now:
                r = pending.popleft()
                tracker.submit(r, t0 + r["due"])
            if srv.pending():
                tracker.step()
            elif pending:
                time.sleep(max(0.0, min(t0 + pending[0]["due"], t_end)
                               - time.perf_counter()))
            else:
                time.sleep(max(0.0, t_end - time.perf_counter()))
    finally:
        traced.stop()
    # a step that ran past the window's end leaves requests due inside it
    # unsent: they count, from their due time, like every other
    for r in pending:
        tracker.submit(r, t0 + r["due"])
    return t0, t_end


def follow(tracker: Tracker, grace_s: float) -> float:
    """No new arrivals; step until every request has a first token or the
    grace period is over. Returns when it stopped."""
    t_grace = time.perf_counter() + grace_s
    while (any(r["first"] is None for r in tracker.recs.values())
           and time.perf_counter() < t_grace):
        tracker.step()
    return time.perf_counter()


def measure(run: H.Run) -> dict:
    """Set-up, the window and the grace period; the server is freed before
    this returns. Gives the end-to-end numbers, the counts, the per-layer
    observations and the sample the check compares."""
    tr = run.traffic
    srv, arch, tracker = prepare(run)
    reqs = schedule(tr, run.seed, run.seconds, arch["vocab_size"])
    setup_s = time.perf_counter() - run.t_process
    run.note(f"setup: {setup_s:.3f} s; {run.compile_log.describe()}")

    mark = run.compile_log.mark()
    traced = H.TracedWindow(run.trace)
    traced.open()
    t0, t_end = drive(tracker, reqs, run.seconds, traced,
                      tr.get("trace_start_s", 0.0), tr.get("trace_seconds", 0))
    traced.close()
    in_window = run.compile_log.since(mark)
    t_stop = follow(tracker, tr["grace_s"])
    peak = H.memory_peak_bytes(run.cell["chips"])

    st = summarize(list(tracker.recs.values()), t_end, t_stop)
    done = [rid for rid in tracker.recs if srv.requests[rid].done]
    run.note(f"window: {run.seconds:.3f} s; requests sent {st['sent']}, "
             f"first token {st['sent'] - st['failed']}, failed "
             f"{st['failed']}, finished {len(done)}; {len(st['itl'])} token "
             f"gaps; {in_window['loads']} programs loaded in the window "
             f"({in_window['compiles']} compiled); generator late p50 "
             f"{percentile(st['late'], 50):.3f} ms max "
             f"{max(st['late']):.3f} ms; queue wait p90 "
             f"{percentile(st['waits'], 90):.3f} ms; peak HBM {peak} B")
    observed = {}
    if run.trace:
        observed = traced_observations(run, traced, tracker, arch)
        observed["queue_wait_p90_ms"] = percentile(st["waits"], 90)
    sample = check_sample(tracker, srv, done, run.seed, tr["check"])
    del tracker
    srv.params = srv._init_params = srv.cache = None
    del srv
    gc.collect()
    return {"arch": arch, "sample": sample, "observed": observed,
            "peak": peak, "attempted": st["sent"], "failed": st["failed"],
            "metrics": {"ttft_p90_ms": percentile(st["ttft"], 90),
                        "itl_p95_ms": percentile(st["itl"], 95),
                        "setup_s": setup_s}}


def run(run: H.Run) -> H.Outcome:
    tr = run.traffic
    m = measure(run)
    t_ref = time.perf_counter()
    gap, n_tok = served_gap(m["arch"], run.seed, m["sample"], tr["max_len"],
                            jnp.dtype(run.config["torch_dtype"]))
    run.note(f"reference: {time.perf_counter() - t_ref:.3f} s over "
             f"{len(m['sample'])} requests, {n_tok} served tokens")
    checks = [H.check("served_logit_gap", gap,
                      tr["check"]["limits"]["served_logit_gap"])]
    return H.Outcome(
        metrics=m["metrics"],
        attempted=m["attempted"], failed=m["failed"], checks=checks,
        observed=m["observed"], memory_peak_bytes=m["peak"])


# --------------------------------------------------------------------- check
def check_sample(tracker: Tracker, srv, done: List[str], seed: int,
                 check: dict) -> List[tuple]:
    """(prompt, served tokens, indices compared) of ``check["requests"]``
    finished requests from different slots: the one with the longest output,
    then others in an order drawn from the seed (from slots already taken
    only where too few slots served). Of each, the first and last
    ``check["tokens_per_request"] // 2`` served tokens are compared."""
    if not done:
        return []
    gen = {rid: list(srv.requests[rid].generated) for rid in done}
    longest = max(done, key=lambda rid: len(gen[rid]))
    rest = [rid for rid in done if rid != longest]
    order = np.random.default_rng(seed).permutation(len(rest))
    candidates = [longest] + [rest[i] for i in order]
    picked, slots = [], set()
    for rid in candidates:
        slot = tracker.recs[rid]["slot"]
        if len(picked) < check["requests"] and slot not in slots:
            picked.append(rid)
            slots.add(slot)
    # fewer slots served than requests asked for: the rest in the same order
    picked += [rid for rid in candidates
               if rid not in picked][:check["requests"] - len(picked)]
    half = check["tokens_per_request"] // 2
    out = []
    for rid in picked:
        n = len(gen[rid])
        js = sorted(set(range(min(half, n))) | set(range(max(0, n - half), n)))
        out.append((tracker.recs[rid]["prompt"], gen[rid], js))
    return out


def positions(sample: List[tuple]):
    """(rows, positions, served tokens) index arrays of every compared
    token: token j of a request is read from the logits at its prompt's
    last position plus j."""
    idx = [(i, len(prompt) - 1 + j, gen[j])
           for i, (prompt, gen, js) in enumerate(sample) for j in js]
    return tuple(jnp.asarray(a, jnp.int32) for a in zip(*idx))


def reference_logits(arch: dict, seed: int, sample: List[tuple], max_len: int,
                     dtype, compute_dtype=None):
    """The reference's logits at every served position [tokens, V], over
    each sampled request's prompt and served tokens padded to ``max_len``
    (padding comes after, so under the causal mask it changes nothing
    before it)."""
    rows = np.zeros((len(sample), max_len), np.int32)
    for i, (prompt, gen, _) in enumerate(sample):
        seq = prompt + gen[:-1]
        rows[i, :len(seq)] = seq
    r, p, _ = positions(sample)
    top = W.reference_top(arch, seed, dtype)
    return R.served_logits(arch, top, W.reference_layer(arch, seed, dtype),
                           jnp.asarray(rows), compute_dtype, picks=(r, p))


def served_gap(arch: dict, seed: int, sample: List[tuple], max_len: int,
               dtype) -> tuple:
    """Widest gap between the reference's best logit and its logit of the
    served token, over every compared served token."""
    if not sample:
        return None, 0
    lg = reference_logits(arch, seed, sample, max_len, dtype)
    t = positions(sample)[2]
    gaps = jnp.max(lg, axis=-1) - jnp.take_along_axis(lg, t[:, None], -1)[:, 0]
    return float(jnp.max(gaps)), int(t.shape[0])


def control_gap(arch: dict, seed: int, sample: List[tuple], max_len: int,
                dtype, compute_dtype) -> float:
    """The same number for the reference at a lower precision put in the
    program's place: at each served position, the gap (in the float32
    reference) of the token that the lower precision puts first."""
    lg = reference_logits(arch, seed, sample, max_len, dtype)
    low = reference_logits(arch, seed, sample, max_len, dtype, compute_dtype)
    pick = jnp.argmax(low, axis=-1)
    gaps = (jnp.max(lg, axis=-1)
            - jnp.take_along_axis(lg, pick[:, None], -1)[:, 0])
    return float(jnp.max(gaps))


# ---------------------------------------------------------------------- trace
def traced_observations(run: H.Run, traced: H.TracedWindow, tracker: Tracker,
                        arch: dict) -> dict:
    path = traced.path()
    try:
        summary = trace_reduce.reduce(
            path, programs={"prefill": PREFILL_PROGRAM,
                            "decode": DECODE_PROGRAM},
            kernels={"flash.prefill": (trace_reduce.FLASH_FORWARD_OP,
                                        PREFILL_PROGRAM)})
    finally:
        traced.cleanup()
    kind = H.device_info(run.cell["chips"])["kind"]
    prefill_lens = [n for t, n in tracker.prefills if traced.covers(t)]
    decode_ctx = [c for t, c in tracker.decodes if traced.covers(t) and c]
    H_, K, hd = (arch["num_attention_heads"], arch["num_key_value_heads"],
                 arch["head_dim"])
    L = arch["num_hidden_layers"]
    flash = [FL.flash_forward(1, H_, K, n, hd) for n in prefill_lens]
    run.note(f"trace: {len(prefill_lens)} prefills and {len(decode_ctx)} "
             f"decode steps traced; on the device "
             f"{summary['programs']['prefill']['count']} prefill and "
             f"{summary['programs']['decode']['count']} decode programs, "
             f"busy {summary['busy_s']:.4f} s of {summary['window_s']:.4f} s")
    return {"trace": summary, "peak": FL.peaks(kind),
            "work": {
                "prefill": {"flops": sum(FL.prefill(arch, n)
                                         for n in prefill_lens),
                            "calls": len(prefill_lens)},
                "decode": {"flops": sum(FL.decode_step(arch, c)
                                        for c in decode_ctx),
                           "calls": len(decode_ctx)},
                "flash.prefill": {"ops": L * sum(f["ops"] for f in flash),
                                  "bytes": L * sum(f["bytes"] for f in flash),
                                  "calls": L * len(flash)}}}
