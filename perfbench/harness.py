"""One run of one cell: build the configuration's weights from the seed,
quantize them through the serving program's public path, serve the cell's
traffic through ``ServingEngine``'s paged scheduler for the measured
window, check the served tokens against the plain reference, and return
the result line.

Set-up (``setup_s``) runs from the start of the process to the opening of
the window: weights, quantization, engine, compilation (from JAX's
persistent cache after a checkout's first run) and bringing the scheduler
to steady state. The reference runs after the window and is not set-up.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import discover, traffic, weights, work
from perfbench.discover import BenchError

GIB = 2 ** 30


class CompileLog:
    """Backend compiles and their seconds, from JAX's monitoring events."""

    def __init__(self):
        self.n = 0
        self.secs = 0.0

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.secs += duration

    @contextlib.contextmanager
    def listening(self):
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        try:
            yield self
        finally:
            jax.monitoring.unregister_event_duration_listener(self._on_duration)


class TokenLog:
    """Host-clock arrival time of every served token, and of every prompt
    token admitted to the cache.

    The scheduler reports each committed token to its telemetry
    (``first_token`` / ``tokens_committed``), each prefill chunk it computed
    (``request_event(rid, "prefill_chunk", n=...)``) and the prompt tokens
    it took from the prefix cache (``request_admitted``) inside ``step``;
    the program has no per-token hook of its own, so this wraps those calls
    on the engine's telemetry object and stamps the tokens when ``step``
    returns, which is when the caller receives them. ``Client.check_stamps``
    refuses a run in which these calls no longer cover every token."""

    HOOKS = ("first_token", "tokens_committed", "request_event", "request_admitted")

    def __init__(self, tel):
        missing = [h for h in self.HOOKS if not callable(getattr(tel, h, None))]
        if missing:
            raise BenchError(f"the engine's telemetry has no {', '.join(missing)}: "
                             "the harness stamps tokens through these calls")
        self.clear()
        first, commit = tel.first_token, tel.tokens_committed
        event, admitted = tel.request_event, tel.request_admitted

        def first_token(rid):
            self.pending.append((rid, 1))
            first(rid)

        def tokens_committed(rid, n):
            self.pending.append((rid, n))
            commit(rid, n)

        def request_event(rid, name, **args):
            if name == "prefill_chunk":
                self.prompt_pending += args["n"]
            event(rid, name, **args)

        def request_admitted(rid, prefix_hit_tokens=0):
            self.prompt_pending += prefix_hit_tokens
            admitted(rid, prefix_hit_tokens=prefix_hit_tokens)

        tel.first_token, tel.tokens_committed = first_token, tokens_committed
        tel.request_event, tel.request_admitted = request_event, request_admitted

    def clear(self) -> None:
        self.pending: list[tuple[int, int]] = []
        self.times: dict[int, list[float]] = {}
        self.prompt_pending = 0
        self.prompt: list[tuple[float, int]] = []  # (stamp, prompt tokens admitted)

    def stamp(self, t: float) -> None:
        for rid, n in self.pending:
            self.times.setdefault(rid, []).extend([t] * n)
        self.pending.clear()
        if self.prompt_pending:
            self.prompt.append((t, self.prompt_pending))
            self.prompt_pending = 0

    @property
    def any_pending(self) -> bool:
        return bool(self.pending or self.prompt_pending)


class StepRecorder:
    """Keeps the per-row inputs (slot ids, positions) of every packed step
    dispatched while on, by reference: nothing is copied to the host until
    the window has closed."""

    def __init__(self, sched):
        self.calls: list = []
        self.on = False
        inner = getattr(sched, "_packed_fn", None)
        if inner is None:
            raise BenchError("the scheduler has no _packed_fn: the harness records "
                             "the traced steps' inputs through it")

        def packed(params, pools, bt, slot_ids, positions, ctx, tokens):
            if self.on:
                self.calls.append((slot_ids, positions))
            return inner(params, pools, bt, slot_ids, positions, ctx, tokens)

        sched._packed_fn = packed

    def fetch(self) -> list[tuple[np.ndarray, np.ndarray]]:
        return [(np.asarray(s), np.asarray(p)) for s, p in self.calls]


@dataclasses.dataclass
class Run:
    """What a per-layer metric reader gets: the configuration, the serving
    settings, the device peaks, the traced window's reduced trace and the
    inputs of the packed steps in it."""

    cfg: dict
    serve: dict
    peaks: dict
    trace: object = None  # perfbench.trace.Trace
    steps: list = dataclasses.field(default_factory=list)


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation; inf counts."""
    v = np.sort(np.asarray(values, np.float64))
    if not len(v):
        return float("nan")
    pos = (len(v) - 1) * q / 100.0
    lo, hi = int(np.floor(pos)), int(np.ceil(pos))
    if v[hi] == np.inf:
        return float("inf")
    return float(v[lo] + (v[hi] - v[lo]) * (pos - lo))


def build_engine(cfg: dict, serve: dict, seed: int):
    """(model, engine) serving ``cfg`` quantized under ``cfg["quant"]``."""
    from repro.core import quantize_model
    from repro.models.model import build
    from repro.serving.engine import ServeConfig, ServingEngine

    model = build(discover.model_config(cfg))
    spec = discover.quant_spec(cfg["quant"])
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params = weights.make_weights(shapes, cfg, seed)
    qparams = jax.block_until_ready(quantize_model(model, params, spec))
    del params
    sc = ServeConfig.from_spec(spec, cache_len=serve["cache_len"],
                               token_budget=serve["token_budget"],
                               block_size=serve["block_size"])
    return model, ServingEngine(model, qparams, sc, batch_slots=serve["slots"])


class Client:
    """Submits the cell's requests and steps the scheduler, open or closed loop."""

    def __init__(self, engine, reqs: list, mix: dict, log):
        self.sched = engine.scheduler
        self.tokens = TokenLog(engine.telemetry)
        self.reqs, self.mix, self.log = reqs, mix, log
        self.next = 0
        self.rid_of: dict[int, int] = {}  # scheduler rid -> request index
        self.sent: dict[int, float] = {}  # request index -> due (open) / submit time
        self.results: dict[int, list[int]] = {}
        self.t0 = None
        self.busy_steps = 0

    def _submit(self, i: int, t: float) -> None:
        rid = self.sched.submit(self.reqs[i].prompt, self.reqs[i].max_new)
        self.rid_of[rid] = i
        self.sent[i] = t

    def _feed(self, now: float) -> None:
        if self.mix["loop"] == "open":
            while self.next < len(self.reqs) and self.t0 + self.reqs[self.next].due <= now:
                self._submit(self.next, self.t0 + self.reqs[self.next].due)
                self.next += 1
        else:
            outstanding = len(self.sent) - len(self.results)
            while outstanding < self.mix["concurrency"] and self.next < len(self.reqs):
                self._submit(self.next, now)
                self.next += 1
                outstanding += 1
        if self.next == len(self.reqs):
            raise BenchError(f"traffic ran out after {len(self.reqs)} requests; "
                             "raise 'requests' in the traffic file")

    def step(self) -> None:
        self._feed(time.perf_counter())
        if self.sched.step(self.results) or self.tokens.any_pending:
            self.tokens.stamp(time.perf_counter())
            self.busy_steps += 1
            return
        # idle: nothing queued or running; wait for the next arrival
        if self.mix["loop"] == "open":
            wait = self.t0 + self.reqs[self.next].due - time.perf_counter()
            time.sleep(max(0.0, min(wait, 0.05)))

    def decoding(self) -> int:
        """Requests that have their first token and are not finished."""
        done = {self.rid_of[r] for r in self.results}
        return sum(1 for rid, i in self.rid_of.items()
                   if rid in self.tokens.times and i not in done)

    def warm(self) -> None:
        """Compiles the serving programs before the arrival clock starts:
        one throwaway request, then forgotten. Its prompt is a single block
        of token 0, which no request of the traffic starts with, so it
        leaves nothing in a prefix cache that the traffic could hit."""
        self.sched.submit([0] * 16, 2)
        while self.sched.step({}):
            pass
        self.tokens.clear()

    def start(self) -> None:
        self.t0 = time.perf_counter()

    def run_until(self, cond) -> None:
        while not cond():
            self.step()

    def check_stamps(self) -> None:
        """Every token of every finished request was stamped: the telemetry
        calls that ``TokenLog`` wraps still report each committed token."""
        for rid, out in self.results.items():
            n = len(self.tokens.times.get(rid, ()))
            if n != len(out):
                raise BenchError(f"request {self.rid_of[rid]} served {len(out)} tokens and "
                                 f"the telemetry calls reported {n}")


def window_metrics(drv: Client, t_open: float, t_close: float, mix: dict) -> dict:
    """End-to-end numbers of the window from the host clock."""
    times = {drv.rid_of[rid]: ts for rid, ts in drv.tokens.times.items()}
    gaps = [b - a for ts in times.values() for a, b in zip(ts, ts[1:])
            if t_open <= b < t_close]
    out = {"itl_p95_ms": percentile(gaps, 95) * 1e3, "n_gaps": len(gaps)}
    if mix["loop"] == "open":
        due = [i for i, t in drv.sent.items() if t_open <= t < t_close]
        ttft = [times[i][0] - drv.sent[i] if i in times else np.inf for i in due]
        out["ttft_p90_s"] = percentile(ttft, 90)
        out["n_due"] = len(due)
        out["n_missing"] = int(sum(np.isinf(ttft)))
    # prompt tokens admitted to the cache (computed or taken from the prefix
    # cache) plus output tokens received, in the window
    n_out = sum(1 for ts in times.values() for t in ts if t_open <= t < t_close)
    n_prompt = sum(n for t, n in drv.tokens.prompt if t_open <= t < t_close)
    out["tokens_per_s"] = (n_out + n_prompt) / (t_close - t_open)
    out["n_tokens"] = n_out + n_prompt
    return out


def gap_numbers(g: np.ndarray, cfg: dict) -> dict:
    """What the check reads from the gaps of the served tokens: the share
    of them that lie more than ``cfg["wide_gap"]`` reference standard
    deviations below the reference's best (compared with its limit), and
    the widest and the mean gap (reported)."""
    if not g.size:
        return {"wide_gap_share": 1.0, "gap_max": float("inf"), "gap_mean": float("inf"),
                "n": 0, "all": g}
    return {"wide_gap_share": float((g > cfg["wide_gap"]).mean()), "gap_max": float(g.max()),
            "gap_mean": float(g.mean()), "n": int(g.size), "all": g}


def within(checks: dict, limits: dict) -> bool:
    return all(checks[k] <= v for k, v in limits.items())


def check_outputs(drv: Client, cfg: dict, mix: dict, seed: int, repo: Path,
                  keep: dict | None = None) -> dict:
    """Served tokens of a seeded sample of finished requests (the longest
    among them) against the plain reference: at every served position, by
    how many of the reference's logit standard deviations the served
    token's logit lies below the reference's best."""
    done = sorted(drv.rid_of[r] for r in drv.results)
    served = {drv.rid_of[r]: out for r, out in drv.results.items()}
    if not done:
        return gap_numbers(np.zeros(0), cfg)
    longest = max(done, key=lambda i: len(drv.reqs[i].prompt) + drv.reqs[i].max_new)
    rng = traffic.seed_rng(seed + 1)
    rest = [i for i in done if i != longest]
    pick = [longest] + list(rng.permutation(rest)[: mix["check_requests"] - 1])
    seqs = [drv.reqs[i].prompt + served[i] for i in pick]
    starts = [len(drv.reqs[i].prompt) - 1 for i in pick]
    if keep is not None:
        keep.update(cfg=cfg, seqs=seqs, starts=starts)
    return reference_gaps(cfg, seed, seqs, starts, repo)


def reference_gaps(cfg: dict, seed: int, seqs: list, starts: list, repo: Path,
                   picks: list | None = None) -> dict:
    """Gaps of the tokens in ``seqs`` after ``starts`` (or of ``picks``,
    the tokens another path puts first there) under the reference."""
    from repro.models.model import build

    ref = discover.reference(cfg["reference"], repo / "perfbench")
    model = build(discover.model_config(cfg))
    params = weights.make_weights(jax.eval_shape(model.init, jax.random.PRNGKey(0)),
                                  cfg, seed)
    pad = 256 * -(-max(map(len, seqs)) // 256)
    toks = np.zeros((len(seqs), pad), np.int32)
    tgt = np.full((len(seqs), pad), -1, np.int32)  # token judged at each position
    for b, (s, st) in enumerate(zip(seqs, starts)):
        toks[b, : len(s)] = s
        nxt = s[st + 1:] if picks is None else picks[b]
        tgt[b, st: st + len(nxt)] = nxt

    @jax.jit
    def gaps(params, toks, tgt):
        def one(args):
            t, g = args
            lg = ref.logits(params, cfg, t)
            chosen = jnp.take_along_axis(lg, jnp.maximum(g, 0)[:, None], axis=1)[:, 0]
            return (lg.max(-1) - chosen) / lg.std(-1)
        return jax.lax.map(one, (toks, tgt))

    return gap_numbers(np.asarray(gaps(params, jnp.asarray(toks), jnp.asarray(tgt)))[tgt >= 0],
                       cfg)


def device_info(dev, n_chips: int) -> dict:
    stats = dev.memory_stats() or {}
    return {"platform": dev.platform, "kind": dev.device_kind, "count": n_chips,
            "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}


def run_cell(workload: str, seed: int, seconds: int, trace_on: bool, *,
             repo: Path = discover.REPO, require_chip: bool = True,
             peaks: dict | None = None, t_start: float | None = None,
             log=print, keep: dict | None = None) -> dict:
    """One run; returns the result line as a dict (``checks`` last).
    ``keep``, when given, receives the configuration and the checked sample
    (sequences and where their served tokens start)."""
    t_start = time.perf_counter() if t_start is None else t_start
    bench = discover.load_benchmark(repo)
    cell = discover.workload(bench, workload)
    cfg = discover.config(bench, cell["config"], repo)
    mix = discover.traffic(cell["traffic"], repo / "perfbench")
    devs = jax.devices()
    dev = devs[0]
    if require_chip and dev.platform != "tpu":
        raise BenchError(f"no TPU: JAX found {dev.platform!r}; this benchmark "
                         "measures only on the chip")
    if len(devs) < cell["chips"]:
        raise BenchError(f"cell {workload} needs {cell['chips']} chips, found {len(devs)}")
    peaks = work.peaks(dev.device_kind) if peaks is None else peaks
    serve = mix["serve"]
    compiles = CompileLog()
    with compiles.listening():
        model, engine = build_engine(cfg, serve, seed)
        reqs = traffic.generate(mix, seed, cfg["vocab_size"])
        drv = Client(engine, reqs, mix, log)
        recorder = StepRecorder(engine.scheduler) if trace_on else None
        drv.warm()
        drv.start()
        if mix["loop"] == "open":
            warm_end = drv.t0 + mix["warmup_s"]
            drv.run_until(lambda: time.perf_counter() >= warm_end and drv.tokens.times)
        else:
            drv.run_until(lambda: drv.decoding() >= serve["slots"])
        n_setup_compiles = compiles.n
        t_open = time.perf_counter()
        setup_s = t_open - t_start
        t_close = t_open + seconds
        tracer = _Tracer(repo, mix, recorder) if trace_on else None
        if tracer:
            tracer.start()

        def closed():
            if tracer:
                tracer.poll()
            return time.perf_counter() >= t_close

        drv.run_until(closed)
        t_end = time.perf_counter()
        window_compiles = compiles.n - n_setup_compiles
        steps_in_window = drv.busy_steps
        hbm = (dev.memory_stats() or {}).get("bytes_in_use", 0)
        # finish what the window owes: first tokens of requests due in it,
        # and enough finished requests to check
        due = [i for i, t in drv.sent.items() if t_open <= t < t_close]
        deadline = t_end + mix["drain_s"]

        def drained():
            firsts = {drv.rid_of[r] for r in drv.tokens.times}
            return time.perf_counter() >= deadline or (
                all(i in firsts for i in due) and len(drv.results) >= mix["check_requests"])

        drv.run_until(drained)
    drv.check_stamps()
    e2e = window_metrics(drv, t_open, t_close, mix)
    info = device_info(dev, cell["chips"])
    log(f"window: {t_end - t_open:.3f} s, {steps_in_window} steps, "
        f"{window_compiles} compiles (setup: {n_setup_compiles} compiles, "
        f"{compiles.secs:.2f} s compiling), bytes in use {hbm}, "
        f"peak {info['memory_peak_bytes']}, {e2e}")
    if window_compiles:
        raise BenchError(f"{window_compiles} compiles inside the measured window")
    run = Run(cfg=cfg, serve=serve, peaks=peaks)
    if tracer:
        run.trace, run.steps = tracer.finish()
        if not run.steps:
            raise BenchError("no packed step went through the scheduler's _packed_fn "
                             "while the trace ran")
        info["busy_s"] = run.trace.busy_s
        info["window_s"] = run.trace.window_s
    del engine, drv.sched
    gc.collect()
    t_ref = time.perf_counter()
    checks = check_outputs(drv, cfg, mix, seed, repo, keep)
    log(f"reference over {checks['n']} served tokens: {time.perf_counter() - t_ref:.2f} s; "
        f"widest gap {checks['gap_max']!r}, mean gap {checks['gap_mean']!r}, share over "
        + ", ".join(f"{t}: {float((checks['all'] > t).mean())!r}" for t in (0.8, 1.0, 1.2)))
    limits = cfg["limits"]
    ok = within(checks, limits)
    failed = sum(1 for i, out in ((drv.rid_of[r], o) for r, o in drv.results.items())
                 if len(out) != reqs[i].max_new or not all(0 <= t < cfg["vocab_size"] for t in out))
    metrics = {}
    if trace_on:
        for m in discover.cell_metrics(bench, workload, "per_layer"):
            v = discover.metric_reader(m["name"], repo / "perfbench")(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        values = dict(e2e, setup_s=setup_s, hbm_in_use_gib=hbm / GIB)
        for m in discover.cell_metrics(bench, workload, "end_to_end"):
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    line = {"correct": bool(ok and failed == 0), "attempted": len(drv.results),
            "failed": failed, "metrics": metrics, "device": info}
    if trace_on:
        line["breakdown"] = run.trace.breakdown()
    line["checks"] = {k: {"value": checks[k], "limit": v} for k, v in limits.items()}
    if keep is not None:
        keep["gaps"] = checks["all"].tolist()
    return line


class _Tracer:
    """Profiles the first ``trace_s`` seconds of the window."""

    def __init__(self, repo: Path, mix: dict, recorder: StepRecorder):
        self.dir = repo / "perfbench" / ".trace"
        self.secs = mix["trace_s"]
        self.recorder = recorder
        self.t_stop = None

    def start(self) -> None:
        import shutil

        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        # no Python function events: they slow the host loop, and only the
        # device plane is read
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        self.recorder.on = True
        self.t_stop = time.perf_counter() + self.secs

    def poll(self) -> None:
        """Stops the trace once its time is up."""
        if not self.recorder.on or time.perf_counter() < self.t_stop:
            return
        self.recorder.on = False
        jax.profiler.stop_trace()

    def finish(self):
        from perfbench import trace

        if self.recorder.on:
            self.t_stop = 0.0
            self.poll()
        path = next(self.dir.rglob("*.xplane.pb"))
        return trace.Trace.load(path), self.recorder.fetch()
